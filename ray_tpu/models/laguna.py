"""Laguna (poolside; `model_type` `laguna`): a decoder whose attention layers
are of TWO kinds in the order `layer_types` lists, full and sliding-window
(3 sliding in every 4 at the published sizes), each kind with its own count of
query heads over the same key-value heads, its own rotation and its own cache;
a gate a head on the attention's output; a dense SwiGLU in the first
`num_dense_layers` layers and softmax-routed experts beside one shared expert
in the rest.

Built FROM the one layer and the one trunk (`llama.decoder_layer` in
`llama.decoder_trunk(runs=)`): three parameter stacks, `lead` (full attention,
dense MLP), `full` (full attention, experts) and `win` (window attention,
experts), run in the published order as `llama.Run`s, and ONE attention
strategy (`llama.gqa_attention`) built once a kind with what the kind differs
by. With y a token's normalised residual, no bias anywhere, H_l the layer's
query heads (from its `wq`'s width), 8 key-value heads, D lanes a head:

    q = y Wq [H_l, D], k = y Wk, v = y Wv [Hkv, D]; query head h reads
        key-value head h // (H_l / Hkv); RMSNorm over each head's D lanes of
        q and of k (`q_norm`, `k_norm`: one weight of D a layer), then
    rope by kind (`Rope`): the first `rotary_dim` lanes rotated (pairs (i, i +
        rotary_dim / 2), as `llama.rope`), the rest passed through; inverse
        frequencies `theta^(-2i / rotary_dim)`, YaRN-blended where the kind
        says (`kimi_k2.yarn_inv_freq`); cos and sin times `attention_factor`,
        so the rotated lanes of q and of k carry it;
    scores q k^T / sqrt(D); query i sees key j iff j <= i and, on a window
        layer, i - j < `window` (the query's own key among the `window`);
    gate: o[:, h, :] *= sigmoid(y Wg)[:, h], Wg [H, H_l] (`w_head_gate`);
        then the layer's `wo`
    MLP: `llama.dense_mlp`, or `moe.moe_mlp`: softmax over all experts, the
        top_k renormalised to sum to one, times `routed_scaling`, on the
        experts' outputs; one shared expert on every token, un-gated; this
        chip's SHARE of the experts (`experts_held`)

The cache is the pool's two CLASSES of page (`init_kv_pool`): the full layers
keep a row a token in pages of `block_size` tokens (`k`, `v`, [Lf, NB, BS,
row]: `llama.paged_attend`), the window layers a RING a sequence (`k_win`,
`v_win`, [Lw, NS, window, row]: `llama.window_attend`), position p at row p %
window, `window` rows whatever the sequence's length. `forward_paged` takes a
sequence as its block table, its length and `state_pages[b]`, the id of its
ring (0 the garbage ring, as block 0 is): never a slot, so a PD hand-off moves
a sequence as its token pages and its ring, and any free ring takes it.

What a ring cannot give: the window's rows at an EARLIER position. A cached
prefix (the suffix's first queries need the `window` rows before the prefix's
end, and a block's hash says nothing of them) and a rejected speculative
window (its later positions have overwritten rows the committed position
sees) are refused by the engine for a pool with such leaves
(`Model.sequence_leaves`; ROADMAP R2 has what is left).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, kimi_k2, llama, moe
from ray_tpu.ops.platform import target_platform

KINDS = {"full_attention": "full", "sliding_attention": "win"}   # `layer_types` -> a kind


class Rope(NamedTuple):
    """A kind of layer's rotation (`rope_parameters[kind]`): `theta`; how many
    of a head's lanes turn (`partial_rotary_factor` x head_dim, the first
    ones); YaRN's (factor, original length, beta_fast, beta_slow) or None;
    what multiplies cos and sin (`attention_factor`)."""
    theta: float = 10000.0
    rotary_dim: int | None = None     # None: every lane
    yarn: tuple | None = None
    attention_factor: float = 1.0

    def rotate(self, x, positions):
        """x [B, S, H, D] -> the same with its first `rotary_dim` lanes turned."""
        d = x.shape[-1]
        rot = d if self.rotary_dim is None else self.rotary_dim
        turned = llama.rope(x[..., :rot], positions, None,
                            kimi_k2.yarn_inv_freq(rot, self.theta, self.yarn))
        if self.attention_factor != 1.0:
            turned = (turned * self.attention_factor).astype(x.dtype)
        return turned if rot == d else jnp.concatenate([turned, x[..., rot:]], axis=-1)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    # hidden, key-value heads, head width, vocabulary, norm eps, dtype;
    # `num_heads` is the FULL layers' count, `intermediate_size` the DENSE
    # layers' width and `num_layers` every layer, of both kinds
    base: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    # router and experts; its base's `intermediate_size` is ONE expert's width
    experts: moe.MoEConfig = dataclasses.field(default_factory=moe.MoEConfig.tiny)
    layer_types: tuple = ("full_attention", "sliding_attention")
    window_heads: int = 6             # a window layer's query heads
    window: int = 8                   # `sliding_window`: keys a query sees, its own among them
    num_dense_layers: int = 1
    shared_width: int = 32            # `shared_expert_intermediate_size`
    rope_full: Rope = Rope()
    rope_window: Rope = Rope()
    # what the published config does not key (the configuration file's
    # `assumed`): (a) the gate's form and (c) the norm over each head's lanes of
    # q and of k are each one line of `llama.gqa_attention`, which a layer's
    # `w_head_gate` and `q_norm` / `k_norm` switch on; (b) is `experts
    # .score_func`; (d), the shared expert added as it is, with no gate of its
    # own, is `moe.moe_mlp`'s `moe/shared` for every family

    @property
    def vocab_size(self) -> int:   # what an engine asks of any configuration
        return self.base.vocab_size

    @property
    def kinds(self) -> list[str]:
        """Each layer's stack, in order: `lead`, `full` or `win`."""
        if len(self.layer_types) != self.base.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.base.num_layers} layers")
        kinds = [KINDS[t] for t in self.layer_types]
        if "win" in kinds[:self.num_dense_layers]:
            raise ValueError("a dense layer with window attention: the published stack "
                             "has none, and it would be a fourth parameter stack")
        return ["lead"] * self.num_dense_layers + kinds[self.num_dense_layers:]

    def heads(self, kind: str) -> int:
        return self.window_heads if kind == "win" else self.base.num_heads

    def cache_layers(self, leaf: str) -> int:
        """Layers that keep the cache `leaf` is of: `win`, or `full` (the
        leading dense layers are full layers)."""
        return sum((k == "win") == (leaf == "win") for k in self.kinds)

    @staticmethod
    def tiny() -> "LagunaConfig":  # for tests: every kind of run, small, groups of 2 and 3
        base = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=8,
            num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128, rms_eps=1e-6,
            tie_embeddings=False, dtype=jnp.float32, remat=False)
        experts = moe.MoEConfig(
            base=dataclasses.replace(base, intermediate_size=32), num_experts=8, top_k=2,
            norm_topk_prob=True, score_func="softmax", routed_scaling=2.5,
            experts_held=(0, 4))
        return LagunaConfig(
            base=base, experts=experts, window_heads=6, window=8, shared_width=48,
            layer_types=("full_attention", "sliding_attention", "sliding_attention",
                         "sliding_attention") * 2,
            rope_full=Rope(500000.0, 8, (16.0, 32, 32.0, 1.0), 0.1 * math.log(16.0) + 1.0),
            rope_window=Rope(10000.0))


# ---------------------------------------------------------------- params
_MLP_AXES = {
    "dense": {"w_gate": (None, "embed_fsdp", "mlp"), "w_up": (None, "embed_fsdp", "mlp"),
              "w_down": (None, "mlp", "embed_fsdp")},
    "moe": {"router": (None, None, None),
            "e_gate": (None, "expert", "embed_fsdp", "mlp"),
            "e_up": (None, "expert", "embed_fsdp", "mlp"),
            "e_down": (None, "expert", "mlp", "embed_fsdp"),
            "s_gate": (None, "embed_fsdp", "mlp"), "s_up": (None, "embed_fsdp", "mlp"),
            "s_down": (None, "mlp", "embed_fsdp")},
}


def logical_axes(cfg: LagunaConfig) -> dict:
    attn = {"attn_norm": (None, None), "mlp_norm": (None, None),
            "wq": (None, "embed_fsdp", "heads"), "wk": (None, "embed_fsdp", "kv_heads"),
            "wv": (None, "embed_fsdp", "kv_heads"), "wo": (None, "heads", "embed_fsdp"),
            "q_norm": (None, None), "k_norm": (None, None),
            "w_head_gate": (None, "embed_fsdp", "heads")}
    stacks = {kind: {**attn, **_MLP_AXES["dense" if kind == "lead" else "moe"]}
              for kind in set(cfg.kinds)}
    return {"embed": ("vocab", "embed_fsdp"), "lm_head": ("embed_fsdp", "vocab"),
            "final_norm": (None,), **stacks}


def init(cfg: LagunaConfig, key: jax.Array) -> dict:
    """Scaled-normal weights (`llama.init`'s: every matrix normal at `1 /
    sqrt(fan-in)`, norm weights one), one scan-stacked tree a kind of layer
    that occurs; the experts' leaves hold the experts held here alone. The
    residual is CONDITIONED as `xing4.init`'s (PERF.md section 6, PR 37): every
    sub-layer's output projection (`wo`, `w_down`, `e_down`, `s_down`) at `1 /
    sqrt(2 L)` of that and the embedding at unit rms, so the 2 L sub-layer
    outputs add up to the size of what they are added to."""
    base, ex = cfg.base, cfg.experts
    h, hd, dt, nkv = base.hidden_size, base.hd, base.dtype, base.num_kv_heads
    held = ex.experts_held[1] if ex.experts_held else ex.num_experts
    out_scale = (2 * base.num_layers) ** -0.5

    def dense(key, fan_in, *shape, scale=1.0):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (scale / math.sqrt(fan_in))).astype(dt)

    def stack(kind, key, n):
        nh = cfg.heads(kind)
        ks = jax.random.split(key, 12)
        layer = {"attn_norm": jnp.ones((n, h), jnp.float32),
                 "mlp_norm": jnp.ones((n, h), jnp.float32),
                 "wq": dense(ks[0], h, n, h, nh * hd), "wk": dense(ks[1], h, n, h, nkv * hd),
                 "wv": dense(ks[2], h, n, h, nkv * hd),
                 "wo": dense(ks[3], nh * hd, n, nh * hd, h, scale=out_scale),
                 "q_norm": jnp.ones((n, hd), jnp.float32),
                 "k_norm": jnp.ones((n, hd), jnp.float32),
                 "w_head_gate": dense(ks[4], h, n, h, nh)}
        if kind == "lead":
            m = base.intermediate_size
            return {**layer, "w_gate": dense(ks[5], h, n, h, m), "w_up": dense(ks[6], h, n, h, m),
                    "w_down": dense(ks[7], m, n, m, h, scale=out_scale)}
        m, ms = ex.base.intermediate_size, cfg.shared_width
        return {**layer, "router": dense(ks[5], h, n, h, ex.num_experts),
                "e_gate": dense(ks[6], h, n, held, h, m),
                "e_up": dense(ks[7], h, n, held, h, m),
                "e_down": dense(ks[8], m, n, held, m, h, scale=out_scale),
                "s_gate": dense(ks[9], h, n, h, ms), "s_up": dense(ks[10], h, n, h, ms),
                "s_down": dense(ks[11], ms, n, ms, h, scale=out_scale)}

    kinds = cfg.kinds
    names = sorted(set(kinds))
    k_embed, k_head, *k_stacks = jax.random.split(key, 2 + len(names))
    params = {"embed": jax.random.normal(k_embed, (base.vocab_size, h), jnp.float32).astype(dt),
              "lm_head": dense(k_head, h, h, base.vocab_size),
              "final_norm": jnp.ones((h,), jnp.float32)}
    for kind, k in zip(names, k_stacks):
        params[kind] = stack(kind, k, kinds.count(kind))
    return params


# ---------------------------------------------------------------- the stack
def _runs(cfg: LagunaConfig, params: dict, attends: dict, platform: str | None, live=None):
    """(the parameters with each expert stack's experts taken out, the stack
    as `llama.Run`s): consecutive layers of one kind are a run, a kind's
    layers count up through its parameter stack and a CACHE's through its
    leaves (`lead` and `full` share the full layers' `k` and `v`). `attends`
    is the cache strategy a kind of attention (`full`, `win`), and the ONE
    attention strategy is built around each with the kind's rotation; its
    head count is its weights'. The experts' weights stay where they are
    (`moe.unstacked_experts`): a kind's expert strategy closes over its own
    stack of them, and over `live` (bool [B, S], or None: every row), the rows
    that are no bucket's padding: only they are routed to the experts held."""
    attention = {"full": llama.gqa_attention(attends["full"], cfg.rope_full.rotate),
                 "win": llama.gqa_attention(attends["win"], cfg.rope_window.rotate)}
    for leaf, strategy in attention.items():
        # the layer's scope in a profile (`decoder_layer` opens it in place of
        # `attn`): the two kinds are told apart by name
        strategy.scope = f"attn_{leaf}/attn"
    params, mlps = dict(params), {"lead": llama.dense_mlp}
    for kind in set(cfg.kinds) - {"lead"}:
        params[kind], stacked = moe.unstacked_experts(params[kind])
        mlps[kind] = partial(moe.moe_mlp, cfg=cfg.experts, platform=platform, stacked=stacked,
                             live=live)
    runs, in_stack, in_cache = [], {}, {}
    for kind in cfg.kinds:
        leaf = "win" if kind == "win" else "full"
        if runs and runs[-1].stack == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(llama.Run(kind, in_stack.get(kind, 0), 1, attention[leaf], mlps[kind],
                                  cache_first=in_cache.get(leaf, 0)))
        in_stack[kind] = in_stack.get(kind, 0) + 1
        in_cache[leaf] = in_cache.get(leaf, 0) + 1
    return params, runs


def forward(params, tokens, cfg: LagunaConfig, attn_fn=None, platform: str | None = None):
    """Token ids [B, S] -> float32 logits [B, S, V] with no cache: every
    sequence from position 0. `attn_fn` is the FULL layers' (default: the
    causal `auto_attention`); the window layers' is `auto_attention` under
    the band."""
    if platform is None:
        platform = target_platform(tokens, params["embed"])
    keeps_none = lambda fn: lambda q, k, v, cache, index: (fn(q, k, v), None)
    auto = partial(llama.auto_attention, causal=True, platform=platform)
    attends = {"full": keeps_none(attn_fn or auto),
               "win": keeps_none(partial(auto, window=cfg.window))}
    params, runs = _runs(cfg, params, attends, platform)
    return llama.decoder_trunk(params, tokens, cfg.base, runs=runs)[0]


# ---------------------------------------------------------------- serving
SEQUENCE_LEAVES = ("k_win", "v_win")


def init_kv_pool(cfg: LagunaConfig, num_blocks: int, block_size: int,
                 num_sequences: int) -> dict:
    """The paged pool of a stack of two kinds of attention, pages on the
    second axis of every leaf and page 0 of each class its garbage page: `k`
    and `v` [Lf, num_blocks, block_size, Hkv * Dp] as `llama.init_kv_pool`
    lays them (a row a token), Lf the FULL layers alone; and, a page a
    SEQUENCE (`Model.sequence_leaves`), `k_win` and `v_win` [Lw, num_sequences,
    window, Hkv * Dp], the window layers' RINGS (`llama.window_attend`).
    Beside them `counters`: `moe_rows` and `moe_moved` as `kimi_k2
    .init_kv_pool`'s, and the window class's, `win_rings` (rings a live
    sequence holds) and `win_rows` (the rows of them that are live), with
    `kv_held_mb` and `kv_uniform_mb`: what the live sequences' rows take in
    this pool and what they would in one table for every layer (MB, float32)."""
    kv = llama.init_kv_pool(
        dataclasses.replace(cfg.base, num_layers=cfg.cache_layers("full")),
        num_blocks, block_size)
    ring = (cfg.cache_layers("win"), num_sequences, cfg.window, kv["k"].shape[-1])
    zero = lambda dtype: jnp.zeros((), dtype)
    return {**kv, "k_win": jnp.zeros(ring, cfg.base.dtype),
            "v_win": jnp.zeros(ring, cfg.base.dtype),
            "counters": {"moe_rows": zero(jnp.int32), "moe_moved": zero(jnp.int32),
                         "win_rings": zero(jnp.int32), "win_rows": zero(jnp.int32),
                         "kv_held_mb": zero(jnp.float32), "kv_uniform_mb": zero(jnp.float32)}}


def _pool_counters(cfg: LagunaConfig, pool: dict, state_pages, tokens_held, block_size: int):
    """The window class's counters of a call that leaves `tokens_held` [B]
    tokens in each sequence (0 in a dead row)."""
    live = state_pages > 0
    held = jnp.where(live, tokens_held, 0)
    ring_rows = jnp.minimum(held, cfg.window)
    row_mb = 2 * pool["k"].shape[-1] * pool["k"].dtype.itemsize / 1e6   # a K and a V row
    Lf, Lw = cfg.cache_layers("full"), cfg.cache_layers("win")
    in_blocks = (-(-held // block_size) * block_size).sum().astype(jnp.float32)
    rings = live.sum().astype(jnp.int32)
    return {"win_rings": rings, "win_rows": ring_rows.sum().astype(jnp.int32),
            "kv_held_mb": row_mb * (Lf * in_blocks + Lw * cfg.window * rings),
            "kv_uniform_mb": row_mb * (Lf + Lw) * in_blocks}


def forward_paged(params, tokens, cfg: LagunaConfig, pool: dict, tables, lengths,
                  block_size: int, use_kernel: bool | None = None,
                  platform: str | None = None, head_rows=None, fresh: bool = False,
                  state_pages=None):
    """`llama.forward_paged`'s contract over the pool of two classes of page:
    tokens [B, S] append at positions [lengths, lengths + S) -> (logits, the
    updated pool); `state_pages` int32 [B] is each sequence's ring of `k_win`
    and `v_win` (0: a dead row's, the garbage ring). The full layers are
    `llama.paged_attend`'s (the paged kernel at S == 1 on a TPU, the flash
    forward over a `fresh` prompt's own rows); the window layers
    `llama.window_attend`'s (the window kernel over the ring's live rows, the
    banded flash forward).

    With `head_rows` [B] the tokens after position `head_rows[b]` are a
    bucket's padding: their rows of a full layer are written, as every
    family's, at positions the next steps overwrite before they read them;
    of a window layer they are written NOWHERE, since a ring's row is a live
    position's (`window_attend`), and no expert held here is run for them
    (`moe.moe_mlp(live=)`)."""
    B, S = tokens.shape
    if state_pages is None:
        raise ValueError("laguna.forward_paged needs `state_pages` [B]: the ring of "
                         "`k_win` and `v_win` each sequence's window layers write")
    if platform is None:
        platform = target_platform(tokens, pool["k"])
    if use_kernel is None:
        use_kernel = S == 1 and platform == "tpu" and not fresh
    positions, blk_idx, blk_off = llama.page_rows(tables, lengths, S, block_size)
    live = jnp.full((B,), S, jnp.int32) if head_rows is None else head_rows + 1
    attends = {
        "full": llama.paged_attend(cfg.base, tables, lengths, positions, blk_idx, blk_off,
                                   block_size, use_kernel, platform, fresh),
        "win": llama.window_attend(cfg.base, cfg.window, state_pages, lengths, live,
                                   use_kernel, platform, fresh),
    }
    is_live = None if head_rows is None else jnp.arange(S)[None, :] < live[:, None]
    params, runs = _runs(cfg, params, attends, platform, live=is_live)
    cache = {name: leaf for name, leaf in pool.items() if name != "counters"}
    logits, cache, stats = llama.decoder_trunk(
        params, tokens, cfg.base, runs=runs, cache=cache, positions=positions,
        head_rows=head_rows)
    counters = {"moe_rows": stats["rows"].sum().astype(jnp.int32),
                "moe_moved": stats["moved"].sum().astype(jnp.int32),
                **_pool_counters(cfg, cache, state_pages, lengths + live, block_size)}
    return logits, {**cache, "counters": counters}


# it serves paged; a windowed backward and training a stack of several kinds
# are ROADMAP R2
MODEL = Model(init=init, logical_axes=logical_axes, loss=None,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool,
              sequence_leaves=SEQUENCE_LEAVES)
