"""LFM2 (LiquidAI; `model_type` `lfm2_moe`): a stack whose layers are of TWO
kinds of mixer, gated short convolutions and grouped-query attention, in an
order that `layer_types` lists (no prefix, no period), with a dense SwiGLU in
the first `num_dense_layers` layers and sigmoid-routed experts in the rest.

Built FROM the one layer and the one trunk (`llama.decoder_layer` in
`llama.decoder_trunk(runs=)`): the stack is cut into runs of one kind each,
one parameter stack a (kind of mixer, kind of MLP) that occurs (`conv_dense`,
`conv_moe`, `attn_moe` at the published sizes), and each kind brings its two
strategies. With y a token's normalised residual, every matrix without bias:

    conv mixer:  [B_t | C_t | x_t] = y_t W_in              W_in [H, 3 H]
                 u_t = B_t * x_t                            elementwise
                 c_t = sum_j w[j] * u_{t - (K - 1 - j)}     K = `conv_L_cache`
                                                            taps, depthwise,
                                                            u zero before the
                                                            sequence's start
                 out = (C_t * c_t) W_o                      the layer's `wo`
    attn mixer:  `llama.gqa_attention` with an RMSNorm over EACH HEAD's lanes
                 of q and k (one weight of `head_dim`) before rope
    MLP:         `llama.dense_mlp`, or `moe.moe_mlp`: sigmoid scores, a
                 selection bias that chooses and never weighs, the top_k
                 renormalised over their sum + 1e-6, no shared expert, and
                 this chip's SHARE of the experts (`experts_held`)

The convolution's state after position t is (u_{t-K+2} .. u_t): K - 1 = 2 rows
of H values a SEQUENCE, not a row a token. It lives in the pool's pages all
the same (`init_kv_pool`: the leaf `conv`, K - 1 rows a block), by a rule
that makes a block's rows a function of the tokens up to the block's end:

    row t % (K - 1) of block t // block_size holds u_t of the LAST position
    of that residue written into the block

so a FULL block holds u at its last K - 1 positions, the state a sequence has
at that block's end. A step at position t reads u_{t-1} from row (t - 1) %
(K - 1) of block table[(t - 1) // block_size], u_{t-2} likewise, zero where
the position is negative (a freed page is never trusted to hold zeros), and
writes its own; consecutive positions never share a row, so a decode step's
write lands on the one row its read no longer needs. That keeps
`forward_paged`'s contract (a sequence is `(tables, lengths)`, no slot), lets
a cached prefix resume EXACTLY (the block before the suffix holds the state
the suffix starts from), and moves the state with the pages in a PD hand-off.
It costs K - 1 rows a block where a slot would hold K - 1 a sequence.

What a rewind would need and does not get: a position whose row a LATER
position of the same residue has overwritten cannot be stepped from again, so
`serve/spec_decode.py` refuses a pool with such a leaf.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, llama, moe
from ray_tpu.ops.platform import target_platform

MIXERS = {"conv": "conv", "full_attention": "attn"}   # `layer_types` -> a stack's name


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    # hidden, heads, vocabulary, norm eps, rope, dtype; `intermediate_size` is
    # the DENSE layers' width and `num_layers` every layer, of both kinds
    base: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    # router and experts; its base's `intermediate_size` is ONE expert's width
    experts: moe.MoEConfig = dataclasses.field(default_factory=moe.MoEConfig.tiny)
    layer_types: tuple = ("conv", "full_attention")
    num_dense_layers: int = 1
    conv_taps: int = 3                # the published `conv_L_cache`

    @property
    def vocab_size(self) -> int:   # what an engine asks of any configuration
        return self.base.vocab_size

    @property
    def state_rows(self) -> int:
        """Rows of u a convolution layer keeps: a sequence's, and a block's."""
        return self.conv_taps - 1

    @property
    def kinds(self) -> list[str]:
        """Each layer's stack, in order: `<mixer>_<dense|moe>`."""
        if len(self.layer_types) != self.base.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.base.num_layers} layers")
        return [f"{MIXERS[t]}_{'dense' if i < self.num_dense_layers else 'moe'}"
                for i, t in enumerate(self.layer_types)]

    def cache_layers(self, mixer: str) -> int:
        return sum(k.startswith(mixer) for k in self.kinds)

    @staticmethod
    def tiny() -> "Lfm2Config":  # for tests: every kind of run, small
        base = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=8,
            num_heads=4, num_kv_heads=2, max_seq_len=128, rope_theta=1e6,
            rms_eps=1e-5, tie_embeddings=True, dtype=jnp.float32, remat=False)
        experts = moe.MoEConfig(
            base=dataclasses.replace(base, intermediate_size=32), num_experts=8,
            top_k=2, norm_topk_prob=True, score_func="sigmoid", norm_topk_eps=1e-6,
            experts_held=(0, 4))
        # a whole stack of one (the dense layer), runs of one, a run of three
        return Lfm2Config(base=base, experts=experts, num_dense_layers=1, layer_types=(
            "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention",
            "conv"))


# ---------------------------------------------------------------- params
_MIXER_AXES = {
    "conv": {"w_in": (None, "embed_fsdp", "mlp"), "conv_w": (None, None, None),
             "wo": (None, "mlp", "embed_fsdp")},
    "attn": {"wq": (None, "embed_fsdp", "heads"), "wk": (None, "embed_fsdp", "kv_heads"),
             "wv": (None, "embed_fsdp", "kv_heads"), "q_norm": (None, None),
             "k_norm": (None, None), "wo": (None, "heads", "embed_fsdp")},
}
_MLP_AXES = {
    "dense": {"w_gate": (None, "embed_fsdp", "mlp"), "w_up": (None, "embed_fsdp", "mlp"),
              "w_down": (None, "mlp", "embed_fsdp")},
    "moe": {"router": (None, None, None), "router_bias": (None, None),
            "e_gate": (None, "expert", "embed_fsdp", "mlp"),
            "e_up": (None, "expert", "embed_fsdp", "mlp"),
            "e_down": (None, "expert", "mlp", "embed_fsdp")},
}


def logical_axes(cfg: Lfm2Config) -> dict:
    norms = {"attn_norm": (None, None), "mlp_norm": (None, None)}
    stacks = {kind: {**norms, **_MIXER_AXES[kind.split("_")[0]],
                     **_MLP_AXES[kind.split("_")[1]]} for kind in set(cfg.kinds)}
    return {"embed": ("vocab", "embed_fsdp"), "final_norm": (None,), **stacks}


def init(cfg: Lfm2Config, key: jax.Array) -> dict:
    """Scaled-normal weights (`llama.init`'s: every matrix normal at `1 /
    sqrt(fan-in)`, norm weights one), one scan-stacked tree a kind of layer
    that occurs; the experts' leaves hold the experts held here alone, and the
    selection bias is seeded, normal at 0.1 (`kimi_k2.init`'s reason). The
    residual is CONDITIONED as `xing4.init`'s (PERF.md section 6, PR 37): every
    sub-layer's output projection (`wo`, `w_down`, `e_down`) at `1 / sqrt(2 L)`
    of that and the embedding (tied: it is the head too) at unit rms, so the
    2 L sub-layer outputs add up to the size of what they are added to. The
    taps are normal at `1 / sqrt(K)`: the K products of a unit `u` sum to
    unit size."""
    base, ex = cfg.base, cfg.experts
    h, hd, dt = base.hidden_size, base.hd, base.dtype
    nh, nkv, K = base.num_heads, base.num_kv_heads, cfg.conv_taps
    held = ex.experts_held[1] if ex.experts_held else ex.num_experts
    out_scale = (2 * base.num_layers) ** -0.5

    def dense(key, fan_in, *shape, scale=1.0):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (scale / math.sqrt(fan_in))).astype(dt)

    def mixer(name, key, n):
        ks = jax.random.split(key, 4)
        if name == "conv":
            return {"w_in": dense(ks[0], h, n, h, 3 * h),
                    "conv_w": dense(ks[1], K, n, K, h),
                    "wo": dense(ks[2], h, n, h, h, scale=out_scale)}
        return {"wq": dense(ks[0], h, n, h, nh * hd), "wk": dense(ks[1], h, n, h, nkv * hd),
                "wv": dense(ks[2], h, n, h, nkv * hd),
                "q_norm": jnp.ones((n, hd), jnp.float32),
                "k_norm": jnp.ones((n, hd), jnp.float32),
                "wo": dense(ks[3], nh * hd, n, nh * hd, h, scale=out_scale)}

    def mlp(name, key, n):
        ks = jax.random.split(key, 5)
        if name == "dense":
            m = base.intermediate_size
            return {"w_gate": dense(ks[0], h, n, h, m), "w_up": dense(ks[1], h, n, h, m),
                    "w_down": dense(ks[2], m, n, m, h, scale=out_scale)}
        m = ex.base.intermediate_size
        return {"router": dense(ks[0], h, n, h, ex.num_experts),
                "router_bias": 0.1 * jax.random.normal(ks[1], (n, ex.num_experts), jnp.float32),
                "e_gate": dense(ks[2], h, n, held, h, m),
                "e_up": dense(ks[3], h, n, held, h, m),
                "e_down": dense(ks[4], m, n, held, m, h, scale=out_scale)}

    kinds = cfg.kinds
    k_embed, *k_stacks = jax.random.split(key, 1 + len(set(kinds)))
    params = {"embed": jax.random.normal(k_embed, (base.vocab_size, h), jnp.float32).astype(dt),
              "final_norm": jnp.ones((h,), jnp.float32)}
    for kind, k in zip(sorted(set(kinds)), k_stacks):
        n = kinds.count(kind)
        k_mixer, k_mlp = jax.random.split(k)
        params[kind] = {"attn_norm": jnp.ones((n, h), jnp.float32),
                        "mlp_norm": jnp.ones((n, h), jnp.float32),
                        **mixer(kind.split("_")[0], k_mixer, n),
                        **mlp(kind.split("_")[1], k_mlp, n)}
    return params


# ---------------------------------------------------------------- the mixer
class PagedState(NamedTuple):
    """Where a call's S new tokens a sequence stand against the paged
    convolution state: the block tables, the sequences' lengths before the
    call (their first new position), how many of the S tokens are LIVE [B]
    (the rest pad a bucket: they write nothing), the pool's block size, and
    `fresh` (static: every sequence starts at position 0, nothing is read)."""
    tables: jax.Array
    lengths: jax.Array
    live: jax.Array
    block_size: int
    fresh: bool


def short_conv(cfg: Lfm2Config, state: PagedState | None = None):
    """The mixer strategy of a convolution layer (`decoder_layer`'s
    `attention`: normalised y [B, S, H] -> the gated convolution's output as
    o [B, S, 1, H], before the layer's `wo`). With `state` the cache is the
    pool whose leaf `conv` [Lc, NB, K - 1, H] holds the layers' rows of u by
    the module docstring's rule, and `index` is the layer's place among the
    convolution layers; without, a sequence starts from zeros and nothing is
    kept. Scopes, inside the layer's `conv` (it takes the place of `attn`):
    `in_proj`, `state_read`, `mix`, `state_write`.

    The taps are K - 1 shifted multiply-adds in float32 on the bfloat16 `u`
    the pool also keeps, so a prefill that has its `u` in hand and a decode
    step that reads them back multiply the same numbers."""
    K, R = cfg.conv_taps, cfg.state_rows

    def before(pool, index, u):
        """u at the R positions before each sequence's first new one,
        [B, R, H], oldest first; zero where there is none."""
        (B, _, H), dtype = u.shape, u.dtype
        if state is None or state.fresh:
            return jnp.zeros((B, R, H), dtype)
        pos = state.lengths[:, None] - jnp.arange(R, 0, -1, dtype=jnp.int32)   # [B, R]
        at = jnp.maximum(pos, 0)
        blk = state.tables[jnp.arange(B)[:, None], at // state.block_size]
        rows = pool["conv"][index, blk, at % R]                                 # [B, R, H]
        return jnp.where((pos >= 0)[..., None], rows, 0).astype(dtype)

    def write(pool, index, u):
        """The rows that the call's live positions leave: of every block they
        touch, u at the last live position of each residue, and nothing of a
        position this call did not compute."""
        B, S, H = u.shape
        bs, conv = state.block_size, pool["conv"]
        first = state.lengths
        if S == 1:
            blk = state.tables[jnp.arange(B), first // bs]
            return {**pool, "conv": conv.at[index, blk, first % R].set(u[:, 0].astype(conv.dtype))}
        max_blocks = state.tables.shape[1]
        # S positions from anywhere in a block touch at most this many blocks
        seq_blk = first[:, None] // bs + jnp.arange((S + bs - 2) // bs + 1, dtype=jnp.int32)
        end = jnp.minimum((seq_blk + 1) * bs, (first + state.live)[:, None]) - 1   # [B, nb]
        pos = end[..., None] - jnp.arange(R, dtype=jnp.int32)                    # [B, nb, R]
        ok = ((pos >= first[:, None, None]) & (pos >= (seq_blk * bs)[..., None])
              & (seq_blk < max_blocks)[..., None])
        blk = state.tables[jnp.arange(B)[:, None], jnp.minimum(seq_blk, max_blocks - 1)]
        # what is not written goes to the garbage block 0, as a padded K/V row
        blk = jnp.where(ok, blk[..., None], 0).reshape(B, -1)
        src = jnp.clip(pos - first[:, None, None], 0, S - 1).reshape(B, -1)
        rows = jnp.take_along_axis(u, src[..., None], axis=1)                    # [B, nb R, H]
        return {**pool, "conv": conv.at[index, blk, pos.reshape(B, -1) % R].set(
            rows.astype(conv.dtype))}

    def mixer(_, y, layer, pool, positions, index):
        S = y.shape[1]
        with jax.named_scope("in_proj"):
            gate_in, gate_out, x = jnp.split(y @ layer["w_in"], 3, axis=-1)
            # held as they are: beside a donated pool of gigabytes XLA:TPU's
            # rematerialisation runs the [S, H] x [H, 3 H] product again for
            # each of its readers (the mix, the gate, the state's write:
            # three times a layer in the 4,096 prefill; PERF.md section 6, PR 40)
            u, gate_out = jax.lax.optimization_barrier((gate_in * x, gate_out))
        with jax.named_scope("state_read"):
            past = before(pool, index, u)
        with jax.named_scope("mix"):
            taps = layer["conv_w"].astype(jnp.float32)                           # [K, H]
            seq = jnp.concatenate([past, u], axis=1).astype(jnp.float32)         # [B, R + S, H]
            c = taps[R] * seq[:, R:]
            for d in range(1, K):   # u_{t - d}, the tap d back
                c = c + taps[R - d] * seq[:, R - d:R - d + S]
            o = gate_out * c.astype(u.dtype)
        if state is not None:
            with jax.named_scope("state_write"):
                pool = write(pool, index, u)
        return o[:, :, None, :], pool

    mixer.scope = "conv"
    return mixer


# ---------------------------------------------------------------- the stack
def _runs(cfg: Lfm2Config, params: dict, mixers: dict, platform: str | None):
    """(the parameters with each expert stack's experts taken out, the stack
    as `llama.Run`s): consecutive layers of one kind are a run, a kind's
    layers count up through its parameter stack and a mixer's through its
    cache. The experts' weights stay where they are (`moe.unstacked_experts`):
    a kind's expert strategy closes over its own stack of them."""
    params, mlps = dict(params), {}
    for kind in set(cfg.kinds):
        if kind.endswith("_moe"):
            params[kind], stacked = moe.unstacked_experts(params[kind])
            mlps[kind] = partial(moe.moe_mlp, cfg=cfg.experts, platform=platform,
                                 stacked=stacked)
        else:
            mlps[kind] = llama.dense_mlp
    runs, in_stack, in_cache = [], {}, {}
    for kind in cfg.kinds:
        mixer = kind.split("_")[0]
        if runs and runs[-1].stack == kind:
            runs[-1] = runs[-1]._replace(count=runs[-1].count + 1)
        else:
            runs.append(llama.Run(kind, in_stack.get(kind, 0), 1, mixers[mixer], mlps[kind],
                                  cache_first=in_cache.get(mixer, 0)))
        in_stack[kind] = in_stack.get(kind, 0) + 1
        in_cache[mixer] = in_cache.get(mixer, 0) + 1
    return params, runs


def forward(params, tokens, cfg: Lfm2Config, attn_fn=None, platform: str | None = None):
    """Token ids [B, S] -> float32 logits [B, S, V] with no cache: every
    sequence from position 0, the convolutions from zeros."""
    if platform is None:
        platform = target_platform(tokens, params["embed"])
    params, runs = _runs(cfg, params, {"attn": llama.plain_attend(attn_fn),
                                       "conv": short_conv(cfg)}, platform)
    return llama.decoder_trunk(params, tokens, cfg.base, runs=runs)[0]


# ---------------------------------------------------------------- serving
def init_kv_pool(cfg: Lfm2Config, num_blocks: int, block_size: int) -> dict:
    """The paged pool of a stack of two kinds of mixer, pages on the second
    axis of every leaf (block 0 the garbage block): `k` and `v` [La, NB, BS,
    Hkv * Dp] as `llama.init_kv_pool` lays them (a row a token; a 64-wide
    head in its 128-lane tile), La the ATTENTION layers alone, and `conv`
    [Lc, NB, K - 1, H], the convolution layers' state (the module docstring's
    rule: K - 1 rows a block, whatever the block's size in tokens). Beside
    them `counters`, as `kimi_k2.init_kv_pool`'s: `moe_rows` and `moe_moved`
    of the last forward's expert layers."""
    if block_size % cfg.state_rows:
        raise ValueError(f"block_size {block_size} is no multiple of the convolution's "
                         f"{cfg.state_rows} state rows: a block's rows would not be "
                         f"its last positions'")
    kv = llama.init_kv_pool(
        dataclasses.replace(cfg.base, num_layers=cfg.cache_layers("attn")),
        num_blocks, block_size)
    conv = jnp.zeros((cfg.cache_layers("conv"), num_blocks, cfg.state_rows,
                      cfg.base.hidden_size), cfg.base.dtype)
    return {**kv, "conv": conv,
            "counters": {"moe_rows": jnp.zeros((), jnp.int32),
                         "moe_moved": jnp.zeros((), jnp.int32)}}


def forward_paged(params, tokens, cfg: Lfm2Config, pool: dict, tables, lengths,
                  block_size: int, use_kernel: bool | None = None,
                  platform: str | None = None, head_rows=None, fresh: bool = False):
    """`llama.forward_paged`'s contract over the pool of two kinds of cache:
    tokens [B, S] append at positions [lengths, lengths + S) -> (logits, the
    updated pool). The attention layers are `llama.paged_attend`'s (the paged
    kernel at S == 1 on a TPU, the flash forward over a `fresh` prompt's own
    rows, the gathered table otherwise); the convolution layers read and
    write the `conv` leaf (`short_conv`).

    The state a call leaves is the state after the LAST POSITION IT ANSWERS
    FOR: with `head_rows` [B] the tokens after position `head_rows[b]` are a
    bucket's padding (a prefill samples from its last live position, and a
    later row changes no logit it returns), so their `u` is written nowhere;
    their K and V rows are, as every family's, at positions the next steps
    overwrite before they read them. Without `head_rows` every token is
    live."""
    B, S = tokens.shape
    if platform is None:
        platform = target_platform(tokens, pool["k"])
    if use_kernel is None:
        use_kernel = S == 1 and platform == "tpu" and not fresh
    positions, blk_idx, blk_off = llama.page_rows(tables, lengths, S, block_size)
    live = jnp.full((B,), S, jnp.int32) if head_rows is None else head_rows + 1
    mixers = {
        "attn": llama.gqa_attention(llama.paged_attend(
            cfg.base, tables, lengths, positions, blk_idx, blk_off, block_size,
            use_kernel, platform, fresh)),
        "conv": short_conv(cfg, PagedState(tables, lengths, live, block_size, fresh)),
    }
    params, runs = _runs(cfg, params, mixers, platform)
    cache = {name: leaf for name, leaf in pool.items() if name != "counters"}
    logits, cache, stats = llama.decoder_trunk(
        params, tokens, cfg.base, runs=runs, cache=cache, positions=positions,
        head_rows=head_rows)
    counters = {"moe_rows": stats["rows"].sum().astype(jnp.int32),
                "moe_moved": stats["moved"].sum().astype(jnp.int32)}
    return logits, {**cache, "counters": counters}


# it serves paged; training a stack of several kinds is ROADMAP R2
MODEL = Model(init=init, logical_axes=logical_axes, loss=None,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
