"""Nemotron-H (NVIDIA; `model_type` `nemotron_h`): a stack whose blocks are
ONE sub-layer each, of three kinds in the order `hybrid_override_pattern`
spells (`M` a Mamba-2 selective state-space mixer, `*` grouped-query
attention, `E` routed experts beside a shared one), each `x <- x + f(RMSNorm(x))`.

Built FROM the one layer and the one trunk (`llama.decoder_layer` in
`llama.decoder_trunk(runs=)`): a kind is a parameter stack (`mamba`, `attn`,
`experts`) and a `llama.Run` whose other strategy is None, so the sub-layer a
kind does not have is not run and its norm is not held. With y a token's
normalised residual, no bias but the convolution's:

    M:  [z | xBC | dt] = y W_in            W_in [H, d_inner + conv_channels + heads]
        xBC <- silu(conv_K(xBC) + b)       depthwise, causal, K taps over the
                                           `conv_channels` = d_inner + 2 G N
                                           channels, zero before the start
        [x | B | C] = xBC                  x [heads, P], B and C [G, N], a
                                           group's B and C shared by its heads
        dt = softplus(dt + dt_bias)        a head;  A = -exp(A_log)  a head
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T      a head's state [P, N]
        y_t = h_t C_t + D x_t
        g = RMSNorm_groups(y_t * silu(z_t))  over G groups of d_inner / G lanes,
                                             one weight of d_inner
        out = g W_out                        the layer's `wo`
    *:  `llama.gqa_attention(rotary=False)`: causal, NO rotary rotation (the
        family's attention layers take no positional embedding)
    E:  `moe.moe_mlp`: sigmoid scores, a selection bias that chooses and never
        weighs, the top_k renormalised over their sum + 1e-20, times
        `routed_scaling`; an expert is `relu(y W_up)^2 W_down` (no gate), the
        shared expert the same at its own width, and this chip's SHARE of the
        experts (`experts_held`)

The state of an `M` layer after position t is (h_t of every head, float32
[heads, P, N]; xBC's pre-activation inputs at the last K - 1 positions): 2 MiB
and 36 KiB a layer A SEQUENCE at the published sizes, whatever its length. It
is the pool's second CLASS of page (`init_kv_pool`: the leaves `ssm` and
`conv`, `[Lm, NS, ...]`): a page a sequence, where `k` and `v` keep a row a
token in pages of `block_size` tokens. `forward_paged` takes a sequence as its
block table, its length and `state_pages[b]`, the id of its state page (0 the
garbage page, as block 0 is): never a slot, so a PD hand-off moves a sequence
as its token pages and its state page, and any free page takes it.

What a running sum cannot give: the state at an EARLIER position. A cached
prefix (a block's hash says nothing of h) and a rejected speculative window
(no rewind) are refused by the engine for a pool with such leaves
(`Model.sequence_leaves`; ROADMAP R6 has what is left).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, llama, moe
from ray_tpu.ops.platform import target_platform

STACKS = {"M": "mamba", "*": "attn", "E": "experts"}   # a pattern's letter -> its stack


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    # hidden, the attention layers' heads, vocabulary, norm eps, dtype;
    # `num_layers` is every block, of all three kinds
    base: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    # router and experts; its base's `intermediate_size` is ONE routed expert's width
    experts: moe.MoEConfig = dataclasses.field(default_factory=moe.MoEConfig.tiny)
    pattern: str = "ME*E"             # the published `hybrid_override_pattern`
    shared_width: int = 64            # the shared expert's (`moe_shared_expert_intermediate_size`)
    mamba_heads: int = 4
    mamba_head_dim: int = 16          # P
    state_size: int = 16              # N, `ssm_state_size`
    n_groups: int = 2                 # G: groups of heads that share B and C
    conv_kernel: int = 4              # K taps
    chunk_size: int = 128             # the chunked scan's block; changes no value
    # the published initialisation's range of dt, for `init`'s `dt_bias`
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    ssm_dtype: Any = jnp.float32      # the running sum h, in the pool

    @property
    def vocab_size(self) -> int:   # what an engine asks of any configuration
        return self.base.vocab_size

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def kinds(self) -> list[str]:
        """Each block's stack, in order."""
        if len(self.pattern) != self.base.num_layers:
            raise ValueError(f"a pattern of {len(self.pattern)} blocks for "
                             f"{self.base.num_layers} layers")
        return [STACKS[c] for c in self.pattern]

    def count(self, stack: str) -> int:
        return self.kinds.count(stack)

    @staticmethod
    def tiny() -> "NemotronHConfig":  # for tests: every kind, small
        base = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=0, num_layers=8,
            num_heads=4, num_kv_heads=2, max_seq_len=512, rms_eps=1e-5,
            tie_embeddings=False, dtype=jnp.float32, remat=False)
        experts = moe.MoEConfig(
            base=dataclasses.replace(base, intermediate_size=32), num_experts=8,
            top_k=2, norm_topk_prob=True, score_func="sigmoid", routed_scaling=2.5,
            norm_topk_eps=1e-20, activation="relu2", experts_held=(0, 4))
        return NemotronHConfig(base=base, experts=experts, pattern="MEM*EME*",
                               shared_width=48)


# ---------------------------------------------------------------- params
def logical_axes(cfg: NemotronHConfig) -> dict:
    stacks = {
        "mamba": {"attn_norm": (None, None), "w_in": (None, "embed_fsdp", "mlp"),
                  "conv_w": (None, None, None), "conv_b": (None, None),
                  "dt_bias": (None, None), "A_log": (None, None), "D": (None, None),
                  "gate_norm": (None, None), "wo": (None, "mlp", "embed_fsdp")},
        "attn": {"attn_norm": (None, None), "wq": (None, "embed_fsdp", "heads"),
                 "wk": (None, "embed_fsdp", "kv_heads"), "wv": (None, "embed_fsdp", "kv_heads"),
                 "wo": (None, "heads", "embed_fsdp")},
        "experts": {"mlp_norm": (None, None), "router": (None, None, None),
                    "router_bias": (None, None),
                    "e_up_t": (None, "expert", "mlp", "embed_fsdp"),
                    "e_down": (None, "expert", "mlp", "embed_fsdp"),
                    "s_up": (None, "embed_fsdp", "mlp"), "s_down": (None, "mlp", "embed_fsdp")},
    }
    return {"embed": ("vocab", "embed_fsdp"), "lm_head": ("embed_fsdp", "vocab"),
            "final_norm": (None,), **{k: stacks[k] for k in set(cfg.kinds)}}


def init(cfg: NemotronHConfig, key: jax.Array) -> dict:
    """Scaled-normal weights (`llama.init`'s: every matrix normal at `1 /
    sqrt(fan-in)`, norm weights one), one scan-stacked tree a kind of block;
    the experts' leaves hold the experts held here alone and the selection
    bias is seeded, normal at 0.1 (`kimi_k2.init`'s reason). The residual is
    CONDITIONED as `xing4.init`'s (PERF.md section 6, PR 37): every block's
    output projection (`wo`, `e_down`, `s_down`) at `1 / sqrt(L)` of that (a
    block is one sub-layer, so L of them add up) and the embedding at unit
    rms. The state-space tensors are seeded in the published initialisation's
    ranges: `A_log` the log of uniform [1, 16], `dt_bias` the inverse
    softplus of a dt log-uniform in [`time_step_min`, `time_step_max`] (and
    at least `time_step_floor`), `D` one, the taps and their bias uniform in
    +- `1 / sqrt(K)`."""
    base, ex = cfg.base, cfg.experts
    h, hd, dt = base.hidden_size, base.hd, base.dtype
    nh, nkv, K = base.num_heads, base.num_kv_heads, cfg.conv_kernel
    held = ex.experts_held[1] if ex.experts_held else ex.num_experts
    out_scale = base.num_layers ** -0.5

    def dense(key, fan_in, *shape, scale=1.0):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (scale / math.sqrt(fan_in))).astype(dt)

    def uniform(key, lo, hi, *shape):
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)

    def stack(name, key, n):
        ks = jax.random.split(key, 8)
        if name == "mamba":
            d_in, C, H = cfg.d_inner, cfg.conv_channels, cfg.mamba_heads
            step = jnp.exp(uniform(ks[3], math.log(cfg.time_step_min),
                                   math.log(cfg.time_step_max), n, H))
            step = jnp.maximum(step, cfg.time_step_floor)
            return {"attn_norm": jnp.ones((n, h), jnp.float32),
                    "w_in": dense(ks[0], h, n, h, d_in + C + H),
                    "conv_w": uniform(ks[1], -K ** -0.5, K ** -0.5, n, K, C).astype(dt),
                    "conv_b": uniform(ks[2], -K ** -0.5, K ** -0.5, n, C).astype(dt),
                    "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                    "A_log": jnp.log(uniform(ks[4], 1.0, 16.0, n, H)),
                    "D": jnp.ones((n, H), jnp.float32),
                    "gate_norm": jnp.ones((n, d_in), jnp.float32),
                    "wo": dense(ks[5], d_in, n, d_in, h, scale=out_scale)}
        if name == "attn":
            return {"attn_norm": jnp.ones((n, h), jnp.float32),
                    "wq": dense(ks[0], h, n, h, nh * hd), "wk": dense(ks[1], h, n, h, nkv * hd),
                    "wv": dense(ks[2], h, n, h, nkv * hd),
                    "wo": dense(ks[3], nh * hd, n, nh * hd, h, scale=out_scale)}
        m, ms = ex.base.intermediate_size, cfg.shared_width
        return {"mlp_norm": jnp.ones((n, h), jnp.float32),
                "router": dense(ks[0], h, n, h, ex.num_experts),
                "router_bias": 0.1 * jax.random.normal(ks[1], (n, ex.num_experts), jnp.float32),
                # [m, H] an expert, transposed: 1,856 is no whole number of lane tiles
                "e_up_t": dense(ks[2], h, n, held, m, h),
                "e_down": dense(ks[3], m, n, held, m, h, scale=out_scale),
                "s_up": dense(ks[4], h, n, h, ms),
                "s_down": dense(ks[5], ms, n, ms, h, scale=out_scale)}

    names = sorted(set(cfg.kinds))
    k_embed, k_head, *k_stacks = jax.random.split(key, 2 + len(names))
    params = {"embed": jax.random.normal(k_embed, (base.vocab_size, h), jnp.float32).astype(dt),
              "lm_head": dense(k_head, h, h, base.vocab_size),
              "final_norm": jnp.ones((h,), jnp.float32)}
    for name, k in zip(names, k_stacks):
        params[name] = stack(name, k, cfg.count(name))
    return params


# ---------------------------------------------------------------- the scan
def ssd_scan(x, dt, A, B, C, h0, chunk: int):
    """The selective state-space recurrence over S positions, in chunks (the
    state-space duality's block decomposition): x [b, S, H, P], dt float32 [b,
    S, H] (0 where a position must not advance the state: a bucket's
    padding), A float32 [H] (negative), B and C [b, S, G, N] (head h reads
    group h // (H / G)), h0 float32 [b, H, P, N] or None (zeros) ->
    (y float32 [b, S, H, P] without the `D x` term, the state after the last
    position, float32 [b, H, P, N]).

        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t

    With a_t = dt_t A and cs its running sum inside a chunk: within a chunk
    y_t = sum_{s <= t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s, a masked [Q, Q]
    product; a chunk's own contribution to the state is sum_s exp(cs_Q - cs_s)
    dt_s x_s B_s^T; the state is carried from chunk to chunk by a scan over
    chunks, and adds exp(cs_t) h C_t to the chunk's rows. The products take
    their operands in x's dtype (bfloat16 where served) and accumulate in
    float32; decays and running sums are float32. The mask is applied to the
    EXPONENT: above the diagonal cs_t - cs_s is positive and its exponential
    overflows."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    R, Q, dtype = H // G, chunk, x.dtype
    pad = -S % Q
    if pad:   # dt 0: the padding neither decays nor adds
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (S + pad) // Q
    # head-major inside a chunk, so that Q (128) is the lane axis of every [Q, Q]
    xs = x.reshape(b, nc, Q, G, R, P).transpose(0, 1, 3, 4, 2, 5)        # [b, c, G, R, Q, P]
    dts = dt.reshape(b, nc, Q, G, R).transpose(0, 1, 3, 4, 2)             # [b, c, G, R, Q]
    Bs = B.reshape(b, nc, Q, G, N).transpose(0, 1, 3, 2, 4)               # [b, c, G, Q, N]
    Cs = C.reshape(b, nc, Q, G, N).transpose(0, 1, 3, 2, 4)
    cs = jnp.cumsum(dts * A.reshape(G, R, 1), axis=-1)                    # [b, c, G, R, Q]
    # within a chunk
    cb = jnp.einsum("bcgtn,bcgsn->bcgts", Cs, Bs, preferred_element_type=jnp.float32)
    seg = cs[..., :, None] - cs[..., None, :]                             # cs_t - cs_s
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))                     # [b, c, G, R, Q, Q]
    m = (cb[:, :, :, None] * decay * dts[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrts,bcgrsp->bcgrtp", m, xs, preferred_element_type=jnp.float32)
    # a chunk's own state, and the state carried into each chunk
    to_end = jnp.exp(cs[..., -1:] - cs) * dts                             # [b, c, G, R, Q]
    xw = (xs.astype(jnp.float32) * to_end[..., None]).astype(dtype)
    own = jnp.einsum("bcgrsp,bcgsn->bcgrpn", xw, Bs, preferred_element_type=jnp.float32)
    whole = jnp.exp(cs[..., -1])                                          # [b, c, G, R]
    h = (jnp.zeros((b, G, R, P, N), jnp.float32) if h0 is None
         else h0.astype(jnp.float32).reshape(b, G, R, P, N))

    def carry(h, c):
        own_c, whole_c = c
        return whole_c[..., None, None] * h + own_c, h

    h, at_start = jax.lax.scan(carry, h, (own.swapaxes(0, 1), whole.swapaxes(0, 1)))
    at_start = at_start.swapaxes(0, 1)                                    # [b, c, G, R, P, N]
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bcgtn,bcgrpn->bcgrtp", Cs, at_start.astype(dtype),
        preferred_element_type=jnp.float32)
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(b, nc * Q, H, P)[:, :S]
    return y, h.reshape(b, H, P, N)


# ---------------------------------------------------------------- the mixer
class PagedState(NamedTuple):
    """Where a call's S new tokens a sequence stand against the paged state:
    each sequence's state page [B] (0: the garbage page, a dead row's), the
    sequences' lengths before the call, how many of the S tokens are LIVE [B]
    (the rest pad a bucket: they must not advance the state), and `fresh`
    (static: every sequence starts at position 0, nothing is read)."""
    pages: jax.Array
    lengths: jax.Array
    live: jax.Array
    fresh: bool


def mamba_mixer(cfg: NemotronHConfig, state: PagedState | None = None,
                use_kernel: bool = False, interpret: bool = False):
    """The mixer strategy of an `M` block (`decoder_layer`'s `attention`:
    normalised y [B, S, H] -> the gated, group-normed scan output as o [B, S,
    heads, P], before the layer's `wo`). With `state` the cache is the pool
    whose leaves `ssm` [Lm, NS, heads, P, N] and `conv` [Lm, NS, (K - 1) *
    conv_channels] hold each sequence's state in ITS page, and `index` is the
    layer's place among the `M` blocks; without, a sequence starts from zeros
    and nothing is kept. Scopes, inside the layer's `ssm` (it takes the place
    of `attn`): `in_proj`, `conv`, `state_read`, `scan` (S > 1) or `step` (S
    == 1), `state_write`, `gate_norm`.

    The state a call leaves is the state after each sequence's LAST LIVE
    position: dt is zeroed past it (exp(0 A) = 1 and 0 x B^T = 0: h stands
    still) and the convolution's rows are taken at it, so a bucket's padding
    writes nothing of itself. A page is never trusted to hold zeros: a
    `fresh` call reads none, and any other masks what it read where the
    sequence's length is 0.

    `use_kernel` (a decode step, S == 1, over a pool): the running state's
    read, step and write are ONE Pallas call that updates each live
    sequence's page of `ssm` in place (`ops/ssm_state.py`, scope `ssm/step`;
    `interpret` off the TPU); the plain path gathers the pages, steps and
    scatters them back (three passes over the state where the kernel makes
    one)."""
    K, H, P, N, G = (cfg.conv_kernel, cfg.mamba_heads, cfg.mamba_head_dim,
                     cfg.state_size, cfg.n_groups)
    R, d_in, C = K - 1, cfg.d_inner, cfg.conv_channels

    def mixer(base, y, layer, pool, positions, index):
        B_, S, _ = y.shape
        dtype = y.dtype
        with jax.named_scope("in_proj"):
            z, xbc, dt = jnp.split(y @ layer["w_in"], [d_in, d_in + C], axis=-1)
        keep = state is not None and not state.fresh
        in_place = keep and use_kernel and S == 1
        with jax.named_scope("state_read"):
            past, h0 = jnp.zeros((B_, R, C), dtype), None
            if keep:
                began = (state.lengths > 0)
                past = jnp.where(began[:, None], pool["conv"][index, state.pages], 0
                                 ).astype(dtype).reshape(B_, R, C)
                if not in_place:
                    h0 = jnp.where(began[:, None, None, None],
                                   pool["ssm"][index, state.pages], 0)
        live = jnp.full((B_,), S, jnp.int32) if state is None else state.live
        with jax.named_scope("conv"):
            taps = layer["conv_w"].astype(jnp.float32)                    # [K, C]
            seq = jnp.concatenate([past, xbc], axis=1)                    # [B, R + S, C]
            seq32 = seq.astype(jnp.float32)
            c = layer["conv_b"].astype(jnp.float32) + taps[R] * seq32[:, R:]
            for d in range(1, K):   # the input d positions back
                c = c + taps[R - d] * seq32[:, R - d:R - d + S]
            c = jax.nn.silu(c).astype(dtype)
            if state is not None:
                # the R rows that end at the last live position: rows [live,
                # live + R) of `seq`, whose first R rows are the past's. Taken
                # HERE, ahead of the scan: left to the scheduler the slice
                # waits for the layer's scatter at the program's end and every
                # layer's [S, channels] input stays live until then (48 MB a
                # layer of the 4,096 prefill, 1.1 GB; PERF.md section 6, PR 45)
                rows = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, R, axis=0))(
                    seq, live)
                c, rows = jax.lax.optimization_barrier((c, rows))
            x, Bm, Cm = jnp.split(c, [d_in, d_in + G * N], axis=-1)
            x = x.reshape(B_, S, H, P)
            Bm, Cm = Bm.reshape(B_, S, G, N), Cm.reshape(B_, S, G, N)
        A = -jnp.exp(layer["A_log"].astype(jnp.float32))                  # [H]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])   # [B, S, H]
        if S == 1:
            with jax.named_scope("step"):
                dt1, x1 = dt[:, 0], x[:, 0].astype(jnp.float32)           # [B, H], [B, H, P]
                b1 = jnp.repeat(Bm[:, 0].astype(jnp.float32), H // G, axis=1)   # [B, H, N]
                c1 = jnp.repeat(Cm[:, 0].astype(jnp.float32), H // G, axis=1)
                decay, dx = jnp.exp(dt1 * A), dt1[..., None] * x1
                if in_place:
                    from ray_tpu.ops.ssm_state import ssm_state_step

                    # a sequence at its first position takes nothing of its page
                    ssm, ys = ssm_state_step(
                        pool["ssm"], index, state.pages, jnp.where(began[:, None], decay, 0.0),
                        dx, b1, c1, interpret=interpret)
                    pool, ys = {**pool, "ssm": ssm}, ys[:, None]
                else:
                    h = (jnp.zeros((B_, H, P, N), jnp.float32) if h0 is None
                         else h0.astype(jnp.float32))
                    h = decay[..., None, None] * h + dx[..., None] * b1[:, :, None, :]
                    ys = (h * c1[:, :, None, :]).sum(axis=-1)[:, None]    # [B, 1, H, P]
        else:
            with jax.named_scope("scan"):
                at = jnp.arange(S, dtype=jnp.int32)
                dt = jnp.where(at[None, :, None] < live[:, None, None], dt, 0.0)
                ys, h = ssd_scan(x, dt, A, Bm, Cm, h0, cfg.chunk_size)
        ys = ys + layer["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        if state is not None:
            with jax.named_scope("state_write"):
                pool = {**pool, "conv": pool["conv"].at[index, state.pages].set(
                    rows.reshape(B_, R * C).astype(pool["conv"].dtype))}
                if not in_place:
                    pool["ssm"] = pool["ssm"].at[index, state.pages].set(
                        h.astype(pool["ssm"].dtype))
        with jax.named_scope("gate_norm"):
            g = (ys * jax.nn.silu(z.astype(jnp.float32)).reshape(B_, S, H, P)
                 ).reshape(B_, S, G, d_in // G)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + base.rms_eps)
            g = (g.reshape(B_, S, d_in) * layer["gate_norm"]).astype(dtype)
        return g.reshape(B_, S, H, P), pool

    mixer.scope = "ssm"
    return mixer


# ---------------------------------------------------------------- the stack
def _runs(cfg: NemotronHConfig, params: dict, mixers: dict, platform: str | None):
    """(the parameters with the expert stack's experts taken out, the stack as
    `llama.Run`s): the published order never has two blocks of one kind in a
    row, so every run is one block long; a kind's blocks count up through its
    parameter stack and a mixer's through its cache. The experts' weights
    stay where they are (`moe.unstacked_experts`)."""
    params = dict(params)
    expert_mlp = None
    if "experts" in params:
        params["experts"], stacked = moe.unstacked_experts(params["experts"])
        expert_mlp = partial(moe.moe_mlp, cfg=cfg.experts, platform=platform, stacked=stacked)
    runs, seen = [], {}
    for kind in cfg.kinds:
        first = seen.get(kind, 0)
        if kind == "experts":
            runs.append(llama.Run(kind, first, 1, None, expert_mlp, held=True))
        else:
            runs.append(llama.Run(kind, first, 1, mixers[kind], None, cache_first=first,
                                  held=True))
        seen[kind] = first + 1
    return params, runs


def forward(params, tokens, cfg: NemotronHConfig, attn_fn=None, platform: str | None = None):
    """Token ids [B, S] -> float32 logits [B, S, V] with no cache: every
    sequence from position 0, the state-space layers from zeros."""
    if platform is None:
        platform = target_platform(tokens, params["embed"])
    mixers = {"attn": llama.plain_attend(attn_fn, rotary=False), "mamba": mamba_mixer(cfg)}
    params, runs = _runs(cfg, params, mixers, platform)
    return llama.decoder_trunk(params, tokens, cfg.base, runs=runs)[0]


# ---------------------------------------------------------------- serving
SEQUENCE_LEAVES = ("ssm", "conv")


def init_kv_pool(cfg: NemotronHConfig, num_blocks: int, block_size: int,
                 num_sequences: int) -> dict:
    """The paged pool of a stack with two CLASSES of page, pages on the
    second axis of every leaf and page 0 of each class its garbage page: `k`
    and `v` [La, num_blocks, block_size, Hkv * Dp] as `llama.init_kv_pool`
    lays them (a row a token), La the attention blocks alone; and, a page a
    SEQUENCE (`Model.sequence_leaves`), `ssm` [Lm, num_sequences, heads, P, N]
    in `cfg.ssm_dtype` (float32: a running sum over hundreds of decode steps)
    and `conv` [Lm, num_sequences, (K - 1) * conv_channels] in the model's
    dtype (a page is ONE row, the K - 1 inputs end to end: laid out [.., K - 1,
    channels] XLA:TPU scatters it in a layout with the 3 rows on the lanes,
    a 1.77 GB padded copy of a 41 MB leaf; PERF.md section 6, PR 45), Lm the
    `M` blocks. Beside them `counters`, as `kimi_k2.init_kv_pool`'s."""
    kv = llama.init_kv_pool(
        dataclasses.replace(cfg.base, num_layers=cfg.count("attn")), num_blocks, block_size)
    Lm = cfg.count("mamba")
    return {**kv,
            "ssm": jnp.zeros((Lm, num_sequences, cfg.mamba_heads, cfg.mamba_head_dim,
                              cfg.state_size), cfg.ssm_dtype),
            "conv": jnp.zeros((Lm, num_sequences, (cfg.conv_kernel - 1) * cfg.conv_channels),
                              cfg.base.dtype),
            "counters": {"moe_rows": jnp.zeros((), jnp.int32),
                         "moe_moved": jnp.zeros((), jnp.int32)}}


def forward_paged(params, tokens, cfg: NemotronHConfig, pool: dict, tables, lengths,
                  block_size: int, use_kernel: bool | None = None,
                  platform: str | None = None, head_rows=None, fresh: bool = False,
                  state_pages=None):
    """`llama.forward_paged`'s contract over the pool of two classes of page:
    tokens [B, S] append at positions [lengths, lengths + S) -> (logits, the
    updated pool); `state_pages` int32 [B] is each sequence's page of `ssm`
    and `conv` (0: a dead row's, the garbage page). The attention blocks are
    `llama.paged_attend`'s without rotation; the `M` blocks read and write
    their sequence's page (`mamba_mixer`).

    The state a call leaves is the state after the LAST POSITION IT ANSWERS
    FOR: with `head_rows` [B] the tokens after position `head_rows[b]` are a
    bucket's padding and advance nothing (their K and V rows are written, as
    every family's, at positions the next steps overwrite before they read
    them). Without `head_rows` every token is live."""
    B, S = tokens.shape
    if state_pages is None:
        raise ValueError("nemotron_h.forward_paged needs `state_pages` [B]: the page of "
                         "`ssm` and `conv` each sequence's state lives in")
    if platform is None:
        platform = target_platform(tokens, pool["k"])
    if use_kernel is None:
        use_kernel = S == 1 and platform == "tpu" and not fresh
    positions, blk_idx, blk_off = llama.page_rows(tables, lengths, S, block_size)
    live = jnp.full((B,), S, jnp.int32) if head_rows is None else head_rows + 1
    mixers = {
        "attn": llama.gqa_attention(llama.paged_attend(
            cfg.base, tables, lengths, positions, blk_idx, blk_off, block_size,
            use_kernel, platform, fresh), rotary=False),
        "mamba": mamba_mixer(cfg, PagedState(state_pages, lengths, live, fresh),
                             use_kernel=use_kernel and S == 1, interpret=platform != "tpu"),
    }
    params, runs = _runs(cfg, params, mixers, platform)
    cache = {name: leaf for name, leaf in pool.items() if name != "counters"}
    logits, cache, stats = llama.decoder_trunk(
        params, tokens, cfg.base, runs=runs, cache=cache, positions=positions,
        head_rows=head_rows)
    counters = {"moe_rows": stats["rows"].sum().astype(jnp.int32),
                "moe_moved": stats["moved"].sum().astype(jnp.int32)}
    return logits, {**cache, "counters": counters}


# it serves paged; the scan's backward and training a stack of several kinds
# are ROADMAP R6 / R2
MODEL = Model(init=init, logical_axes=logical_axes, loss=None,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool,
              sequence_leaves=SEQUENCE_LEAVES)
