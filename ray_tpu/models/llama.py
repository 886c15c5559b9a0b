"""Llama-family transformer, TPU-first.

This is the flagship model for the framework's train/serve stack (BASELINE.json
configs: GPT-2 124M → Llama-3 8B). Design choices for the MXU/HBM:

- Pure-functional: params are an explicit pytree; every param carries a logical-axis
  tuple (ray_tpu.parallel.sharding) so one rule table yields dp/fsdp/tp shardings.
- bfloat16 activations & params by default; fp32 RMSNorm accumulation and logits.
- GQA attention with rotary embeddings; causal mask built with lax-friendly
  broadcasted_iota (no dynamic shapes).
- SwiGLU MLP; optional remat (jax.checkpoint) per block to trade FLOPs for HBM.
- lax.scan over layers keeps compile time O(1) in depth.

The reference has no in-tree model code (it orchestrates vLLM/torch); this file is the
TPU-native equivalent of the model stacks those engines provide.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import operator
from functools import partial
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import Model
from ray_tpu.ops.platform import target_platform


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int | None = None
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full": recompute the whole block in backward (max HBM savings);
    # "dots": save matmul outputs, recompute only elementwise ops (the
    # usual transformer sweet spot — ~5% extra FLOPs instead of ~33%).
    remat_policy: str = "full"
    # passes over the WHOLE layer stack with the same weights (`decoder_trunk`);
    # more than one in a looped family (models/ouro.py)
    loop_steps: int = 1

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    # ---- presets (sizes per public Llama/GPT specs) ----
    @staticmethod
    def tiny() -> "LlamaConfig":  # for tests / dryruns
        return LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
            num_heads=4, num_kv_heads=2, max_seq_len=128, dtype=jnp.float32, remat=False,
        )

    @staticmethod
    def gpt2_124m() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=50257, hidden_size=768, intermediate_size=3072, num_layers=12,
            num_heads=12, num_kv_heads=12, max_seq_len=1024, rope_theta=10000.0,
            tie_embeddings=True,
        )

    @staticmethod
    def llama_1b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=2048, intermediate_size=8192, num_layers=16,
            num_heads=32, num_kv_heads=8, head_dim=64, max_seq_len=8192,
        )

    @staticmethod
    def llama_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_layers=32,
            num_heads=32, num_kv_heads=8, max_seq_len=8192,
        )


# ---------------------------------------------------------------- params
def logical_axes(cfg: LlamaConfig) -> dict:
    """Logical-axis tree matching init() — consumed by parallel.sharding rules.

    Layer params carry a leading None for the scanned `layers` dimension.
    """
    block = {
        "attn_norm": (None, None),
        "wq": (None, "embed_fsdp", "heads"),
        "wk": (None, "embed_fsdp", "kv_heads"),
        "wv": (None, "embed_fsdp", "kv_heads"),
        "wo": (None, "heads", "embed_fsdp"),
        "mlp_norm": (None, None),
        "w_gate": (None, "embed_fsdp", "mlp"),
        "w_up": (None, "embed_fsdp", "mlp"),
        "w_down": (None, "mlp", "embed_fsdp"),
    }
    tree = {
        "embed": ("vocab", "embed_fsdp"),
        "layers": block,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = ("embed_fsdp", "vocab")
    return tree


def init(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Initialize parameters (scaled normal init, scan-stacked layers)."""
    hd, nh, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    h, m, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def norm_init(*shape):
        return jnp.ones(shape, dtype=jnp.float32)

    def dense(key, fan_in, *shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn_norm": norm_init(L, h),
        "wq": dense(ks[0], h, L, h, nh * hd),
        "wk": dense(ks[1], h, L, h, nkv * hd),
        "wv": dense(ks[2], h, L, h, nkv * hd),
        "wo": dense(ks[3], nh * hd, L, nh * hd, h),
        "mlp_norm": norm_init(L, h),
        "w_gate": dense(ks[4], h, L, h, m),
        "w_up": dense(ks[5], h, L, h, m),
        "w_down": dense(ks[6], m, L, m, h),
    }
    params = {
        "embed": dense(k_embed, h, cfg.vocab_size, h),
        "layers": layers,
        "final_norm": norm_init(h),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, h, h, cfg.vocab_size)
    return params


def param_count(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------- ops
def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight).astype(x.dtype)


def rope(x, positions, theta, inv_freq=None):
    """Rotary embedding; x: [B, S, H, D]. `inv_freq` float32 [D / 2] replaces
    the plain `theta^(-2i/D)` (a family with scaled frequencies: kimi_k2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = (1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
             if inv_freq is None else inv_freq)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def attention(q, k, v, causal: bool = True, mask=None, window: int | None = None):
    """Dense MXU attention. q:[B,S,Hq,D], k/v:[B,S,Hkv,D] (GQA broadcast).
    `window` (causal): query i sees key j only where i - j < window."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) / math.sqrt(D)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        cmask = qi >= ki
        if window is not None:
            cmask &= qi - ki < window
        scores = jnp.where(cmask[None, None, None], scores, -1e30)
    if mask is not None:
        scores = jnp.where(mask[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, S, Hq, D)


def flash_pays(seq_len: int, platform: str) -> bool:
    """The crossover of a causal attention over rows in hand, decided here for
    every family: from S = 1,024 up on a TPU the [S, S] score matrix
    dominates HBM traffic and the flash forward kernel wins; shorter
    sequences fit XLA's fused dense path, and off the TPU the kernel only
    runs interpreted."""
    return platform == "tpu" and seq_len >= 1024


def auto_attention(q, k, v, causal: bool = True, platform: str | None = None,
                   window: int | None = None):
    """Pallas flash kernel for long causal sequences placed on a TPU, dense
    MXU attention otherwise; `window` is a sliding-window layer's (query i
    sees the `window` keys that end at its own: the kernel's band, the dense
    path's second mask).

    The crossover is `flash_pays`'s. `platform` is where the computation runs:
    callers that know their mesh pass it (train/spmd.py default_attn_fn);
    None derives it from q/k/v's placement (ops/platform.py).

    Who calls it: the training forward over the whole sequence
    (`plain_attend`), and the paged prefill of prompts that start at position
    0 over the rows it has just computed (`forward_paged(fresh=True)`: the
    flash forward from the 1,024 bucket up on a TPU, [S, S] dense scores
    under that)."""
    if platform is None:
        platform = target_platform(q, k, v)
    if causal and flash_pays(q.shape[1], platform):
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, interpret=False, window=window)
    return attention(q, k, v, causal=causal, window=window)


def dense_mlp(y, layer):
    """The MLP strategy of the dense families: the gated MLP on normalised
    activations y [B, S, H] -> ([B, S, H], {}: it has no stats)."""
    with jax.named_scope("mlp"):
        gate = jax.nn.silu(y @ layer["w_gate"])
        return (gate * (y @ layer["w_up"])) @ layer["w_down"], {}


def project_heads(y, w, head_dim: int, norm=None):
    """y [B, S, H] x w [H, N] -> [B, S, N / head_dim, head_dim]: a projection
    whose result is split into heads (`wq`, `wk`, `wv` here, the latent
    family's `w_uq`); `norm`, where given, is applied to the whole projected
    vector before the split. In a decode step (S == 1, a static shape) the
    product's result is held by an `optimization_barrier` before anything
    reads it: the weight is then hundreds of times the rows, and without the
    barrier XLA:TPU folds the split into the product, lays the result out
    heads-first for the rotation and the decode kernel, and carries that
    layout back through the product to the WEIGHT, which it then stages in
    VMEM and transposes, a layer's `wq` every layer of every step (Ouro:
    the whole stacks of `wq` and `wk`, hoisted out of the passes' scan, 403
    MB each a step). Held, the product is the plain `[B, H] x [H, N]` that
    reads the scan's slice of its stack in place, as `dense_mlp`'s and `wo`'s
    do, and the layout is given to its small result (PERF.md section 6, PR
    46). At S > 1 the rows are as large as the weight and a materialised
    result would cost a pass over them for nothing: no barrier, the text every
    prefill and training program had. The barrier is the identity."""
    B, S, _ = y.shape
    out = y @ w
    if S == 1:
        out = jax.lax.optimization_barrier(out)
    if norm is not None:
        out = norm(out)
    return out.reshape(B, S, -1, head_dim)


def gqa_attention(attend, rotary: bool | Callable = True):
    """The attention strategy of the grouped-query families, around a cache
    strategy `attend(q, k, v, cache, index) -> (o, cache)`: the three
    projections `wq`, `wk`, `wv` split into heads (`project_heads`; head
    counts from the projected widths, so a
    tensor-sharded stage passes its local weights), an RMSNorm of q and k
    where the layer holds `q_norm` and `k_norm`, before rope, and the
    weight's width says over what: OLMoE's over the WHOLE projected vector
    (a weight of `heads * head_dim`, before the split into heads), or over
    EACH HEAD's `head_dim` lanes with one weight of that width shared by the
    heads (after the split), rope on all of q and k (none where `rotary` is
    False: a family whose attention layers take no positional embedding,
    `models/nemotron_h.py`; where it is a function `(x, positions) -> x`, a
    kind of layer's own rotation: `models/laguna.py`, whose full layers
    rotate half of a head's lanes by scaled frequencies and whose window
    layers all of them by plain ones), and `attend` over the
    rotated heads q [B, S, Hq, D], k/v [B, S, Hkv, D]. `cache` is the WHOLE
    cache, every layer's (and every other kind of layer's), and `index`
    the layer's place in it: `attend` writes this layer's rows in place and
    reads them back (`forward_paged`: pages of the pool), or keeps nothing
    (`plain_attend`: cache and index are None). Where the layer holds
    `w_head_gate` [H, Hq] the heads' outputs are GATED, a sigmoid scalar a
    head from the layer's input (arXiv:2505.06708's head-wise gate): `o[..., h,
    :] *= sigmoid(y w_head_gate)[..., h]` (scope `gate`), before the layer's
    `wo`."""

    def attention(cfg, y, layer, cache, positions, index):
        eps, hd = cfg.rms_eps, cfg.hd
        qk_norm = "q_norm" in layer
        # a weight as wide as the projection norms the whole vector, one of
        # `head_dim` each head's lanes (one head: the two are the same)
        per_head = qk_norm and layer["q_norm"].shape[-1] != layer["wq"].shape[-1]
        whole = lambda name: (partial(rms_norm, weight=layer[name], eps=eps)
                              if qk_norm and not per_head else None)
        q = project_heads(y, layer["wq"], hd, whole("q_norm"))
        k = project_heads(y, layer["wk"], hd, whole("k_norm"))
        if per_head:   # (`laguna`'s configuration file: assumed (c))
            q = rms_norm(q, layer["q_norm"], eps)
            k = rms_norm(k, layer["k_norm"], eps)
        v = project_heads(y, layer["wv"], hd)
        if callable(rotary):
            q, k = rotary(q, positions), rotary(k, positions)
        elif rotary:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        o, cache = attend(q, k, v, cache, index)
        if "w_head_gate" in layer:   # (`laguna`'s configuration file: assumed (a))
            with jax.named_scope("gate"):
                gate = jax.nn.sigmoid((y @ layer["w_head_gate"]).astype(jnp.float32))
                o = o * gate[..., None].astype(o.dtype)
        return o, cache

    return attention


def plain_attend(attn_fn=None, rotary: bool | Callable = True):
    """The attention strategy that keeps no cache (training): grouped-query
    projections and `attn_fn` (default: `auto_attention`, causal) over the
    whole sequence."""
    attn_fn = attn_fn or partial(auto_attention, causal=True)
    return gqa_attention(lambda q, k, v, cache, index: (attn_fn(q, k, v), None), rotary)


def one_stream(x, layer, name: str):
    """The residual of every family but one: a sub-layer reads x [B, S, H] and
    its output is added to it -> (x, `write`: F(x) -> x + F(x), no residue)."""
    return x, lambda out: x + out, None


@dataclasses.dataclass(frozen=True)
class HyperConnections:
    """The residual strategy of a family whose residual is `n` streams wide
    (manifold-constrained hyper-connections, arXiv:2512.24880;
    `models/xing4.py`): the trunk's carry is X [B, S, n, H], and around EACH
    sub-layer F (`name` "attn" or "mlp") three maps are computed from the
    streams themselves, in float32 from the streams as they are:

        x~     = RMSNorm(vec(X))               [n H], eps `eps`, no weight
        H~     = alpha * (x~ phi) + bias       phi [n H, 2 n + n^2], the columns
                                               [pre | post | res], alpha one
                                               scalar a part, bias [2 n + n^2]
        H_pre  = sigmoid(H~_pre) [n];  H_post = 2 sigmoid(H~_post) [n]
        H_res  = Sinkhorn(exp(clip(H~_res, clamp))) [n, n]: `sinkhorn_iters`
                 times rows / (their sums + eps), then columns / (theirs + eps)
        u      = H_pre X                        the sub-layer's input [H]
        X'     = H_res X + H_post^T F(u)

    held by the layer as `hc_<name>_phi` (the model's dtype: the product takes
    its operands as they are and accumulates in float32, as `moe.router_logits`
    does), `hc_<name>_alpha` [3] and `hc_<name>_bias` (float32). Scopes, read
    by name in a profile as siblings of `attn`, `mlp` and `moe/*`: `hc/map`
    (the norm and the projection), `hc/sinkhorn` (the iterations), `hc/mix`
    (`H_pre X`; and, inside the sub-layer's own scope, `H_res X`, `H_post^T
    y`). `widen` copies the embedding into the n streams and `merge` sums them
    for the head."""
    n: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: tuple = (-30.0, 30.0)

    def widen(self, x):
        return jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], self.n, x.shape[-1]))

    def merge(self, x):
        return x.astype(jnp.float32).sum(axis=2).astype(x.dtype)

    def sinkhorn(self, logits):
        """float32 [n n, T] (row-major) -> (the doubly-stochastic maps as n
        rows of n vectors [T], how far they are from it: the largest |row sum
        - 1| or |column sum - 1| over T). Every entry is a vector of its own
        and every sum n - 1 additions of such vectors, so the iterations are
        elementwise work on one shape: XLA:TPU makes ~2 small fusions an
        iteration of it, where sums over an axis of an [n, n, T] array are a
        reduction and a kernel each, 6 an iteration (PERF.md section 6, PR 37).
        A sum is inverted once and multiplied n times: n divisions in place of
        one compile the CPU's program twenty times slower."""
        n, eps = self.n, self.eps
        total = lambda vs: functools.reduce(operator.add, vs)
        m = [[jnp.exp(jnp.clip(logits[i * n + j], *self.clamp)) for j in range(n)]
             for i in range(n)]
        for _ in range(self.sinkhorn_iters):
            by_row = [1.0 / (total(row) + eps) for row in m]
            m = [[v * r for v in row] for row, r in zip(m, by_row)]
            by_col = [1.0 / (total([row[j] for row in m]) + eps) for j in range(n)]
            m = [[v * c for v, c in zip(row, by_col)] for row in m]
        sums = [total(row) for row in m] + [total([row[j] for row in m]) for j in range(n)]
        return m, jnp.abs(jnp.stack(sums) - 1.0).max()

    def read(self, x, layer, name: str):
        """X [B, S, n, H] -> (u [B, S, H], `write`: F(u) [B, S, H] -> X', the
        map's residue) for the sub-layer `name` of `layer`. Inside, tokens are
        one axis T = B S and a map is vectors [T], one an entry."""
        B, S, n, H = x.shape
        T = B * S
        with jax.named_scope("hc/map"):
            flat = x.reshape(T, n * H)
            sq = jnp.square(flat.astype(jnp.float32)).mean(axis=-1, keepdims=True)
            proj = jnp.dot(flat, layer[f"hc_{name}_phi"],
                           preferred_element_type=jnp.float32) * jax.lax.rsqrt(sq + self.eps)
            alpha = jnp.repeat(layer[f"hc_{name}_alpha"], np.array([n, n, n * n]),
                               total_repeat_length=2 * n + n * n)
            maps = (proj * alpha + layer[f"hc_{name}_bias"]).T        # [2 n + n^2, T]
            h_pre = jax.nn.sigmoid(maps[:n])[..., None]
            h_post = 2.0 * jax.nn.sigmoid(maps[n:2 * n])[..., None]
        with jax.named_scope("hc/sinkhorn"):
            h_res, residue = self.sinkhorn(maps[2 * n:])
        streams = [x[:, :, i].reshape(T, H).astype(jnp.float32) for i in range(n)]
        with jax.named_scope("hc/mix"):
            u = sum(h_pre[i] * streams[i] for i in range(n)).astype(x.dtype).reshape(B, S, H)

        def write(out):
            out = out.reshape(T, H).astype(jnp.float32)
            with jax.named_scope("hc/mix"):
                mixed = [sum(h_res[i][j][:, None] * streams[j] for j in range(n))
                         + h_post[i] * out for i in range(n)]
                return jnp.stack(mixed, axis=1).astype(x.dtype).reshape(B, S, n, H)

        return u, write, residue


def decoder_layer(cfg: LlamaConfig, x, layer, cache, positions, attention,
                  mlp=dense_mlp, reduce=lambda t: t, index=None,
                  residual: HyperConnections | None = None):
    """x [B, S, H] through one pre-norm decoder block, the `index`-th of the
    stack -> (x, the updated cache, the MLP's stats). The one spelling that
    every family, both cached forwards and the pipeline's stage run; they
    differ in two strategies:

    - `attention(cfg, y, layer, cache, positions, index) -> (o, cache)` on
      the normalised activations y [B, S, H]: it owns the projections, the
      rotation and the cache, and hands back the heads' outputs o [B, S, Hq,
      Dv] before the output projection `wo`, which is the layer's.
      `gqa_attention(attend)` is the grouped-query families' (a cache
      strategy inside it); `models/kimi_k2.py::latent_attention` caches one
      latent row a token and has a prefill and an absorbed decode path;
      `models/lfm2.py::short_conv` is no attention at all, a gated 3-tap
      convolution whose cache is two rows a SEQUENCE, and names its scope
      (`attention.scope`: the layer opens that in place of `attn`);
    - `mlp(y, layer) -> (out, stats)` on the normalised activations:
      `dense_mlp`, or `moe.moe_mlp`, whose scopes stand beside `mlp`.

    `reduce` is a tensor-sharded stage's sum over its axis of the two
    row-sharded products. What a layer does beyond that follows from the keys
    it holds: with `attn_out_norm` / `mlp_out_norm` (Ouro's sandwich) it
    normalises a sub-layer's output before adding it to the residual.

    `residual` is the third strategy, None (`one_stream`: `x + o`, the program
    it was) but in a family whose residual is several streams wide: x is then
    X [B, S, n, H], each sub-layer reads its input as a learned mix of the
    streams and its output is written back into all of them
    (`HyperConnections.read`; the write's `hc/mix` lies inside the sub-layer's
    scope, where the one stream's add lies), and the stats gain `hc_residue`,
    how far the worse of the layer's two stream-mixing maps is from doubly
    stochastic.

    A block that is ONE sub-layer (`models/nemotron_h.py`: a mixer OR a
    feed-forward part, one norm) passes None for the strategy it does not
    have: that sub-layer is not run and its norm is not held."""
    B, S = x.shape[:2]
    eps = cfg.rms_eps
    read = one_stream if residual is None else residual.read
    stats, residues = {}, []
    if attention is not None:
        u, write, residue = read(x, layer, "attn")
        residues.append(residue)
        # the scopes are names in a profile and in the HLO's op_name, no more; a
        # mixer that is no attention says its own (`models/lfm2.py::short_conv`)
        with jax.named_scope(getattr(attention, "scope", "attn")):
            y = rms_norm(u, layer["attn_norm"], eps)
            o, cache = attention(cfg, y, layer, cache, positions, index)
            o = reduce(o.reshape(B, S, -1) @ layer["wo"])
            if "attn_out_norm" in layer:
                o = rms_norm(o, layer["attn_out_norm"], eps)
            x = write(o)
    if mlp is not None:
        u, write, residue = read(x, layer, "mlp")
        residues.append(residue)
        # `mlp` is opened around the strategy, not over it: the expert layer's
        # scopes are read by name as siblings of `attn` and `mlp`, not children
        with jax.named_scope("mlp"):
            y = rms_norm(u, layer["mlp_norm"], eps)
        out, stats = mlp(y, layer)
        with jax.named_scope("mlp"):
            out = reduce(out)
            if "mlp_out_norm" in layer:
                out = rms_norm(out, layer["mlp_out_norm"], eps)
            x = write(out)
    if residual is not None:
        stats = {**stats, "hc_residue": functools.reduce(jnp.maximum, residues)}
    return x, cache, stats


def remat_body(body, cfg: LlamaConfig):
    """The scan body under `jax.checkpoint` by the configuration's policy."""
    if not cfg.remat:
        return body
    if cfg.remat_policy not in ("full", "dots"):
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r}")
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat_policy == "dots" else None)
    return jax.checkpoint(body, prevent_cse=False, policy=policy)


def lm_head(params, x, cfg: LlamaConfig, normed: bool = False):
    """Final norm (unless x is `normed` already) and the tied or untied
    output head: [..., H] -> float32 logits [..., V]."""
    if not normed:
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head.astype(cfg.dtype)).astype(jnp.float32)


class Run(NamedTuple):
    """`count` layers in a row of ONE kind, in a stack whose layers are of
    several (`decoder_trunk(runs=)`): the kind's parameters are the stack
    `params[stack]` ([n, ...] a leaf: one stack a kind of mixer and kind of
    MLP) and the run is its layers [first, first + count); `attention` and
    `mlp` are the kind's two strategies (`decoder_layer`), either of them None
    in a kind whose block is one sub-layer; `cache_first` is
    where the run starts in the cache of its kind of MIXER, which counts its
    own layers (the 3rd attention layer of a stack is KV layer 2 wherever it
    stands, and a convolution layer between two of them is none); `scope`
    names the run in a profile, if anything does; `held` holds the run's x
    behind an `optimization_barrier`, as a scan's loop edge holds its carry:
    over a stack unrolled into 52 runs of one XLA:TPU re-associates the
    residual's chain of adds into ONE sum at the program's end and keeps every
    block's output until then (30 x 22 MB of a 4,096-token prefill, scratch
    1.54 GB where it is 0.64 held; PERF.md section 6, PR 45)."""
    stack: str
    first: int
    count: int
    attention: Callable | None
    mlp: Callable | None
    cache_first: int = 0
    scope: str | None = None
    held: bool = False


def decoder_trunk(params, tokens, cfg: LlamaConfig, attention=None, mlp=dense_mlp,
                  cache=None, positions=None, head_rows=None,
                  residual: HyperConnections | None = None,
                  runs: Sequence[Run] | None = None):
    """Token ids [B, S] -> (float32 logits [B, S, V], the updated cache, the
    layers' stats stacked): embedding, the layers as RUNS of one kind each,
    `lm_head`. A run is a `lax.scan` of `decoder_layer` over its layers with
    its kind's two strategies; what every layer of the stack counts comes
    back stacked over the layers that count it, in their order.

    A family of one kind of layer names no runs: its stack is ONE run,
    `params["layers"]` whole under `attention` and `mlp`, and the scan slices
    it. Where the parameters also hold `lead_layers` (a family whose first
    layers are dense ahead of its expert layers: `models/kimi_k2.py`), that
    shorter stack is a run ahead of it (scope `lead`) with `dense_mlp` and the
    same attention strategy, at the cache indices [0, its length), and
    `layers` follows at the indices after it.

    `runs` (a family whose layers are of several kinds in an order that is no
    prefix and no period: `models/lfm2.py`, 13 runs of 1 to 3 layers over
    three stacks) is the stack in order. A run that is PART of its stack scans
    the layers' places in it and takes each layer out where it is used (what a
    scan does with its `xs`, so a weight is read once and no slice of a stack
    is copied for the loop); a run of one layer is the bare body at a constant
    index. `attention` and `mlp` are then not read.

    `head_rows` is which positions' logits the caller reads: None for every
    one, or int32 [B] (traced: one program whatever its values) for position
    `head_rows[b]` of sequence b alone. The rows are taken out of x between
    the last layer (a looped family's last norm) and `lm_head`, so the head
    multiplies [B, 1, H] and the logits are [B, 1, V]: a 2,048-token prefill
    that samples from its last live position neither computes nor writes the
    other 2,047 rows of [S, V] float32 (PERF.md section 6, PR 32).

    The cache is never an `xs`/`ys` of the scan: it rides in the carry beside
    x, whole (leaves [L, ...]), and `attention` gets it with the layer's index.
    A scan that slices a layer out of a stacked cache and stacks the result
    back builds a second cache and copies every layer twice a step (PERF.md
    section 6, PR 30: 80% of a decode step's device time); a carried buffer
    that each layer updates at `[index, ...]` stays where it is. Without a
    cache it is a training forward: the carry's cache is None, no index is
    scanned, and the body runs under `remat_body`; a cached forward scans the
    bare body (a `checkpoint` in a decode step would be a different program).

    `cfg.loop_steps` > 1 (Ouro) applies the WHOLE stack that many times in
    sequence with the same weights: a `lax.scan` over passes around the scan
    over layers, the final norm at the end of every pass (the last pass's is
    the head's), and pass `r` of layer `l` at the cache index `r * L + l`, so
    the cache has `loop_steps * L` layers (scope `loop` a pass, `loop/norm`
    the norm between passes; stats are stacked [passes, L, ...]).

    `residual` (`HyperConnections`; None: the one stream x) widens the carry
    to n streams [B, S, n, H]: the embedding is copied into each before the
    first layer, every layer mixes them around its two sub-layers, and they
    are summed between the last layer and the head, after `head_rows` has
    taken its rows. The stats' `hc_residue` then covers the leading dense
    stack too: [lead + L]."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = params["embed"][tokens].astype(cfg.dtype)
    if residual is not None:
        x = residual.widen(x)
    cached = cache is not None
    if runs is None:
        runs = [Run("layers", 0, cfg.num_layers, attention, mlp)]
        lead = params.get("lead_layers")
        if lead is not None:
            n_lead = jax.tree.leaves(lead)[0].shape[0]
            runs = [Run("lead_layers", 0, n_lead, attention, dense_mlp, scope="lead"),
                    runs[0]._replace(cache_first=n_lead)]
    if len(runs) > 1 and cfg.loop_steps != 1:
        raise ValueError("a looped stack of more than one run: no family has "
                         "both, and the cache's indices are unsaid")
    # the layers' places, in the order the runs' scans were always traced:
    # the last run's first
    places = [jnp.arange(run.count, dtype=jnp.int32) for run in reversed(runs)][::-1]

    def run_layers(run: Run, place, x, cache, offset):
        """One run over the carry -> ((x, cache), its stats [count, ...]);
        `offset` (a pass of a looped stack) is added to its cache indices."""
        stack = params[run.stack]
        whole = run.first == 0 and run.count == jax.tree.leaves(stack)[0].shape[0]

        def body(carry, layer_and_index):
            x, cache = carry
            layer, index = layer_and_index
            if not whole:  # its place in the stack: the layer is taken out here
                layer = jax.tree.map(partial(jax.lax.dynamic_index_in_dim, index=layer,
                                             keepdims=False), stack)
            x, cache, stats = decoder_layer(
                cfg, x, layer, cache, positions, run.attention, run.mlp, index=index,
                residual=residual)
            return (x, cache), stats

        index = None
        if cached:
            index = place if run.cache_first == 0 else run.cache_first + place
            index = index if offset is None else offset + index
        of_stack = stack if whole else run.first + place
        if run.count == 1 and not whole:
            carry, stats = body((x, cache), jax.tree.map(lambda a: a[0], (of_stack, index)))
            return carry, jax.tree.map(lambda a: a[None], stats)
        return jax.lax.scan(body if cached else remat_body(body, cfg), (x, cache),
                            (of_stack, index))


    def stack(x, cache, offset=None):  # the layers once, over the carry
        counted = []
        for run, place in zip(runs, places):
            with jax.named_scope(run.scope) if run.scope else contextlib.nullcontext():
                (x, cache), stats = run_layers(run, place, x, cache, offset)
                if run.held:
                    x = jax.lax.optimization_barrier(x)
            counted.append(stats)
        # what a layer counts, stacked over the runs whose layers count it
        # (a leading dense run counts no expert rows)
        by_name = {}
        for stats in counted:
            for name, value in stats.items():
                by_name.setdefault(name, []).append(value)
        return (x, cache), {name: values[0] if len(values) == 1 else jnp.concatenate(values)
                            for name, values in by_name.items()}

    def head(x, normed=False):
        if head_rows is not None:
            rows = head_rows[:, None, None]
            x = jnp.take_along_axis(x, rows if residual is None else rows[..., None], axis=1)
        if residual is not None:
            x = residual.merge(x)
        return lm_head(params, x, cfg, normed)

    if cfg.loop_steps == 1:
        (x, cache), stats = stack(x, cache)
        return head(x), cache, stats

    def one_pass(carry, step):
        with jax.named_scope("loop"):
            (x, cache), stats = stack(*carry, step * cfg.num_layers if cached else None)
            with jax.named_scope("norm"):
                x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return (x, cache), stats

    (x, cache), stats = jax.lax.scan(
        one_pass, (x, cache), jnp.arange(cfg.loop_steps, dtype=jnp.int32))
    return head(x, normed=True), cache, stats


def forward(params, tokens, cfg: LlamaConfig, attn_fn=None, positions=None):
    """Token ids [B, S] → logits [B, S, vocab] (fp32)."""
    return decoder_trunk(params, tokens, cfg, plain_attend(attn_fn),
                         positions=positions)[0]


def token_nll(logits, targets):
    """(Summed cross-entropy, number of targets that count) of float32 logits
    [B, S, V]; targets [B, S] with -100 = ignore."""
    valid = targets != -100
    tsafe = jnp.where(valid, targets, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tsafe[..., None], axis=-1)[..., 0]
    return ((logz - gold) * valid).sum(), valid.sum()


def next_token_loss(logits, targets):
    """Mean cross-entropy of float32 logits [B, S, V]; targets [B, S] with
    -100 = ignore."""
    nll_sum, count = token_nll(logits, targets)
    return nll_sum / jnp.maximum(count, 1)


def loss_fn(params, tokens, targets, cfg: LlamaConfig, attn_fn=None):
    """Next-token cross-entropy; targets [B, S] with -100 = ignore."""
    return next_token_loss(forward(params, tokens, cfg, attn_fn), targets)


def _model_loss(params, tokens, targets, cfg: LlamaConfig, attn_fn, mesh=None):
    return loss_fn(params, tokens, targets, cfg, attn_fn), {}


def param_count_analytic(cfg: LlamaConfig) -> int:
    h, m, L, v = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.vocab_size
    hd, nh, nkv = cfg.hd, cfg.num_heads, cfg.num_kv_heads
    per_layer = h * nh * hd + 2 * h * nkv * hd + nh * hd * h + 3 * h * m + 2 * h
    total = v * h + L * per_layer + h
    if not cfg.tie_embeddings:
        total += h * v
    return total


# ---------------------------------------------------------------- KV-cached inference
def pool_head_dim(head_dim: int) -> int:
    """A head's width in a row of the paged pool: whole 128-lane tiles."""
    return -(-head_dim // 128) * 128


def init_kv_pool(cfg: LlamaConfig, num_blocks: int, block_size: int) -> dict:
    """Paged KV pool: [L, N_blocks, block_size, Hkv * Dp] per k/v, token
    major: a token's keys of all KV heads are ONE contiguous row, and a page
    (layer, block) is one contiguous [block_size, Hkv * Dp] run. L is the
    (K, V) pairs a token caches: the model's layers, or passes x layers where
    the stack runs several times (`cfg.loop_steps`, `decoder_trunk`).

    Unlike a dense per-slot cache ([L, B, Smax, Hkv, D]), HBM is allocated in
    block_size-token pages handed out on demand by a host-side allocator
    (serve/paged_kv.py), so memory scales with ACTUAL tokens, full prefix
    blocks are shareable across sequences, and capacity admits many short
    sequences or few long ones interchangeably (vLLM paged-KV semantics,
    which the reference delegates to vLLM — here native). Block 0 is the
    garbage block that padded positions and empty slots write into.

    Layout contract (with `forward_paged` and ops/paged_attention.py):

    - Dp is `head_dim` rounded up to 128 lanes; head h of a row is lanes
      [h * Dp, h * Dp + head_dim), the rest of its tile zero. Mosaic slices an
      HBM ref in whole lane tiles only, so the pool is ALLOCATED that wide
      (Mistral's and OLMoE's 128: no padding) and nothing pads it a call.
    - Token major because the write decides the layout. A decode step writes
      one token's [Hkv, D] a slot and layer; in a head-major pool
      ([L, Hkv, NB, BS, D]) that is Hkv strided pieces, XLA:TPU then keeps the
      carried pool in a layout with the written window minor-most and copies
      ALL of it back to the default layout for the kernel in every layer
      (PERF.md section 6, PR 30, the second ahead-of-time finding). Here the
      write is a row scatter in the default layout, which the kernel reads.
      A prefill that starts at position 0 writes whole pages, each one
      contiguous run, and scatters no row (`write_pages`)."""
    row = cfg.num_kv_heads * pool_head_dim(cfg.hd)
    shape = (cfg.loop_steps * cfg.num_layers, num_blocks, block_size, row)
    return {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": jnp.zeros(shape, dtype=cfg.dtype),
    }


def page_rows(tables, lengths, S: int, block_size: int):
    """Where S new tokens a sequence land in a paged pool: (positions,
    blk_idx, blk_off), each [B, S]: the tokens' positions [lengths, lengths +
    S), and the pool block and the row in it that each is written to, through
    the block tables [B, max_blocks]."""
    B, max_blocks = tables.shape
    positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    seq_blk = positions // block_size
    # Pad positions past the table (bucketed prefill of a near-full sequence)
    # must scatter into the reserved garbage block 0 — jax's gather clamp
    # would otherwise alias them onto the REAL last block and clobber it.
    oob = seq_blk >= max_blocks
    blk_idx = tables[jnp.arange(B)[:, None], jnp.where(oob, 0, seq_blk)]  # [B,S]
    blk_idx = jnp.where(oob, 0, blk_idx)
    return positions, blk_idx, positions % block_size


def writes_pages(fresh: bool, S: int, block_size: int) -> bool:
    """Whether S new tokens a sequence are whole pages of a paged pool: told
    `fresh` every sequence starts at position 0, block-aligned, so S rows that
    fill whole blocks are S / block_size pages and page j is pool block
    `tables[b, j]` (`write_pages`). Anything else lands row by row
    (`page_rows`): a decode step's one row a slot, a suffix whose start is
    traced, a length that ends inside a block. Both facts are static where a
    step is traced, and the engine knows them on the host
    (`serve/llm_paged.py::prefill_writes`)."""
    return fresh and S % block_size == 0


def write_pages(leaf, layer, tables, rows, block_size: int):
    """The pool's write of a prefill that starts at position 0
    (`writes_pages`): `leaf[layer, tables[b, j]] = rows[b, j * block_size:(j +
    1) * block_size]` for every sequence b and page j; `leaf` `[L, NB, BS,
    row]`, its new `rows` `[B, S, row]`. ONE scatter whose window is a page,
    one contiguous `[block_size, row]` run of the pool, with S / block_size
    indices a sequence where the row scatter has S: XLA:TPU walks a scatter's
    indices one at a time, ~125 ns each whatever the window, so 2,048 rows of
    1,024 lanes take 0.27 ms a leaf and their 128 pages 0.03 (PERF.md section
    6, PR 43, where a page-copy kernel's 0.02 is weighed against it).

    The SAME pool as the row scatter at `page_rows`'s places, bit for bit, in
    every block but the garbage block 0: the bucket's padding rows lie at
    their positions as they did, the tail of a last live block and a block
    reserved for the first decoded token get the same rows, and a page past
    the allocation (table entry 0) or past the table goes to block 0 as its
    rows did, several of them, in any order."""
    B, S, row = rows.shape
    n_pages = S // block_size
    pages = tables[:, :n_pages]
    if n_pages > tables.shape[1]:   # padding past the table: block 0
        pages = jnp.pad(pages, ((0, 0), (0, n_pages - tables.shape[1])))
    return leaf.at[layer, pages].set(
        rows.reshape(B, n_pages, block_size, row).astype(leaf.dtype))


def forward_paged(params, tokens, cfg: LlamaConfig, pool: dict, tables, lengths,
                  block_size: int, use_kernel: bool | None = None,
                  platform: str | None = None, mlp=dense_mlp, head_rows=None,
                  fresh: bool = False):
    """Cached forward over a PAGED pool (`init_kv_pool`'s layout). tokens
    [B,S] append at positions [lengths, lengths+S); tables [B, max_blocks] map
    sequence-block index -> pool block id. Returns (logits [B,S,V], updated
    pool); with `head_rows` (`decoder_trunk`: int32 [B]) the logits of that
    one position a sequence, [B,1,V].

    The pool rides whole in the layer scan's carry (`decoder_trunk`) and each
    layer touches only its own pages of it: new K/V rows scatter into
    `pool[layer, block, offset]` in place (scope `attn/kv_write`), whatever
    reads them; a `fresh` prefill whose S rows fill whole blocks writes them
    as S / block_size whole pages into `pool[layer, tables[:, :S /
    block_size]]` (`write_pages`: a scatter of pages), the same pool outside
    the garbage block. Which keys a layer's queries then read, three ways:

    - `fresh`: every sequence starts at position 0 (`lengths` is all zero: a
      prompt with no cached prefix), so every key a row may see is one of the
      rows this call has just computed. The heads' outputs come from the q, k,
      v in hand, `auto_attention(q, k, v, causal=True)`: the flash forward
      kernel from S = 1,024 up on a TPU, dense [S, S] scores under that and
      off the TPU (scope `attn/prompt_attend`). Nothing is read back from the
      pool, no table is gathered, no column past S is scored. A bucket's
      padding needs no mask of its own: it lies after the live rows, which
      under the causal mask never see it, and its own outputs are dropped by
      the caller. `lengths` is traced and the kernel lists its live tiles at
      trace time, so this is a fact the CALLER states, statically: the engine
      knows it on the host (`serve/llm_paged.py::prefill_reads`);
    - the decode step (S == 1) on a TPU reads the live pages of `pool[layer]`
      through the block table in the pallas kernel (ops/paged_attention.py;
      scope `attn/kv_read`);
    - everything else (a prompt that continues a cached prefix, a window of
      several tokens, S == 1 off the TPU) reads the gathered per-sequence
      view `pool[layer, tables]`, the whole table's `max_blocks * block_size`
      positions whatever S is, with float32 scores against all of them
      (scope `attn/kv_read`).

    A step that donates the pool compiles to a program with no pool-sized
    temporary (tests/test_tpu_aot.py holds it to that). `platform` is where
    the computation runs (the engine passes its own); None derives it from the
    inputs' placement. It picks the default for `use_kernel` (the paged kernel
    at S == 1 on a TPU; `auto_attention`'s own crossover when `fresh`) and,
    when a kernel is used off-TPU (tests: `use_kernel=True`, with `fresh` the
    flash forward at any S), interpret mode. `mlp` is `decoder_layer`'s
    strategy (the expert layer of a MoE family). Where `cfg.loop_steps` > 1
    the pool holds `loop_steps * L` cache layers and `layer` below is the
    cache layer `pass * L + layer`."""
    B, S = tokens.shape
    if platform is None:
        platform = target_platform(tokens, pool["k"])
    if use_kernel is None:
        use_kernel = S == 1 and platform == "tpu" and not fresh
    positions, blk_idx, blk_off = page_rows(tables, lengths, S, block_size)
    attend = paged_attend(cfg, tables, lengths, positions, blk_idx, blk_off, block_size,
                          use_kernel, platform, fresh)
    return decoder_trunk(params, tokens, cfg, gqa_attention(attend), mlp,
                         cache=pool, positions=positions, head_rows=head_rows)[:2]


def pool_rows(t, dtype, head_dim: int):
    """t [B, S, Hkv, D] -> a paged pool's (or a ring's) rows [B, S, Hkv * Dp]:
    each head padded to its whole 128-lane tiles (`pool_head_dim`)."""
    dp = pool_head_dim(head_dim)
    if dp != head_dim:
        t = jnp.pad(t, [(0, 0)] * 3 + [(0, dp - head_dim)])
    return t.reshape(*t.shape[:2], -1).astype(dtype)


def paged_attend(cfg: LlamaConfig, tables, lengths, positions, blk_idx, blk_off,
                 block_size: int, use_kernel: bool, platform: str, fresh: bool):
    """`forward_paged`'s cache strategy (`gqa_attention(attend)`) over the
    leaves `k` and `v` of a paged pool, for S new tokens a sequence that land
    at (blk_idx, blk_off) [B, S] (`page_rows`): the write of their rows, and
    the read by one of `forward_paged`'s three ways. What else the pool holds
    (a family's second kind of cache: `models/lfm2.py`) goes through as it
    is."""
    B, S = positions.shape
    max_blocks = tables.shape[1]
    hd, dp = cfg.hd, pool_head_dim(cfg.hd)

    rows = partial(pool_rows, head_dim=hd)

    def attend(q, k, v, pool, layer):  # the whole pool and this layer's index
        kp, vp = pool["k"], pool["v"]
        # the scopes name, in a profile, the statement behind each pool-shaped
        # operation of a step: attn/kv_write or attn/kv_read
        with jax.named_scope("kv_write"):
            if writes_pages(fresh, S, block_size):
                # whole pages: kp[layer, tables[b, j]] = k[b, j * BS:(j + 1) * BS]
                kp = write_pages(kp, layer, tables, rows(k, kp.dtype), block_size)
                vp = write_pages(vp, layer, tables, rows(v, vp.dtype), block_size)
            else:
                # a row scatter: kp[layer, blk_idx[b,s], blk_off[b,s]] = k[b,s]
                kp = kp.at[layer, blk_idx, blk_off].set(rows(k, kp.dtype))
                vp = vp.at[layer, blk_idx, blk_off].set(rows(v, vp.dtype))
        if fresh:
            # it reads no pool, so it is not `kv_read`'s
            with jax.named_scope("prompt_attend"):
                if use_kernel:
                    from ray_tpu.ops.flash_attention import flash_attention

                    o = flash_attention(q, k, v, causal=True,
                                        interpret=platform != "tpu")
                else:
                    o = auto_attention(q, k, v, causal=True, platform=platform)
            return o, {**pool, "k": kp, "v": vp}
        with jax.named_scope("kv_read"):
            if use_kernel:
                from ray_tpu.ops.paged_attention import paged_decode_attention

                o = paged_decode_attention(
                    q[:, 0], kp, vp, tables, lengths + 1, layer=layer,
                    interpret=platform != "tpu")[:, None]  # [B,1,Hq,D]
            else:
                view = lambda p: p[layer, tables].reshape(
                    B, max_blocks * block_size, -1, dp)[..., :hd]
                o = _cached_attention(q, view(kp), view(vp), lengths, positions)
        return o, {**pool, "k": kp, "v": vp}

    return attend


def ring_rows(n, window: int):
    """Which position each row of a window layer's ring holds once a
    sequence has `n` [B] tokens: row r holds the LAST position p < n with p %
    window == r -> int32 [B, window], negative where the ring has not come
    round to the row yet (n <= r: whatever lies there is not the sequence's)."""
    r = jnp.arange(window, dtype=jnp.int32)[None, :]
    last = n[:, None] - 1
    return last - (last - r) % window


def window_attend(cfg: LlamaConfig, window: int, rings, lengths, live, use_kernel: bool,
                  platform: str, fresh: bool):
    """The cache strategy (`gqa_attention(attend)`) of a SLIDING-WINDOW layer
    (query i sees key j iff j <= i and i - j < window) over the pool's leaves
    `k_win` and `v_win`, `[Lw, NS, window, Hkv * Dp]`: a RING a sequence, ring `rings[b]`
    [B] (0: the garbage ring, a dead row's), position p at row p % window. A
    sequence's window layers hold `window` rows whatever its length, where
    `paged_attend`'s hold a row a token; writing position p overwrites p -
    window, which the window has just passed. `lengths` [B] are the sequences'
    lengths before the call and `live` [B] how many of its S tokens are live
    (the rest pad a bucket and are written nowhere: their rows would displace
    live positions). Two ways, by what a call is:

    - `fresh` (every sequence starts at position 0): attention over the rows
      in hand under the band (`auto_attention(window=)`: the banded flash
      forward from S = 1,024 up on a TPU; scope `prompt_attend`), and the
      ring written WHOLE, one page a sequence: row r takes the last live
      position of its residue (`ring_rows`), or anything at all where there
      is none yet (scope `kv_write`);
    - a decode step (S == 1): the token's row to `lengths % window`, then the
      ring's live rows read in place by the window kernel
      (`ops/paged_attention.py::window_decode_attention`) or, off the TPU, the
      gathered ring with the rows it has not come round to masked (scope
      `kv_read`). A ring is never trusted to hold zeros or its last owner's
      rows: what is read is masked by the sequence's OWN length.

    More than one token over a ring that holds a past (a prompt behind a
    cached prefix, a speculative window) is refused: the engine hands a pool
    with `Model.sequence_leaves` neither (ROADMAP R2)."""
    hd, dp = cfg.hd, pool_head_dim(cfg.hd)
    rows = partial(pool_rows, head_dim=hd)

    def attend(q, k, v, pool, layer):
        B, S = q.shape[:2]
        kr, vr = pool["k_win"], pool["v_win"]
        if fresh:
            with jax.named_scope("kv_write"):
                held = jnp.clip(ring_rows(live, window), 0, S - 1)[..., None]   # [B, W, 1]
                take = lambda t, leaf: leaf.at[layer, rings].set(
                    jnp.take_along_axis(rows(t, leaf.dtype), held, axis=1))
                kr, vr = take(k, kr), take(v, vr)
            with jax.named_scope("prompt_attend"):
                if use_kernel:
                    from ray_tpu.ops.flash_attention import flash_attention

                    o = flash_attention(q, k, v, causal=True, window=window,
                                        interpret=platform != "tpu")
                else:
                    o = auto_attention(q, k, v, causal=True, platform=platform,
                                       window=window)
            return o, {**pool, "k_win": kr, "v_win": vr}
        if S != 1:
            raise NotImplementedError(
                f"{S} tokens a sequence over a window layer's ring that holds a past: a "
                f"ring keeps the last {window} positions and no earlier state to resume "
                f"from; a prefill starts at position 0 (`fresh`) and a decode step "
                f"appends one token")
        with jax.named_scope("kv_write"):
            at = lengths % window
            kr = kr.at[layer, rings, at].set(rows(k, kr.dtype)[:, 0])
            vr = vr.at[layer, rings, at].set(rows(v, vr.dtype)[:, 0])
        with jax.named_scope("kv_read"):
            live_rows = jnp.minimum(lengths + 1, window)   # the token's own among them
            if use_kernel:
                from ray_tpu.ops.paged_attention import window_decode_attention

                o = window_decode_attention(q[:, 0], kr, vr, rings, live_rows, layer=layer,
                                            interpret=platform != "tpu")[:, None]
            else:
                # rows [0, live) of the gathered ring, in ring order
                view = lambda ring: ring[layer, rings].reshape(B, window, -1, dp)[..., :hd]
                o = _cached_attention(q, view(kr), view(vr), live_rows - 1,
                                      (live_rows - 1)[:, None])
        return o, {**pool, "k_win": kr, "v_win": vr}

    return attend


def _cached_attention(q, k_cache, v_cache, lengths, q_positions):
    """q: [B,S,Hq,D]; caches [B,Smax,Hkv,D]; lengths [B] = valid KV prefix."""
    B, S, Hq, D = q.shape
    Smax = k_cache.shape[1]
    Hkv = k_cache.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, S, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache).astype(jnp.float32) / math.sqrt(D)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (B, Smax), 1)
    valid = kpos[:, None, None, None, :] <= q_positions[:, None, None, :, None]
    valid &= kpos[:, None, None, None, :] < lengths[:, None, None, None, None] + q.shape[1]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_cache)
    return out.reshape(B, S, Hq, D)


# what train/spmd.py and the serving engine take of a model
# (ray_tpu/models/__init__.py)
MODEL = Model(init=init, logical_axes=logical_axes, loss=_model_loss,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
