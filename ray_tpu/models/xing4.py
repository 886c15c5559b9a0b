"""Xing4.0 (XingChen-AGI; `model_type` `xing4_0`): the DeepSeek-V3 block of
`models/kimi_k2.py` at its own sizes, on a residual FOUR STREAMS wide that
manifold-constrained hyper-connections mix around every sub-layer
(arXiv:2512.24880; `llama.HyperConnections` has the equations).

Nothing of the block is its own: the latent attention, the latent pool, the
leading dense stack, the sigmoid-routed experts beside a shared expert and a
chip's share of them are `kimi_k2`'s and `moe.moe_mlp`'s, and the one layer in
the one trunk (`llama.decoder_layer`, `llama.decoder_trunk`) runs them with
this family's residual strategy. Its own are the configuration, the hyper-
connection weights of `init`, and a second counter of the pool: `hc_residue`,
the largest distance from one of a row or column sum of a stream-mixing map
`H_res` in the last forward, over tokens, sub-layers and layers.

What the published config does not decide, set here: the embedding is copied
into the four streams and the head reads their sum through the final norm (the
hyper-connections paper's); a sub-layer's three projections are one matrix
`phi` [n H, n + n + n^2] whose columns are [pre | post | res]; and how a freshly
made model's maps start (`init`). The next-token-prediction module
(`num_nextn_predict_layers`) is a training objective and an optional draft; it
is not made: where it reads and writes a four-stream residual is in no key of
the config (ROADMAP R8).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, kimi_k2, llama

# How a freshly made model's maps start. The static part (the biases) decides
# what a map is on average: `H_res` leans to the identity by RES_DIAGONAL in
# the exponent (a stream keeps ~0.7 of itself through a sub-layer and the
# streams stay apart), `H_pre` and `H_post` spread by MAP_SPREAD around
# sigmoid(0). The input-dependent part, `alpha (x~ phi)` with `x~ phi` unit
# normal, is of the static part's spread: alpha = MAP_SPREAD. Larger and a
# freshly made stack of 80 sub-layers is chaotic: a rounding of the streams
# moves every later map, which moves the streams (PERF.md section 6, PR 37).
MAP_SPREAD = 0.25
RES_DIAGONAL = 2.0
# every sub-layer's output projection (`wo`, `w_down`, `e_down`, `s_down`)
# starts at `1 / sqrt(2 L)` of `kimi_k2.init`'s and the embedding at unit rms
# (GPT-2's residual scaling, PR 31's cure for Ouro): the 2 L sub-layer outputs
# then add up to the size of the embedding they are added to. As `kimi_k2.init`
# leaves them, a row of the embedding is 1 / 60 of the first sub-layer's
# output and 40 layers of a freshly made stack answer to rounding, not to the
# tokens: the bfloat16 program read 0.14-0.25 off its float32 reference and a
# layer without `H_post`'s factor 2 read 0.31 (PERF.md section 6, PR 37).
_OUT_PROJECTIONS = ("wo", "w_down", "e_down", "s_down")


@dataclasses.dataclass(frozen=True)
class Xing4Config(kimi_k2.KimiK2Config):
    # hc_mult, hc_sinkhorn_iters, hc_eps, mhc_h_res_clamp_min / max
    hyper: llama.HyperConnections = llama.HyperConnections(n=4)


def _hyper_axes() -> dict:
    return {f"hc_{name}_{part}": (None,) * rank for name in ("attn", "mlp")
            for part, rank in (("phi", 3), ("alpha", 2), ("bias", 2))}


def logical_axes(cfg: Xing4Config) -> dict:
    ax = kimi_k2.logical_axes(cfg)
    return {**ax, **{stack: {**ax[stack], **_hyper_axes()}
                     for stack in ("lead_layers", "layers")}}


def _hyper_init(cfg: Xing4Config, key: jax.Array, L: int) -> dict:
    """A stack's hyper-connection weights, [L, ...] a leaf: `phi` normal at
    `1 / sqrt(n H)` in the model's dtype (so `x~ phi` is unit normal), the
    three `alpha` at MAP_SPREAD, the biases normal at MAP_SPREAD with
    RES_DIAGONAL on the diagonal of the `res` part; float32 but `phi`."""
    n, h = cfg.hyper.n, cfg.base.hidden_size
    out = {}
    for name, k in zip(("attn", "mlp"), jax.random.split(key, 2)):
        k_phi, k_bias = jax.random.split(k)
        phi = jax.random.normal(k_phi, (L, n * h, 2 * n + n * n), jnp.float32)
        bias = MAP_SPREAD * jax.random.normal(k_bias, (L, 2 * n + n * n), jnp.float32)
        bias = bias.at[:, 2 * n:].add(RES_DIAGONAL * jnp.eye(n).reshape(-1))
        out[f"hc_{name}_phi"] = (phi / math.sqrt(n * h)).astype(cfg.base.dtype)
        out[f"hc_{name}_alpha"] = jnp.full((L, 3), MAP_SPREAD, jnp.float32)
        out[f"hc_{name}_bias"] = bias
    return out


def init(cfg: Xing4Config, key: jax.Array) -> dict:
    """`kimi_k2.init` at this configuration's sizes with the residual scaled
    (`_OUT_PROJECTIONS`, the embedding), and in both stacks each sub-layer's
    hyper-connection weights (`_hyper_init`)."""
    params = kimi_k2.init(cfg, key)
    out_scale = (2 * cfg.cache_layers) ** -0.5
    k_lead, k_layers = jax.random.split(jax.random.fold_in(key, 37))

    def stack(name, k, L):
        scaled = {n: (w * out_scale).astype(w.dtype) if n in _OUT_PROJECTIONS else w
                  for n, w in params[name].items()}
        return {**scaled, **_hyper_init(cfg, k, L)}

    embed = params["embed"] * math.sqrt(cfg.base.hidden_size)
    return {**params, "embed": embed.astype(params["embed"].dtype),
            "lead_layers": stack("lead_layers", k_lead, cfg.first_k_dense),
            "layers": stack("layers", k_layers, cfg.base.num_layers)}


def init_kv_pool(cfg: Xing4Config, num_blocks: int, block_size: int) -> dict:
    """`kimi_k2.init_kv_pool` (one latent row a token and cache layer) with a
    second counter: `hc_residue`, float32."""
    pool = kimi_k2.init_kv_pool(cfg, num_blocks, block_size)
    return {**pool, "counters": {**pool["counters"],
                                 "hc_residue": jnp.zeros((), jnp.float32)}}


def forward_paged(params, tokens, cfg: Xing4Config, pool: dict, tables, lengths,
                  block_size: int, **kw):
    """`kimi_k2.forward_paged` over four streams: the same attention and
    expert strategies in the same trunk, with `cfg.hyper` as its residual."""
    return kimi_k2.forward_paged(params, tokens, cfg, pool, tables, lengths,
                                 block_size, residual=cfg.hyper, **kw)


def forward(params, tokens, cfg: Xing4Config, block_size: int = 16):
    """Token ids [B, S] -> float32 logits [B, S, V] with no cache of the
    caller's: a prefill from position 0 into a pool made for it and dropped."""
    B, S = tokens.shape
    per_seq = -(-S // block_size)
    tables = 1 + jnp.arange(B * per_seq, dtype=jnp.int32).reshape(B, per_seq)
    pool = init_kv_pool(cfg, 1 + B * per_seq, block_size)
    return forward_paged(params, tokens, cfg, pool, tables,
                         jnp.zeros((B,), jnp.int32), block_size)[0]


# it serves paged, and does not train here (PERF.md section 4)
MODEL = Model(init=init, logical_axes=logical_axes, loss=None,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
