"""Looped decoder (ByteDance Ouro-1.4B / 2.6B): a layer stack run several times.

The Llama backbone (`llama.decoder_layer` in `llama.decoder_trunk`: pre-norm
attention with rotary positions and a SwiGLU MLP, no grouping at the
published sizes) with two changes, both the trunk's and the layer's own:

    x_0 = embed[tokens]
    for pass r = 1..loop_steps, for layer l = 1..L:       # the SAME L layers
        a = Attn_l(RMSNorm(x; attn_norm_l))               # K/V of THIS pass and layer
        x = x + RMSNorm(a; attn_out_norm_l)               # sandwich norm
        m = SwiGLU_l(RMSNorm(x; mlp_norm_l))
        x = x + RMSNorm(m; mlp_out_norm_l)                # sandwich norm
      after layer L of every pass:  x = RMSNorm(x; final_norm)
    logits = x @ lm_head

Pass r of layer l attends to the keys and values that pass r of layer l
computed for the earlier positions, so a token caches `loop_steps * L` (K, V)
pairs: the cache's layer `r * L + l` (`llama.decoder_trunk`, `llama.init_kv_pool`).

The published model also has an exit gate (a `hidden -> 1` map on each pass's
output) by which a token may leave the loop early. At the published
`early_exit_threshold` of 1 no token does, the gate does not enter the logits,
and its two tensors are not made here; leaving early per token (a cache with
holes for the passes skipped) is ROADMAP R10.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, llama


@dataclasses.dataclass(frozen=True)
class OuroConfig(llama.LlamaConfig):
    loop_steps: int = 4        # the published `total_ut_steps`

    @staticmethod
    def tiny() -> "OuroConfig":  # for tests
        return OuroConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
            num_heads=4, num_kv_heads=4, max_seq_len=128, rms_eps=1e-6,
            dtype=jnp.float32, remat=False, loop_steps=3)

    @staticmethod
    def ouro_2_6b() -> "OuroConfig":
        """ByteDance/Ouro-2.6B as its config.json has it."""
        return OuroConfig(
            vocab_size=49152, hidden_size=2048, intermediate_size=5632,
            num_layers=48, num_heads=16, num_kv_heads=16, head_dim=128,
            max_seq_len=65536, rope_theta=1e6, rms_eps=1e-6, loop_steps=4)


_OUT_NORMS = ("attn_out_norm", "mlp_out_norm")


def logical_axes(cfg: OuroConfig) -> dict:
    ax = llama.logical_axes(cfg)
    return {**ax, "layers": {**ax["layers"], **{k: (None, None) for k in _OUT_NORMS}}}


def init(cfg: OuroConfig, key: jax.Array) -> dict:
    """`llama.init` and the two output norms a layer: four norms a layer. The
    output norms start at `1 / sqrt(2 L)`, the family's residual scaling (the
    one GPT-2 gives its output projections, put where this family has a
    weight for it): a pass's 2 L sub-layer outputs then add up to the size of
    its input. Each is normalised to that weight whatever the residual's own
    size, so at a weight of one the residual of a freshly made stack doubles
    in the first layer of every pass and its logits follow rounding error,
    not the tokens."""
    params = llama.init(cfg, key)
    out = jnp.full((cfg.num_layers, cfg.hidden_size),
                   (2 * cfg.num_layers) ** -0.5, jnp.float32)
    return {**params, "layers": {**params["layers"], **{k: out for k in _OUT_NORMS}}}


# The trunk runs `cfg.loop_steps` passes and the pool holds `loop_steps * L`
# cache layers from the configuration alone, and the layer applies the output
# norms it holds: the forwards, the loss and the pool are the Llama family's.
forward = llama.forward
forward_paged = llama.forward_paged
init_kv_pool = llama.init_kv_pool

MODEL = Model(init=init, logical_axes=logical_axes, loss=llama.MODEL.loss,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
