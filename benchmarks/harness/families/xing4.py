"""The Xing4.0 family (XingChen-AGI Xing4.0-29B-A4B, `model_type` `xing4_0`:
the DeepSeek-V3 block, latent attention whose cache is one row of
`kv_lora_rank + qk_rope_head_dim` values a token, two leading dense layers,
then layers of 64 sigmoid-routed SwiGLU experts of which a token takes 4,
beside one shared expert, on a residual of `hc_mult` = 4 streams that
manifold-constrained hyper-connections mix around every sub-layer):
`ray_tpu/models/xing4.py` served by the paged engine through the program's
`Model` record. The configuration file holds ONE CHIP'S SHARE of a stated
deployment (`share`): `n_routed_experts` is the experts held here of
`share.router_outputs` that the router chooses over; depth and vocabulary are
whole. It serves only (at 16 bytes a parameter five layers of a share and the
prediction module do not fit a chip), so it has no `train_state_and_step`.
See the package docstring for what a family module holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import kimi_k2, seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record; the
# block's arithmetic is the Kimi family's at this model's sizes
from benchmarks.harness.families.kimi_k2 import (  # noqa: F401
    attention_params, cache_layers, expert_params, latent_attention_step)
from benchmarks.harness.families.llama import serve_app  # noqa: F401
from benchmarks.harness.families.ouro import kv_pool_blocks  # noqa: F401

HYPER_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
              "mhc_h_res_clamp_max")
MODEL_KEYS = kimi_k2.MODEL_KEYS + HYPER_KEYS


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `Xing4Config`: the Kimi family's block at
    these sizes, and the hyper-connections' five keys. A program without the
    family (any before PR 37) ends here, by name."""
    import dataclasses

    try:
        from ray_tpu.models import llama, xing4
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'xing4' needs `ray_tpu.models.xing4` (a residual "
            "of `hc_mult` streams mixed around every sub-layer by Sinkhorn-projected "
            "hyper-connections, `llama.HyperConnections` as the residual strategy of "
            "`llama.decoder_layer` / `decoder_trunk`, around `kimi_k2`'s latent "
            "attention and `moe.moe_mlp`'s share of the experts): this program has "
            "none, so it cannot serve Xing4.0 through build_openai_app -> "
            "PagedLLMEngine") from None
    block = kimi_k2.model_config({k: model[k] for k in kimi_k2.MODEL_KEYS}, **extra)
    hyper = llama.HyperConnections(
        n=model["hc_mult"], sinkhorn_iters=model["hc_sinkhorn_iters"], eps=model["hc_eps"],
        clamp=(float(model["mhc_h_res_clamp_min"]), float(model["mhc_h_res_clamp_max"])))
    fields = {f.name: getattr(block, f.name) for f in dataclasses.fields(block)}
    return xing4.Xing4Config(**fields, hyper=hyper)


def seeded_params(cfg, seed: int):
    """The program's own `xing4.init`, jitted once: weights are made on the
    device in the type they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import xing4

    return jax.jit(partial(xing4.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions that differ for this architecture

def hyper_params(m: dict) -> int:
    """A layer's hyper-connection matrices: for each of its two sub-layers
    one `phi` [n H, n + n + n^2] (the 3 + 2 n + n^2 scalars beside it are
    dozens)."""
    n = m["hc_mult"]
    return 2 * n * m["hidden_size"] * (2 * n + n * n)


def params_here(m: dict) -> dict:
    """Matrix weights this chip holds, by part (norms, the correction bias
    and the maps' scalars are thousands beside them): what the configuration
    file's memory arithmetic is reckoned from."""
    h = m["hidden_size"]
    dense = m["first_k_dense_replace"]
    sparse = m["num_hidden_layers"] - dense
    outside = (attention_params(m) + h * m["share"]["router_outputs"]
               + m["n_shared_experts"] * expert_params(m))
    return {"dense_layers": dense * (attention_params(m) + 3 * h * m["intermediate_size"]),
            "expert_layers_outside_experts": sparse * outside,
            "experts_held": sparse * m["n_routed_experts"] * expert_params(m),
            "hyper_connections": m["num_hidden_layers"] * hyper_params(m),
            "embedding_and_head": 2 * h * m["vocab_size"]}


def experts_touched(m: dict, batch: float) -> float:
    """Of the experts held here, how many at least one of `batch` tokens
    chooses if the router spreads evenly: each token takes
    `num_experts_per_tok` of `share.router_outputs`."""
    miss = 1.0 - m["num_experts_per_tok"] / m["share"]["router_outputs"]
    return m["n_routed_experts"] * (1.0 - miss ** batch)


def decode_stream_step(m: dict, context_tokens: float, batch: int) -> dict:
    """What ONE decode step must stream from HBM: every layer's attention,
    router, shared expert and hyper-connection matrices, the dense layers'
    MLPs, of the held experts those that `batch` rows touch
    (`experts_touched`: 7.6 of 8 at 48 rows), the head once, and the live
    context's latent rows of every cache layer (`latent_attention_step`). The
    embedding is a lookup of `batch` rows. FLOPs: 2 a weight and row in the
    matrices a row goes through (of the experts, its share of its 4), and
    attention's. HBM bandwidth bounds it at decode batch sizes."""
    here = params_here(m)
    sparse = m["num_hidden_layers"] - m["first_k_dense_replace"]
    head = m["hidden_size"] * m["vocab_size"]
    fixed = (here["dense_layers"] + here["expert_layers_outside_experts"]
             + here["hyper_connections"] + head)
    touched = sparse * experts_touched(m, batch) * expert_params(m)
    routed = (sparse * m["num_experts_per_tok"] * expert_params(m)
              * m["n_routed_experts"] / m["share"]["router_outputs"])
    attn = latent_attention_step(m, context_tokens, batch)
    return {"flops": 2 * batch * (fixed + routed) + attn["flops"],
            "bytes": (fixed + touched) * shapes._itemsize(m) + attn["bytes"]}
