"""The LFM2 family (LiquidAI LFM2-8B-A1B, `model_type` `lfm2_moe`: 24 layers
of two kinds of mixer in the order `layer_types` lists, 18 gated short
convolutions of `conv_L_cache` = 3 taps and 6 grouped-query attention layers
of 32 query and 8 key-value heads of 64 with a per-head RMSNorm of q and k, a
dense SwiGLU in the first `num_dense_layers` = 2 layers and 32 sigmoid-routed
SwiGLU experts of which a token takes 4 in the other 22, no shared expert,
the head tied to the embedding): `ray_tpu/models/lfm2.py` served by the paged
engine through the program's `Model` record. The configuration file holds ONE
CHIP'S SHARE of a stated deployment (`share`): `num_experts` is the experts
held here of `share.router_outputs` that the router chooses over; depth and
vocabulary are whole. It serves only (`lfm2.MODEL.loss` is None: training a
stack of several kinds is ROADMAP R2), so it has no `train_state_and_step`.
See the package docstring for what a family module holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record
from benchmarks.harness.families.llama import serve_app  # noqa: F401
from benchmarks.harness.families.ouro import kv_pool_blocks  # noqa: F401

MODEL_KEYS = ("conv_L_cache", "conv_bias", "hidden_size", "intermediate_size",
              "layer_types", "max_position_embeddings", "moe_intermediate_size",
              "norm_eps", "norm_topk_prob", "num_attention_heads", "num_dense_layers",
              "num_experts", "num_experts_per_tok", "num_hidden_layers",
              "num_key_value_heads", "rope_theta", "routed_scaling_factor",
              "use_expert_bias", "vocab_size", "tie_word_embeddings", "torch_dtype",
              "share")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `Lfm2Config`. A program without the family
    (any before PR 40) ends here, by name."""
    import dataclasses

    import jax.numpy as jnp

    try:
        from ray_tpu.models import lfm2, llama, moe
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'lfm2' needs `ray_tpu.models.lfm2` (a trunk over "
            "runs of layer kinds, `llama.decoder_trunk(runs=)`; the gated short "
            "convolution as a mixer strategy whose state rides in the paged pool's "
            "`conv` leaf beside the attention layers' keys and values; a per-head "
            "RMSNorm of q and k in `llama.gqa_attention`): this program has none, so "
            "it cannot serve LFM2 through build_openai_app -> PagedLLMEngine") from None
    refuse = {
        "conv_bias": bool(model["conv_bias"]),
        "use_expert_bias": not model["use_expert_bias"],
        "tie_word_embeddings": not model["tie_word_embeddings"],
        "layer_types": (len(model["layer_types"]) != model["num_hidden_layers"]
                        or set(model["layer_types"]) - set(lfm2.MIXERS)),
    }
    if any(refuse.values()):
        raise SystemExit(
            f"benchmark: Lfm2Config has no other {sorted(k for k, v in refuse.items() if v)} "
            f"than the published LFM2-8B-A1B's (no convolution bias, a selection bias "
            f"on the router, the head tied to the embedding, one of "
            f"{sorted(lfm2.MIXERS)} a layer)")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    share = model["share"]
    held, total = model["num_experts"], share["router_outputs"]
    base = llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"], num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["norm_eps"],
        tie_embeddings=True, dtype=dtype, **extra)
    experts = moe.MoEConfig(
        base=dataclasses.replace(base, intermediate_size=model["moe_intermediate_size"]),
        num_experts=total, top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"], score_func="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]), norm_topk_eps=1e-6,
        experts_held=None if held == total else (share["rank"] * held, held))
    return lfm2.Lfm2Config(base=base, experts=experts,
                           layer_types=tuple(model["layer_types"]),
                           num_dense_layers=model["num_dense_layers"],
                           conv_taps=model["conv_L_cache"])


def seeded_params(cfg, seed: int):
    """The program's own `lfm2.init` (the residual conditioned as PR 37 found
    necessary: output projections at `1 / sqrt(2 x 24)` of their fan-in scale,
    the embedding at unit rms), jitted once: weights are made on the device in
    the type they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import lfm2

    return jax.jit(partial(lfm2.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions for this architecture

def head_dim(m: dict) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


def layers_of(m: dict, kind: str) -> int:
    return sum(t == kind for t in m["layer_types"])


def cache_layers(m: dict) -> int:
    """(K, V) pairs a token caches: the attention layers alone."""
    return layers_of(m, "full_attention")


def conv_params(m: dict) -> int:
    """A convolution mixer: the input projection to [B | C | x], the taps,
    the output projection."""
    h = m["hidden_size"]
    return h * 3 * h + m["conv_L_cache"] * h + h * h


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], head_dim(m)
    return (2 * h * m["num_attention_heads"] * d + 2 * h * m["num_key_value_heads"] * d)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def params_here(m: dict) -> dict:
    """Matrix weights this chip holds, by part (norm weights and the selection
    bias are thousands beside them): what the configuration file's memory
    arithmetic is reckoned from. The head is the embedding, counted once."""
    h = m["hidden_size"]
    sparse = m["num_hidden_layers"] - m["num_dense_layers"]
    return {"embedding_and_head": h * m["vocab_size"],
            "conv_mixers": layers_of(m, "conv") * conv_params(m),
            "attention": layers_of(m, "full_attention") * attention_params(m),
            "dense_mlps": m["num_dense_layers"] * 3 * h * m["intermediate_size"],
            "routers": sparse * h * m["share"]["router_outputs"],
            "experts_held": sparse * m["num_experts"] * expert_params(m)}


def experts_touched(m: dict, batch: float) -> float:
    """Of the experts held here, how many at least one of `batch` tokens
    chooses if the router spreads evenly: each token takes
    `num_experts_per_tok` of `share.router_outputs`."""
    miss = 1.0 - m["num_experts_per_tok"] / m["share"]["router_outputs"]
    return m["num_experts"] * (1.0 - miss ** batch)


def pool_row(m: dict) -> int:
    """Values in a token's K (or V) row of the paged pool: every key-value
    head in whole 128-lane tiles, which is what the kernel reads."""
    return m["num_key_value_heads"] * -(-head_dim(m) // 128) * 128


def paged_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Paged decode attention over one decode step, the 6 attention layers:
    each reads the keys and values of the `context_tokens` tokens the live
    sequences hold AT THE POOL'S WIDTH (a 64-wide head lies in a 128-lane
    tile and the kernel's copies move whole tiles: the bytes the step must
    move with this pool, twice what the heads hold; PERF.md section 7), and
    reads and writes one query and output row a slot. FLOPs are the heads'
    own 64 lanes'. HBM bandwidth bounds it."""
    L, hq, d = cache_layers(m), m["num_attention_heads"], head_dim(m)
    item = shapes._itemsize(m)
    kv = 2 * context_tokens * pool_row(m) * item
    qo = 2 * batch * hq * d * item
    return {"flops": L * 2 * 2 * context_tokens * hq * d, "bytes": L * (kv + qo)}


def conv_state_step(m: dict, batch: int) -> dict:
    """The convolution layers' state over one decode step: `conv_L_cache - 1`
    rows of `hidden_size` read and one written a live slot and layer."""
    rows = m["conv_L_cache"] * batch * m["hidden_size"] * shapes._itemsize(m)
    return {"flops": layers_of(m, "conv") * 2 * m["conv_L_cache"] * batch * m["hidden_size"],
            "bytes": layers_of(m, "conv") * rows}


def decode_stream_step(m: dict, context_tokens: float, batch: int) -> dict:
    """What ONE decode step must stream from HBM: every mixer's, router's and
    dense MLP's matrices once, the tied head once (the embedding is a lookup
    of `batch` rows of the same matrix), of the held experts those that
    `batch` rows touch (`experts_touched`: 15.8 of 16 at 32 rows), the live
    context's K and V rows of the 6 attention layers at their padded width
    (`paged_attention_step`), and the 18 convolution layers' state rows of
    the live slots (`conv_state_step`). FLOPs: 2 a weight and row in the
    matrices a row goes through (of the experts, its share of its 4), and
    attention's. HBM bandwidth bounds it at decode batch sizes."""
    here = params_here(m)
    sparse = m["num_hidden_layers"] - m["num_dense_layers"]
    fixed = (here["embedding_and_head"] + here["conv_mixers"] + here["attention"]
             + here["dense_mlps"] + here["routers"])
    touched = sparse * experts_touched(m, batch) * expert_params(m)
    routed = (sparse * m["num_experts_per_tok"] * expert_params(m)
              * m["num_experts"] / m["share"]["router_outputs"])
    attn, conv = paged_attention_step(m, context_tokens, batch), conv_state_step(m, batch)
    return {"flops": 2 * batch * (fixed + routed) + attn["flops"] + conv["flops"],
            "bytes": (fixed + touched) * shapes._itemsize(m) + attn["bytes"] + conv["bytes"]}
