"""The Kimi-K2 family (moonshotai Kimi-K2.7-Code, `model_type` `kimi_k2`, the
DeepSeek-V3 block: latent attention whose cache is one row of `kv_lora_rank +
qk_rope_head_dim` values a token, one leading dense layer, then layers of 384
sigmoid-routed SwiGLU experts of which a token takes 8, beside one shared
expert): `ray_tpu/models/kimi_k2.py` served by the paged engine through the
program's `Model` record. The configuration file holds ONE CHIP'S SHARE of a
stated deployment (`share`): `n_routed_experts` is the experts held here of
`share.router_outputs` that the router chooses over, `vocab_size` the slice of
the vocabulary held here. It serves only (at 16 bytes a parameter four expert
layers of a share do not fit a chip), so it has no `train_state_and_step`.
See the package docstring for what a family module holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record
from benchmarks.harness.families.llama import serve_app  # noqa: F401
from benchmarks.harness.families.ouro import kv_pool_blocks  # noqa: F401

MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_hidden_layers", "first_k_dense_replace", "moe_layer_freq",
              "num_attention_heads", "num_key_value_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "n_group", "topk_group", "topk_method", "scoring_func",
              "norm_topk_prob", "routed_scaling_factor", "vocab_size",
              "rope_theta", "rope_scaling", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings", "hidden_act",
              "attention_bias", "num_nextn_predict_layers", "torch_dtype", "share")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `KimiK2Config`. A program without the family
    (any before PR 33) ends here, by name."""
    import dataclasses

    import jax.numpy as jnp

    try:
        from ray_tpu.models import kimi_k2, llama, moe
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'kimi_k2' needs `ray_tpu.models.kimi_k2` (latent "
            "attention over a paged pool of one latent row a token, read absorbed "
            "at decode; a leading dense stack ahead of the expert layers; sigmoid "
            "routing with a correction bias, a shared expert and a chip's share of "
            "the experts in `moe.moe_mlp`): this program has none, so it cannot "
            "serve Kimi-K2 through build_openai_app -> PagedLLMEngine") from None
    refuse = {
        "n_group": model["n_group"] != 1 or model["topk_group"] != 1,
        "topk_method": model["topk_method"] != "noaux_tc",
        "scoring_func": model["scoring_func"] != "sigmoid",
        "moe_layer_freq": model["moe_layer_freq"] != 1,
        "hidden_act": model.get("hidden_act", "silu") != "silu",
        "attention_bias": bool(model.get("attention_bias")),
        "tie_word_embeddings": bool(model["tie_word_embeddings"]),
        "num_nextn_predict_layers": model.get("num_nextn_predict_layers", 0) != 0,
        "num_key_value_heads": model["num_key_value_heads"] != model["num_attention_heads"],
    }
    if any(refuse.values()):
        raise SystemExit(
            f"benchmark: KimiK2Config has no other {sorted(k for k, v in refuse.items() if v)} "
            f"than the published Kimi-K2.7-Code's (no expert groups, noaux_tc sigmoid "
            f"routing, every later layer sparse, SwiGLU, no bias, untied head, no "
            f"next-token-prediction layers, a latent a token whatever the KV heads)")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    share = model["share"]
    held, total = model["n_routed_experts"], share["router_outputs"]
    base = llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"] - model["first_k_dense_replace"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        tie_embeddings=False, dtype=dtype, **extra)
    experts = moe.MoEConfig(
        base=dataclasses.replace(base, intermediate_size=model["moe_intermediate_size"]),
        num_experts=total, top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"], score_func="sigmoid",
        routed_scaling=model["routed_scaling_factor"],
        experts_held=None if held == total else (share["rank"] * held, held))
    sc = model["rope_scaling"]
    if sc is not None and (sc["type"] != "yarn" or sc["mscale"] != sc["mscale_all_dim"]):
        raise SystemExit("benchmark: KimiK2Config's rope scaling is none, or YaRN with "
                         "mscale == mscale_all_dim (cos and sin unscaled)")
    return kimi_k2.KimiK2Config(
        base=base, experts=experts, first_k_dense=model["first_k_dense_replace"],
        shared_experts=model["n_shared_experts"], q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        yarn=None if sc is None else (
            float(sc["factor"]), sc["original_max_position_embeddings"],
            float(sc["beta_fast"]), float(sc["beta_slow"]), float(sc["mscale_all_dim"])))


def seeded_params(cfg, seed: int):
    """The program's own `kimi_k2.init`, jitted once: weights are made on the
    device in the type they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import kimi_k2

    return jax.jit(partial(kimi_k2.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions for this architecture

def cache_layers(m: dict) -> int:
    """Latent rows a token caches: one a layer, dense and expert alike."""
    return m["num_hidden_layers"]


def attention_params(m: dict) -> int:
    """A layer's latent attention: the two down-projections, the query's
    up-projection, the per-head key and value up-projections, the output."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * nh * qk
            + h * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + nh * m["kv_lora_rank"] * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + nh * m["v_head_dim"] * h)


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def params_here(m: dict) -> dict:
    """Matrix weights this chip holds, by part (norms and the correction bias
    are thousands beside them): what the depth rule of the configuration file
    is reckoned from."""
    h = m["hidden_size"]
    dense = m["first_k_dense_replace"]
    sparse = m["num_hidden_layers"] - dense
    outside = (attention_params(m) + h * m["share"]["router_outputs"]
               + m["n_shared_experts"] * expert_params(m))
    return {"dense_layers": dense * (attention_params(m) + 3 * h * m["intermediate_size"]),
            "expert_layers_outside_experts": sparse * outside,
            "experts_held": sparse * m["n_routed_experts"] * expert_params(m),
            "embedding_and_head": 2 * h * m["vocab_size"]}


def latent_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Absorbed latent decode attention over one decode step, every cache
    layer: the live context's latent rows are read ONCE (every head attends
    over the one row: `kv_lora_rank + qk_rope_head_dim` values a token, 1,152
    B in bfloat16; the pool pads a row to whole lane tiles, which is the
    program's cost, not the algorithm's), scores over all of it and values
    over the latent part for every query head (2 x heads x (576 + 512) FLOPs a
    token), and one absorbed query and one latent output a slot and head."""
    L, nh = cache_layers(m), m["num_attention_heads"]
    rank, rope = m["kv_lora_rank"], m["qk_rope_head_dim"]
    item = shapes._itemsize(m)
    rows = context_tokens * (rank + rope) * item
    qo = batch * nh * (rank + rope + rank) * item
    return {"flops": L * 2 * nh * (rank + rope + rank) * context_tokens,
            "bytes": L * (rows + qo)}
