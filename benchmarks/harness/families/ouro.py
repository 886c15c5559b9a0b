"""The Ouro family (ByteDance Ouro-2.6B: a Llama-shaped stack of 48 layers
applied `total_ut_steps` = 4 times in sequence with the same weights, an
RMSNorm on each sub-layer's output, the final norm between passes, and a cache
of its own for every (pass, layer)): `ray_tpu/models/ouro.py` served by the
paged engine through the program's `Model` record. It serves only (training
through the loop is ROADMAP R10), so it has no `train_state_and_step`. See the
package docstring for what a family module holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record
from benchmarks.harness.families.llama import serve_app  # noqa: F401

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rope_scaling", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings",
              "sliding_window", "use_sliding_window", "hidden_act",
              "torch_dtype", "total_ut_steps", "early_exit_threshold")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `OuroConfig`. A program without the family
    (any before PR 31) ends here, by name."""
    import jax.numpy as jnp

    try:
        from ray_tpu.models import ouro
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'ouro' needs `ray_tpu.models.ouro` (a layer "
            "stack run `total_ut_steps` times with a cache layer for every pass) "
            "and engines that take a family's `Model` record "
            "(`ray_tpu.models.model_of`): this program has neither, so it cannot "
            "serve Ouro through build_openai_app -> PagedLLMEngine") from None
    if model.get("sliding_window") is not None or model.get("use_sliding_window"):
        raise SystemExit("benchmark: OuroConfig has no sliding window")
    if model.get("rope_scaling") is not None:
        raise SystemExit("benchmark: OuroConfig has no rope scaling")
    if model.get("hidden_act", "silu") != "silu":
        raise SystemExit("benchmark: OuroConfig's MLP is SwiGLU (silu)")
    if model["early_exit_threshold"] < 1:
        raise SystemExit(
            "benchmark: early_exit_threshold under 1 lets a token leave the loop "
            "before the last pass; OuroConfig runs every pass for every token and "
            "has no exit gate")
    return ouro.OuroConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        tie_embeddings=model["tie_word_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
        loop_steps=model["total_ut_steps"], **extra)


def seeded_params(cfg, seed: int):
    """The program's own `ouro.init`, jitted once: weights are made on the
    device in the type they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import ouro

    return jax.jit(partial(ouro.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions that differ for this architecture

def cache_layers(m: dict) -> int:
    """(K, V) pairs a token caches: one for every pass of every layer."""
    return m["total_ut_steps"] * m["num_hidden_layers"]


def params_per_layer(m: dict) -> int:
    """A layer's weights: the seven matrices and the four norm weights."""
    return shapes.matmul_params_per_layer(m) + 4 * m["hidden_size"]


def kv_pool_blocks(config: dict) -> int:
    """Blocks of the engine's pool that a request can be given: `num_blocks`
    less the garbage block, or the engine's dense-parity default."""
    eng = config["engine"]
    return (eng["num_blocks"] - 1 if eng.get("num_blocks") else eng["max_batch_size"]
            * (config["model"]["max_position_embeddings"] // eng["block_size"]))


def paged_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Paged decode attention over one decode step, as `shapes.
    paged_attention_step` counts a layer, over `passes x layers` cache layers:
    every pass reads its own keys and values of the live context."""
    return shapes.paged_attention_step(
        {**m, "num_hidden_layers": cache_layers(m)}, context_tokens, batch)


def decode_stream_step(m: dict, context_tokens: float, batch: int) -> dict:
    """What ONE decode step must stream from HBM whatever the batch: the
    layers' weights once a PASS (a pass's 5 GB do not stay on the chip for the
    next), the head once, and the live context's keys and values of every
    cache layer. The embedding is a lookup of `batch` rows and the norms'
    weights are counted with the layers. FLOPs: 2 a weight and slot in the
    matrices, and attention's. HBM bandwidth bounds it at decode batch sizes
    (2 x batch FLOPs a byte of weights against a balance of 240)."""
    item = shapes._itemsize(m)
    head = m["hidden_size"] * m["vocab_size"]
    weights = cache_layers(m) * params_per_layer(m) + head + m["hidden_size"]
    attn = paged_attention_step(m, context_tokens, batch)
    matmuls = cache_layers(m) * shapes.matmul_params_per_layer(m) + head
    return {"flops": 2 * batch * matmuls + attn["flops"],
            "bytes": weights * item + attn["bytes"]}
