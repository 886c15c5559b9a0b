"""The dense Llama family (Llama, Mistral: grouped-query attention, rotary
positions, RMSNorm, SwiGLU): `ray_tpu/models/llama.py` served by the paged
engine and trained by the SPMD step. See the package docstring for what a
family module holds."""

from __future__ import annotations

from benchmarks.harness.families import seeded_key
# the shapes functions that hold for this architecture, found here by readers
from benchmarks.harness.shapes import (  # noqa: F401
    flash_attention_step, paged_attention_step, train_flops_per_token)

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings",
              "sliding_window", "hidden_act", "torch_dtype")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `LlamaConfig`."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    if model.get("sliding_window") is not None:
        raise SystemExit("benchmark: LlamaConfig has no sliding window")
    if model.get("hidden_act", "silu") != "silu":
        raise SystemExit("benchmark: LlamaConfig's MLP is SwiGLU (silu)")
    return llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["rms_norm_eps"],
        tie_embeddings=model["tie_word_embeddings"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]],
        **extra)


def seeded_params(cfg, seed: int):
    """The program's own `llama.init`, jitted once: weights are made on the
    device in the type they are served in (run eagerly, one normal() compile
    and one float32 tensor per leaf made init most of a cold start)."""
    import jax
    from functools import partial

    from ray_tpu.models import llama

    return jax.jit(partial(llama.init, cfg))(seeded_key(seed))


def serve_app(cfg, model: dict, engine: dict, tokenizer):
    """`build_openai_app(PagedLLMConfig(...))`, and the engine class it builds."""
    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.serve.openai_api import build_openai_app

    app = build_openai_app(PagedLLMConfig(
        model_config=cfg, max_batch_size=engine["max_batch_size"],
        max_seq_len=model["max_position_embeddings"],
        block_size=engine["block_size"], num_blocks=engine.get("num_blocks", 0),
        prefill_buckets=tuple(engine["prefill_buckets"])), tokenizer=tokenizer)
    return app, PagedLLMEngine


def train_state_and_step(model: dict, trainer: dict, mesh, key):
    """`spmd.init_state` jitted once with `out_shardings` (weights and the
    optimizer's moments are born on their shards in the type they are trained
    in), and `spmd.make_train_step` on that state."""
    import jax

    from ray_tpu.train import spmd

    cfg = model_config(model, remat=True, remat_policy=trainer["remat_policy"])
    optimizer = spmd.make_optimizer(warmup=trainer["warmup_steps"])

    def init(k):
        return spmd.init_state(cfg, k, optimizer=optimizer)

    shardings = spmd.state_shardings(cfg, mesh, jax.eval_shape(init, key))
    state = jax.block_until_ready(jax.jit(init, out_shardings=shardings)(key))
    return state, spmd.make_train_step(cfg, mesh, optimizer=optimizer)(state)
