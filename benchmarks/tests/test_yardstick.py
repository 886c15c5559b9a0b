"""The shapes functions against numbers worked by hand, the order statistic,
and the plain reference against the program at a toy size in float32."""

import pytest

from conftest import TINY_MODEL

from benchmarks.harness import shapes
from benchmarks.harness.stats import quantile

MISTRAL_4L = {"hidden_size": 4096, "intermediate_size": 14336,
              "num_hidden_layers": 4, "num_attention_heads": 32,
              "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768,
              "torch_dtype": "bfloat16"}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_train_flops_per_token():
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert shapes.matmul_params_per_layer(MISTRAL_4L) == per_layer == 218103808
    weights = 4 * per_layer + 4096 * 32768
    attn = 4 * 32 * 4 * 128 * (4096 + 1) / 2
    assert shapes.train_flops_per_token(MISTRAL_4L, 4096) == pytest.approx(
        3 * (2 * weights + attn))
    # about 6.44 GFLOP a token: 8,700 tokens/s is 28% of a v5e
    assert 100 * shapes.train_flops_per_token(MISTRAL_4L, 4096) * 8700 / 197e12 == \
        pytest.approx(28.4, abs=0.2)


def test_flash_attention_is_compute_bound_and_paged_attention_memory_bound():
    work = shapes.flash_attention_step(MISTRAL_4L, 2, 4096)
    pairs = 2 * 32 * 4096 * 4097 / 2
    assert work["flops"] == pytest.approx(4 * 7 * 2 * 128 * pairs)
    least, bound = shapes.least_seconds(work, V5E)
    assert bound == "compute" and least == pytest.approx(work["flops"] / 197e12)
    m16 = {**MISTRAL_4L, "num_hidden_layers": 16}
    work = shapes.paged_attention_step(m16, 10000, 24)
    assert work["bytes"] == 16 * (2 * 10000 * 8 * 128 * 2 + 2 * 24 * 32 * 128 * 2)
    least, bound = shapes.least_seconds(work, V5E)
    assert bound == "memory" and least == pytest.approx(work["bytes"] / 819e9)


def test_quantile_interpolates_between_closest_ranks():
    assert quantile([], 50) is None
    assert quantile([3.0], 95) == 3.0
    assert quantile([1, 2, 3, 4], 50) == 2.5
    assert quantile(range(101), 95) == 95


def test_reference_agrees_with_the_program_at_a_toy_size():
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.families import llama as family
    from benchmarks.reference import llama_reference
    from ray_tpu.models import llama

    cfg = family.model_config(TINY_MODEL, remat=False)
    params = family.seeded_params(cfg, 2 ** 31 + 7)
    tokens = np.random.default_rng(0).integers(0, 256, 48)
    want = llama_reference.logits(params, tokens, TINY_MODEL)
    got = llama.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(jnp.abs(want).max())
    targets = np.roll(tokens, -1)
    assert llama_reference.loss(params, tokens, targets, TINY_MODEL) == pytest.approx(
        float(llama.loss_fn(params, jnp.asarray(tokens)[None],
                            jnp.asarray(targets)[None], cfg)), abs=1e-4)
    # and a wrong rotation base is caught: the reference is not insensitive
    other = llama_reference.logits(params, tokens, {**TINY_MODEL, "rope_theta": 1e4})
    assert float(jnp.abs(other - want).max()) > 1e-3
