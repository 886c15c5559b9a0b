"""`llama.project_heads` (PR 46): the projections whose result is split into
heads hold the product's result behind an `optimization_barrier` in a decode
step (S == 1) and nowhere else. The barrier is the identity, so a decode
step's logits and pool are the unheld formulation's bit for bit; what it buys
is in the program XLA:TPU compiles (`tests/test_tpu_aot.py`:
`weight_relayouts`), and which programs' text it changed is in
`tests/test_lowered_text.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_k2, llama, model_of, ouro

BS = 16
TINY = {"llama": llama.LlamaConfig.tiny, "ouro": ouro.OuroConfig.tiny,
        "kimi_k2": kimi_k2.KimiK2Config.tiny}


def unheld_project_heads(y, w, head_dim: int, norm=None):
    """The formulation every family had until PR 46: product, the whole
    vector's norm where there is one, split; nothing held at any S."""
    out = y @ w
    if norm is not None:
        out = norm(out)
    return out.reshape(*y.shape[:2], -1, head_dim)


def _step(cfg):
    """A fresh `jit` of the family's paged forward (the helper is looked up
    when the step is traced, so each formulation needs a trace of its own)."""
    model = model_of(cfg)
    return jax.jit(lambda params, pool, tokens, tables, lengths: model.forward_paged(
        params, tokens, cfg, pool, tables, lengths, BS, platform="cpu"))


def _inputs(cfg, S):
    model = model_of(cfg)
    params = model.init(cfg, jax.random.PRNGKey(0))
    pool = model.init_kv_pool(cfg, 9, BS)
    tables = jnp.array([[1, 2], [3, 4], [5, 6]], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, S), 0, cfg.vocab_size)
    return params, pool, tokens, tables


@pytest.mark.parametrize("family", sorted(TINY))
def test_a_decode_step_is_the_unheld_formulation_s_bit_for_bit(family, monkeypatch):
    """B = 3, S = 1 after a prefill of 8 rows a sequence, contexts of 8, 5 and
    3: logits and every leaf of the pool `array_equal` between the program
    with the barrier and the one without; the held program has a barrier and
    the unheld none (so the comparison is of two programs)."""
    cfg = TINY[family]()
    params, pool, prompt, tables = _inputs(cfg, 8)
    _, pool = _step(cfg)(params, pool, prompt, tables, jnp.zeros((3,), jnp.int32))
    args = (params, pool, prompt[:, :1], tables, jnp.array([8, 5, 3], jnp.int32))

    held = _step(cfg)
    assert "optimization_barrier" in held.lower(*args).as_text()
    logits, new_pool = held(*args)

    monkeypatch.setattr(llama, "project_heads", unheld_project_heads)
    unheld = _step(cfg)
    assert "optimization_barrier" not in unheld.lower(*args).as_text()
    want_logits, want_pool = unheld(*args)

    assert logits.shape == (3, 1, cfg.vocab_size)
    assert np.array_equal(np.asarray(logits), np.asarray(want_logits))
    leaves, want_leaves = jax.tree.leaves(new_pool), jax.tree.leaves(want_pool)
    assert len(leaves) == len(want_leaves) >= 1
    for got, want in zip(leaves, want_leaves):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # the step wrote something: the pool is not the prefill's any more
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(leaves, jax.tree.leaves(pool)))


@pytest.mark.parametrize("family", sorted(TINY))
def test_a_window_of_two_tokens_lowers_without_a_barrier(family):
    """S = 2 (a speculative window; every prefill is wider still): the rows
    are no longer small beside the weight, and nothing is held."""
    cfg = TINY[family]()
    params, pool, tokens, tables = _inputs(cfg, 2)
    text = _step(cfg).lower(params, pool, tokens, tables,
                            jnp.array([8, 5, 3], jnp.int32)).as_text()
    assert "optimization_barrier" not in text


@pytest.mark.parametrize("S, held", [(1, True), (2, False), (16, False)])
def test_project_heads_holds_the_result_at_one_row_a_sequence_only(S, held):
    """The helper alone: `[B, S, H] x [H, N]` split into heads of 16, the
    whole vector's norm applied before the split, and a barrier in its
    lowered text at S == 1 only; the values are the plain product's."""
    y = jax.random.normal(jax.random.PRNGKey(2), (3, S, 32))
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 64))
    norm = lambda t: llama.rms_norm(t, jnp.full((64,), 1.5), 1e-6)
    f = jax.jit(lambda y, w: llama.project_heads(y, w, 16, norm))
    assert ("optimization_barrier" in f.lower(y, w).as_text()) == held
    out = f(y, w)
    assert out.shape == (3, S, 4, 16)
    assert np.array_equal(np.asarray(out), np.asarray(
        jax.jit(lambda y, w: unheld_project_heads(y, w, 16, norm))(y, w)))
