"""Flagship model + SPMD train step tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, model_of, moe, ouro
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.parallel.ring_attention import make_ring_attn_fn
from ray_tpu.train import spmd


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_param_count_matches_analytic(tiny):
    cfg, params = tiny
    assert llama.param_count(params) == llama.param_count_analytic(cfg)


def test_forward_shapes_finite(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    t1 = jnp.zeros((1, 8), jnp.int32)
    t2 = t1.at[0, 7].set(5)
    l1 = llama.forward(params, t1, cfg)
    l2 = llama.forward(params, t2, cfg)
    np.testing.assert_allclose(np.asarray(l1[0, :7]), np.asarray(l2[0, :7]), atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 7]), np.asarray(l2[0, 7]))


def test_loss_ignore_index(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((1, 8), jnp.int32)
    targets_all = jnp.ones((1, 8), jnp.int32)
    targets_mask = targets_all.at[0, :4].set(-100)
    l_all = llama.loss_fn(params, tokens, targets_all, cfg)
    l_mask = llama.loss_fn(params, tokens, targets_mask, cfg)
    assert np.isfinite(float(l_all)) and np.isfinite(float(l_mask))


def test_gqa_head_broadcast():
    B, S, D = 1, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, 4, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, D))
    out = llama.attention(q, k, v)
    assert out.shape == (B, S, 4, D)


def test_presets_param_counts():
    # sanity: presets land near their nominal sizes
    assert 100e6 < llama.param_count_analytic(llama.LlamaConfig.gpt2_124m()) < 180e6
    assert 7e9 < llama.param_count_analytic(llama.LlamaConfig.llama_8b()) < 9e9


def test_train_step_loss_decreases(tiny):
    cfg, _ = tiny
    mesh = make_mesh(8, devices=jax.devices("cpu")[:8], data=2, fsdp=2, tensor=2)
    state = spmd.init_state(cfg, jax.random.PRNGKey(0),
                            optimizer=spmd.make_optimizer(learning_rate=1e-2, warmup=1))
    step = spmd.make_train_step(cfg, mesh)(state)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(5):
        state, metrics = step(state, tokens, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses


def test_train_step_with_ring_attention(tiny):
    cfg, _ = tiny
    mesh = make_mesh(8, devices=jax.devices("cpu")[:8], data=2, fsdp=1, tensor=2, seq=2)
    attn = make_ring_attn_fn(mesh, "seq")
    state = spmd.init_state(cfg, jax.random.PRNGKey(0),
                            optimizer=spmd.make_optimizer(warmup=1))
    step = spmd.make_train_step(cfg, mesh, attn_fn=attn)(state)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, cfg.vocab_size)
    state, metrics = step(state, tokens, tokens)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("prefill, qk_norm", [
    pytest.param(12, False, id="paged-prefill"),
    pytest.param(8, False, id="paged-prefill-then-decode"),
    pytest.param(8, True, id="paged-qk-norm"),
])
def test_cached_forwards_give_the_plain_forwards_logits(tiny, prefill, qk_norm):
    """The first `prefill` positions in one cached call, the rest a token a
    call: the logits of `llama.forward` at the same positions. One layer
    function runs in both, so a layer that holds `q_norm` and `k_norm`
    is normalised through the paged path too."""
    cfg, params = tiny
    B, S, bs = 2, 12, 4
    if qk_norm:
        L, kq, kk = cfg.num_layers, *jax.random.split(jax.random.PRNGKey(3))
        params = {**params, "layers": {
            **params["layers"],
            "q_norm": 1 + 0.5 * jax.random.normal(kq, (L, cfg.num_heads * cfg.hd)),
            "k_norm": 1 + 0.5 * jax.random.normal(kk, (L, cfg.num_kv_heads * cfg.hd))}}
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    want = llama.forward(params, tokens, cfg)
    if qk_norm:   # the case means something only if the norm's weights matter
        assert not np.allclose(np.asarray(want), np.asarray(llama.forward(
            {**params, "layers": {k: v for k, v in params["layers"].items()
                                  if k not in ("q_norm", "k_norm")}}, tokens, cfg)), atol=1e-2)

    cache = llama.init_kv_pool(cfg, 1 + B * 4, bs)     # block 0 is the garbage block
    tables = 1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
    step = lambda toks, cache, lengths: llama.forward_paged(
        params, toks, cfg, cache, tables, lengths, bs)
    got = []
    for start, stop in [(0, prefill)] + [(i, i + 1) for i in range(prefill, S)]:
        logits, cache = step(tokens[:, start:stop], cache, jnp.full((B,), start, jnp.int32))
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _noisy_norms(params, key=5):
    """Norm weights other than one, so that a row taken before the wrong norm
    (or a norm left out) shows in the logits."""
    noisy = lambda i, v: v * (1 + 0.2 * jax.random.normal(jax.random.PRNGKey(key + i), v.shape))
    layers = {k: noisy(i, v) if k.endswith("_norm") else v
              for i, (k, v) in enumerate(sorted(params["layers"].items()))}
    return {**params, "layers": layers, "final_norm": noisy(99, params["final_norm"])}


@pytest.mark.parametrize("cfg", [
    pytest.param(llama.LlamaConfig.tiny(), id="llama"),
    pytest.param(moe.MoEConfig.tiny(), id="moe"),
    pytest.param(ouro.OuroConfig.tiny(), id="ouro"),
])
def test_head_rows_gives_that_row_of_the_all_positions_logits(cfg):
    """`head_rows=r` is the head run on position `r[b]` of sequence b alone:
    logits [B, 1, V] equal to row `r[b]` of the all-positions logits, another
    row a sequence, and the same cache written. Through every family's cached
    forward: the dense trunk, the expert MLP (`mlp=moe_mlp`) and the looped
    trunk, whose row is taken after the LAST pass's norm."""
    model = model_of(cfg)
    params = _noisy_norms(model.init(cfg, jax.random.PRNGKey(0)))
    B, S, bs = 3, 12, 4
    vocab = getattr(cfg, "base", cfg).vocab_size
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, vocab)
    start = jnp.asarray([0, 4, 1], jnp.int32)    # appended at another offset each
    cache = model.init_kv_pool(cfg, 1 + B * 4, bs)
    tables = 1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
    step = lambda **kw: model.forward_paged(params, tokens, cfg, cache, tables, start, bs, **kw)
    want, want_cache = step()
    assert want.shape == (B, S, vocab)
    rows = jnp.asarray([S - 1, 0, 5], jnp.int32)
    got, got_cache = jax.jit(lambda r: step(head_rows=r))(rows)   # traced, as a step's are
    assert got.shape == (B, 1, vocab) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want)[np.arange(B), rows],
                               rtol=1e-5, atol=1e-5)
    # the rows differ, so a row taken at the wrong place would not pass
    assert not np.allclose(np.asarray(want)[:, S - 1], np.asarray(want)[:, 5], atol=1e-2)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_cache[name]), np.asarray(want_cache[name]),
                                   rtol=1e-5, atol=1e-6)


def test_paged_prefill_pads_past_the_table_into_block_zero(tiny):
    """A bucketed prefill whose padding runs past the table (a near-full
    sequence): the pad positions' rows go to the garbage block 0 of every
    layer, the sequence's own last block keeps its rows, and the real
    positions' logits are the plain forward's. The pool is `init_kv_pool`'s:
    [L, NB, block_size, Hkv * 128] for these 16-wide heads."""
    cfg, params = tiny
    bs, mb, real, bucket = 4, 3, 12, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, bucket), 0, cfg.vocab_size)
    tables = jnp.asarray([[2, 3, 1]], jnp.int32)
    empty = llama.init_kv_pool(cfg, 1 + mb, bs)
    assert empty["k"].shape == (cfg.num_layers, 1 + mb, bs, cfg.num_kv_heads * 128)
    zero = jnp.zeros((1,), jnp.int32)
    logits, padded = llama.forward_paged(params, tokens, cfg, empty, tables, zero, bs)
    _, exact = llama.forward_paged(params, tokens[:, :real], cfg, empty, tables, zero, bs)
    np.testing.assert_allclose(np.asarray(logits[:, :real]),
                               np.asarray(llama.forward(params, tokens[:, :real], cfg)),
                               rtol=1e-4, atol=1e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(padded[name][:, 1:]),
                                   np.asarray(exact[name][:, 1:]), rtol=1e-5, atol=1e-6)
        assert np.asarray(padded[name][:, 0]).any(axis=(1, 2)).all()   # every layer's block 0
        assert not np.asarray(exact[name][:, 0]).any()
        # a head's own 16 lanes of its 128-lane tile are written, the rest stay zero
        tile = np.asarray(padded[name]).reshape(*padded[name].shape[:3], cfg.num_kv_heads, 128)
        assert tile[..., :cfg.hd].any() and not tile[..., cfg.hd:].any()


def test_graft_entry_contract():
    import importlib.util, pathlib

    spec = importlib.util.spec_from_file_location(
        "graft_entry", pathlib.Path(__file__).parent.parent / "__graft_entry__.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] > 0
    mod.dryrun_multichip(8)
