"""MoE + ViT model-family tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama, moe, vit
from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.mesh import make_mesh


def test_moe_forward_finite_and_nothing_is_dropped():
    cfg = moe.MoEConfig.tiny()
    params = moe.init(cfg, jax.random.PRNGKey(0))
    assert not {"w_gate", "w_up", "w_down"} & set(params["layers"])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.base.vocab_size)
    logits, stats = moe.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.base.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    L, E, T = cfg.base.num_layers, cfg.num_experts, tokens.size
    assert stats["aux"].shape == (L,) and float(stats["aux"].min()) > 0
    assert stats["experts"].shape == (L, T, cfg.top_k)
    # every (token, choice) reached an expert: the loads are rows over the
    # mean T * k / E, so they sum to E in every layer
    np.testing.assert_allclose(np.asarray(stats["load"]).sum(axis=1), E, rtol=1e-6)


def test_moe_no_row_is_dropped_when_every_token_chooses_one_expert():
    """A router that sends every token to expert 2 first: the capacity-bounded
    layer this replaced kept 1.25 * k * T / E rows of it and dropped the
    rest. Here expert 2 gets all T rows, and the layer's output is what a
    plain loop over each token's choices gives."""
    cfg = moe.MoEConfig(base=llama.LlamaConfig.tiny(), num_experts=4, top_k=1)
    layer = {k: v[0] for k, v in moe.init(cfg, jax.random.PRNGKey(3))["layers"].items()}
    # logits = y @ router: all the mass of the router on expert 2
    layer["router"] = jnp.zeros_like(layer["router"]).at[:, 2].set(1.0)
    y = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.base.hidden_size)))
    out, stats = moe.moe_mlp(y, layer, cfg)
    assert (np.asarray(stats["experts"]) == 2).all()
    np.testing.assert_allclose(np.asarray(stats["load"]), [0, 0, 4, 0])
    p = jax.nn.softmax(y.reshape(32, -1) @ layer["router"], axis=-1)[:, 2:3]
    yt = y.reshape(32, -1)
    want = p * ((jax.nn.silu(yt @ layer["e_gate"][2]) * (yt @ layer["e_up"][2]))
                @ layer["e_down"][2])
    np.testing.assert_allclose(np.asarray(out).reshape(32, -1), want, rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(out)).min(axis=-1).max() > 0   # no token got zeros


def test_moe_trains():
    cfg = moe.MoEConfig.tiny()
    params = moe.init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.base.vocab_size)
    targets = jnp.roll(tokens, -1, 1)

    @jax.jit
    def step(params, state):
        (loss, scalars), grads = jax.value_and_grad(moe.loss_fn, has_aux=True)(
            params, tokens, targets, cfg)
        upd, state = opt.update(grads, state)
        return optax.apply_updates(params, upd), state, loss, scalars

    losses = []
    for _ in range(4):
        params, state, loss, scalars = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert set(scalars) == {"nll", "aux_loss", "router_load_max"}
    np.testing.assert_allclose(
        float(loss), float(scalars["nll"] + cfg.router_aux_coeff * scalars["aux_loss"]),
        rtol=1e-6)
    assert float(scalars["router_load_max"]) >= 1.0


def test_moe_expert_parallel_matches_unsharded():
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = moe.MoEConfig.tiny()
    params = moe.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.base.vocab_size)
    ref = moe.forward(params, tokens, cfg)[0]
    mesh = make_mesh(8, devices=jax.devices("cpu")[:8], data=2, expert=4)
    sharded = shd.shard_params(params, moe.logical_axes(cfg), mesh)
    out = jax.jit(lambda p, t: moe.forward(p, t, cfg, mesh=mesh)[0])(
        sharded, jax.device_put(tokens, NamedSharding(mesh, P(("data", "fsdp"), None)))
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_vit_forward_and_train():
    cfg = vit.ViTConfig.tiny()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    images = jax.random.uniform(jax.random.PRNGKey(1), (4, 32, 32, 3))
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (4, 10)
    assert np.isfinite(np.asarray(logits)).all()

    labels = jnp.asarray([0, 1, 2, 3])
    opt = optax.adam(1e-2)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(vit.loss_fn)(params, images, labels, cfg)
        upd, state = opt.update(grads, state)
        return optax.apply_updates(params, upd), state, loss

    losses = []
    for _ in range(5):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_vit_patchify_roundtrip_shapes():
    x = jnp.arange(2 * 32 * 32 * 3, dtype=jnp.float32).reshape(2, 32, 32, 3)
    p = vit.patchify(x, 8)
    assert p.shape == (2, 16, 192)


def test_vit_param_scale():
    # ViT-L/16 should be ~300M params
    cfg = vit.ViTConfig.vit_l16()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    n = llama.param_count(params)
    assert 250e6 < n < 350e6, n


def test_vit_data_pipeline_integration(ray_start_regular):
    """BASELINE config #4 shape: image dataset streaming into ViT batches."""
    import ray_tpu
    from ray_tpu import data as rdata

    cfg = vit.ViTConfig.tiny()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    images = np.random.rand(32, 32, 32, 3).astype(np.float32)
    ds = rdata.from_numpy({"image": images, "label": np.arange(32) % 10})
    fwd = jax.jit(lambda p, x: vit.forward(p, x, cfg))
    seen = 0
    for batch in ds.iter_batches(batch_size=8, batch_format="jax"):
        logits = fwd(params, batch["image"])
        assert logits.shape == (8, 10)
        seen += 8
    assert seen == 32
