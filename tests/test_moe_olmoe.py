"""models/moe.py at a tiny OLMoE against the plain reference
(benchmarks/reference/olmoe_reference.py), and the train step that takes the
model as an argument."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.harness.families import olmoe as family
from benchmarks.reference import olmoe_reference
from ray_tpu.models import llama, moe
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.train import spmd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference both compute in float32 here (the stand-in's
# torch_dtype), the same mathematics in another order (sorted rows through
# ragged_dot against every expert on every token under a mask), so they differ
# by float32 rounding alone: 2e-6 on logits of size 4 and 1e-7 on gradients,
# measured. 1e-5 admits that and nothing else: router logits rounded to
# bfloat16 move the logits by 1e-3 of their size and a renormalised top k by
# more (below).
TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "olmoe-train.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    params = jax.jit(lambda k: moe.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 7))
    # norm weights other than one, so that a norm in the wrong place shows
    params["layers"] = {
        k: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
        if k.endswith("_norm") else v
        for i, (k, v) in enumerate(sorted(params["layers"].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 48)
    return model, cfg, params, tokens, np.roll(tokens, -1)


def _program(params, tokens, targets, cfg):
    return moe.loss_fn(params, jnp.asarray(tokens)[None], jnp.asarray(targets)[None], cfg)


def test_logits_losses_and_chosen_experts_match_the_reference(tiny):
    model, cfg, params, tokens, targets = tiny
    want, parts = olmoe_reference.objective(params, tokens, targets, model)
    logits, stats = moe.forward(params, jnp.asarray(tokens)[None], cfg)
    got, scalars = _program(params, tokens, targets, cfg)
    assert float(jnp.abs(logits[0] - parts["logits"]).max()) < TOL * float(
        jnp.abs(parts["logits"]).max())
    assert float(got) == pytest.approx(float(want), abs=TOL)
    assert float(scalars["nll"]) == pytest.approx(float(parts["nll"]), abs=TOL)
    assert float(scalars["aux_loss"]) == pytest.approx(float(parts["aux"]), abs=TOL)
    assert olmoe_reference.loss(params, tokens, targets, model) == float(want)
    # the same experts for every token of every layer (as sets: top_k's order
    # among equal weights is not part of the model)
    np.testing.assert_array_equal(np.sort(np.asarray(stats["experts"]), axis=-1),
                                  np.sort(np.asarray(parts["experts"]), axis=-1))
    # a batch that repeats the sequence has the same objective: what the
    # benchmark's check relies on (train_cell.py)
    rep = moe.loss_fn(params, jnp.asarray(np.stack([tokens] * 3)),
                      jnp.asarray(np.stack([targets] * 3)), cfg)[0]
    assert float(rep) == pytest.approx(float(want), abs=TOL)


def test_gradients_of_every_leaf_match_the_reference(tiny):
    model, cfg, params, tokens, targets = tiny
    want = jax.grad(lambda p: olmoe_reference.objective(p, tokens, targets, model)[0])(params)
    got = jax.grad(lambda p: _program(p, tokens, targets, cfg)[0])(params)
    leaves = jax.tree_util.tree_leaves_with_path(got)
    assert len(leaves) == 15      # embed, head, final norm, 12 a layer
    for path, g in leaves:
        w = want
        for key in path:
            w = w[key.key]
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        assert float(jnp.abs(g - w).max()) < TOL * max(scale, 1.0), jax.tree_util.keystr(path)


@pytest.mark.parametrize("fault", ["bfloat16_router_logits", "renormalised_top_k"])
def test_a_wrong_router_fails_the_tolerance(tiny, monkeypatch, fault):
    """The tolerance is tight enough to catch routing in a lower precision and
    weights renormalised over the chosen experts (`norm_topk_prob` is false in
    OLMoE). Rounding is spelled `reduce_precision`: XLA's CPU backend removes
    a float32 -> bfloat16 -> float32 round trip."""
    model, cfg, params, tokens, targets = tiny
    want, parts = olmoe_reference.objective(params, tokens, targets, model)
    if fault == "bfloat16_router_logits":
        exact = moe.router_logits
        monkeypatch.setattr(moe, "router_logits", lambda y, w: jax.lax.reduce_precision(
            exact(y, w), exponent_bits=8, mantissa_bits=7))
    else:
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    got = float(_program(params, tokens, targets, cfg)[0])
    logits = moe.forward(params, jnp.asarray(tokens)[None], cfg)[0][0]
    # the loss is a mean over tokens and forgives most (bfloat16 logits move
    # it by 2e-5 here, a renormalised top k by 1e-2); a token's logits do not
    assert abs(got - float(want)) > 2 * TOL
    assert float(jnp.abs(logits - parts["logits"]).max()) > 100 * TOL * float(
        jnp.abs(parts["logits"]).max())


def test_the_presets_are_the_published_shapes():
    cfg = moe.MoEConfig.olmoe_1b_7b()
    shapes = jax.eval_shape(lambda k: moe.init(cfg, k), jax.random.PRNGKey(0))
    per_layer = sum(int(np.prod(v.shape[1:])) for v in shapes["layers"].values())
    assert per_layer == 419_569_664           # 419.6 M a layer, norms included
    assert shapes["layers"]["e_gate"].shape == (16, 64, 2048, 1024)
    assert shapes["layers"]["q_norm"].shape == (16, 2048)
    assert not cfg.norm_topk_prob and cfg.top_k == 8
    mixtral = moe.MoEConfig.mixtral_8x7b()
    assert mixtral.norm_topk_prob and mixtral.base.intermediate_size == 14336 and not mixtral.qk_norm
    assert "q_norm" not in moe.logical_axes(mixtral)["layers"]


def test_the_train_step_takes_the_model_and_carries_its_scalars():
    cfg = moe.MoEConfig(base=dataclasses.replace(llama.LlamaConfig.tiny(), remat=True,
                                                 remat_policy="dots"),
                        num_experts=4, top_k=2, qk_norm=True)
    mesh = make_mesh(1)
    state = spmd.init_state(cfg, jax.random.PRNGKey(0), model=moe.MODEL)
    assert "e_gate" in state.params["layers"] and "w_gate" not in state.params["layers"]
    step = spmd.make_train_step(cfg, mesh, model=moe.MODEL)(state)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.base.vocab_size)
    losses = []
    for _ in range(3):
        state, metrics = step(state, tokens, jnp.roll(tokens, -1, 1))
        losses.append(float(metrics["loss"]))
    assert set(metrics) == {"loss", "grad_norm", "step", "nll", "aux_loss",
                            "router_load_max"}
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics["nll"]) + cfg.router_aux_coeff * float(metrics["aux_loss"]), rel=1e-6)
    assert 1.0 <= float(metrics["router_load_max"]) <= cfg.num_experts
    assert losses[-1] < losses[0] and int(metrics["step"]) == 3


def test_the_llama_step_lowers_to_the_program_it_lowered_to_before():
    """`make_train_step` with the default model record against the step as it
    was spelled before it took one: the same StableHLO, letter for letter."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), remat=True, remat_policy="dots")
    mesh = make_mesh(1)
    optimizer = spmd.make_optimizer()
    state = spmd.init_state(cfg, jax.random.PRNGKey(0), optimizer=optimizer)
    attn_fn = spmd.default_attn_fn(mesh)

    def step_fn(state, tokens, targets):      # train/spmd.py before PR 27
        def loss(params):
            return llama.loss_fn(params, tokens, targets, cfg, attn_fn)

        lossval, grads = jax.value_and_grad(loss)(state.params)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = spmd.TrainState(new_params, new_opt, state.step + 1)
        return new_state, {"loss": lossval, "grad_norm": gnorm, "step": new_state.step}

    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = spmd.state_shardings(cfg, mesh, state)
    batch_sh = NamedSharding(mesh, P(("data", "fsdp"), None))
    before = jax.jit(step_fn, in_shardings=(sh, batch_sh, batch_sh),
                     out_shardings=(sh, NamedSharding(mesh, P())), donate_argnums=(0,))
    tokens = jnp.zeros((2, 32), jnp.int32)
    now = spmd.make_train_step(cfg, mesh, optimizer=optimizer)(state)
    assert now.lower(state, tokens, tokens).as_text() == \
        before.lower(state, tokens, tokens).as_text()
