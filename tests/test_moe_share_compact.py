"""`moe.moe_mlp` with a chip's share of the experts held (`experts_held`):
the rows it moves are the rows it computes, up to the static bound
`moe.held_rows_bound`, a bound at a time. In float32 on the CPU, against the
plain reference (benchmarks/reference/kimi_k2_reference.py) and against the
same layer with the bound lifted to every pair (the uncompacted text): a
share under its bound, a router that overflows it, and the shapes at which
the bound reaches T x k and the function is what it was."""

import dataclasses
import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_k2_reference as reference
from ray_tpu.models import llama, moe
from ray_tpu.ops.grouped_matmul import ROW_TILE

TOL = 2e-5          # tests/test_kimi_k2.py: float32 in another order reads 2e-6
H, M, E, K, T = 64, 32, 32, 4, 256
# what the reference's router reads of a model file, and the same routing as
# the program's configuration: Kimi's (sigmoid scores, a bias that chooses,
# the chosen renormalised and scaled)
MODEL = {"num_experts_per_tok": K, "norm_topk_prob": True, "routed_scaling_factor": 2.827}
EXPERTS = moe.MoEConfig(base=llama.LlamaConfig.tiny(), num_experts=E, top_k=K,
                        norm_topk_prob=True, score_func="sigmoid", routed_scaling=2.827)


@pytest.fixture(scope="module")
def layer32():
    """(one whole layer's float32 weights over 32 experts, y [1, 256, 64]):
    1,024 (token, choice) pairs, so a share of 2 or 4 experts has a bound of
    256 or 512 rows under them."""
    ks = jax.random.split(jax.random.PRNGKey(38), 6)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    whole = {"router": dense(ks[0], H, E), "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "e_gate": dense(ks[2], E, H, M), "e_up": dense(ks[3], E, H, M),
             "e_down": dense(ks[4], E, M, H)}
    return whole, jax.random.normal(ks[5], (1, T, H), jnp.float32)


def _share(whole: dict, first: int, count: int) -> dict:
    return {k: v[first:first + count] if k.startswith("e_") else v for k, v in whole.items()}


def _every_pair(pairs, count, num_experts):
    """`held_rows_bound` lifted: the layer gathers all T x k sorted rows, as
    it did before it had a bound."""
    return pairs


def _miss(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


def test_the_bound_is_four_times_an_even_share_in_whole_row_tiles():
    """At the cells' shapes (ISSUE 38): Kimi's 2,048 prefill and its 64-slot
    decode step compact, Xing's 512 prefill compacts, Xing's 48-slot decode
    step and the tiny presets do not."""
    assert ROW_TILE == 256
    assert moe.held_rows_bound(2048 * 8, 12, 384) == 2048
    assert moe.held_rows_bound(64 * 8, 12, 384) == 256
    assert moe.held_rows_bound(512 * 4, 8, 64) == 1024
    assert moe.held_rows_bound(48 * 4, 8, 64) == 48 * 4
    assert moe.held_rows_bound(40 * 4, 8, 16) == 40 * 4
    assert moe.held_rows_bound(T * K, 2, E) == 256 and moe.held_rows_bound(T * K, 4, E) == 512
    # never more than every pair, and an uneven share rounds up
    assert moe.held_rows_bound(1000, 1, 3) == 1000
    assert moe.held_rows_bound(4096, 1, 33) == 512


@pytest.mark.parametrize("first, count, stacked", [(4, 2, False), (8, 4, False), (4, 2, True)],
                         ids=["2-of-32", "4-of-32", "2-of-32-stacked"])
def test_a_share_under_its_bound_gives_the_uncompacted_layer(layer32, first, count, stacked):
    """(1) The held pairs fit the bound: the layer's output is the
    uncompacted layer's and the reference's, `rows` the same count, and
    `moved` says ONE bound of rows was gathered, not T x k. With `stacked`
    the experts are every layer's, read in place, this layer the second."""
    whole, y = layer32
    held = dataclasses.replace(EXPERTS, experts_held=(first, count))
    share, kw = _share(whole, first, count), {}
    if stacked:
        kw["stacked"] = {k: jnp.stack([jnp.zeros_like(share[k]), share[k], share[k] + 1.0])
                         for k in ("e_gate", "e_up", "e_down")}
        share = {**{k: v for k, v in share.items() if not k.startswith("e_")},
                 "stack_index": jnp.int32(1)}
    bound = moe.held_rows_bound(T * K, count, E)
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, share, held, platform="cpu", **kw)
        with mock.patch.object(moe, "held_rows_bound", _every_pair):
            plain, plain_stats = moe.moe_mlp(y, share, held, platform="cpu", **kw)
        want = reference.expert_layer(y[0], {**whole, **_share(whole, first, count)}, MODEL,
                                      first=first, shared=False)
    assert 0 < int(stats["rows"]) <= bound < T * K
    assert int(stats["rows"]) == int(plain_stats["rows"])
    assert (int(stats["moved"]), int(plain_stats["moved"])) == (bound, T * K)
    np.testing.assert_array_equal(np.asarray(stats["experts"]), np.asarray(plain_stats["experts"]))
    np.testing.assert_allclose(np.asarray(stats["load"]), np.asarray(plain_stats["load"]))
    assert _miss(out[0], plain[0]) < TOL and _miss(out[0], want) < TOL


@pytest.mark.parametrize("favoured", [(4, 5), (5,), (4,)],
                         ids=["both-held", "the-second-held", "the-first-held"])
def test_a_router_that_overflows_the_bound_drops_nothing(layer32, favoured):
    """(2) A correction bias that sends EVERY token to held experts: more
    pairs than the bound, so the layer takes a second chunk of rows (an
    expert's rows straddle the chunks' edge, and the last chunk is part
    empty where one expert is favoured). Every row still matches the dense
    reference, and `moved` says the bound overflowed."""
    whole, y = layer32
    held = dataclasses.replace(EXPERTS, experts_held=(4, 2))
    whole = {**whole, "router_bias": whole["router_bias"].at[jnp.array(favoured)].add(100.0)}
    share = _share(whole, 4, 2)
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, share, held, platform="cpu")
        want = reference.expert_layer(y[0], {**whole, **share}, MODEL, first=4, shared=False)
    rows = int(stats["rows"])
    assert 256 < rows <= 2 * T and (rows == 2 * T) == (len(favoured) == 2)
    assert int(stats["moved"]) == 2 * 256 >= rows
    per_row = np.abs(np.asarray(out[0] - want)).max(axis=1) / np.abs(np.asarray(want)).max()
    assert per_row.max() < TOL, int(per_row.argmax())
    assert _miss(out[0], want) < TOL


def test_a_share_that_holds_no_pair_adds_nothing(layer32):
    """No pair routed here (the bias sends every token elsewhere): no chunk
    runs, the part of the sum is zero and `moved` is 0."""
    whole, y = layer32
    held = dataclasses.replace(EXPERTS, experts_held=(4, 2))
    share = _share({**whole, "router_bias": whole["router_bias"].at[4:6].add(-100.0)}, 4, 2)
    out, stats = moe.moe_mlp(y, share, held, platform="cpu")
    assert (int(stats["rows"]), int(stats["moved"])) == (0, 0)
    assert not np.asarray(out).any()


def _lowered(num_experts: int, count: int, tokens: int) -> str:
    held = dataclasses.replace(EXPERTS, num_experts=num_experts, experts_held=(0, count))
    w = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    layer = {"router": w(H, num_experts), "router_bias": w(num_experts),
             "e_gate": w(count, H, M), "e_up": w(count, H, M), "e_down": w(count, M, H)}
    return jax.jit(lambda y, layer: moe.moe_mlp(y, layer, held, platform="cpu")).lower(
        w(1, tokens, H), layer).as_text()


@pytest.mark.parametrize("num_experts, count, tokens, compacts", [
    (16, 8, 40, False),      # the tiny preset's halves
    (64, 8, 48, False),      # Xing's decode step: 48 x 4 pairs, 8 of 64 held
    (32, 2, 256, True),      # a share under its bound
], ids=["tiny-8-of-16", "xing-decode-8-of-64", "2-of-32"])
def test_where_the_bound_reaches_every_pair_the_layer_is_what_it_was(
        num_experts, count, tokens, compacts):
    """(4) The bound is static. Where it reaches T x k the lowered text holds
    no loop, no branch, and exactly the two row gathers it always had
    (dispatch, combine) over `[T * k, H]`; where it does not, ONE loop, no
    branch, and no array of T * k rows at all."""
    text = _lowered(num_experts, count, tokens)
    pairs = tokens * K
    assert (moe.held_rows_bound(pairs, count, num_experts) < pairs) == compacts
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.while") == int(compacts)
    wide = re.findall(rf"-> tensor<{pairs}x{H}xf32>", text)
    gathers = re.findall(rf'"stablehlo\.gather".*-> tensor<{pairs}x{H}xf32>', text)
    assert len(gathers) == (0 if compacts else 2), gathers
    assert bool(wide) == (not compacts)


def _dense_loop(y, w, cfg, held, act):
    """The layer as a loop over experts, every expert on every token: the
    router's weights as a [T, E] matrix that is zero where an expert was not
    chosen, a gated expert `act(y G) * (y U)`, an un-gated one `act(y U^T)`
    with its up-projection held transposed, the shared expert unweighted."""
    s = jax.nn.sigmoid(y @ w["router"])
    chosen = jax.lax.top_k(s + w["router_bias"], cfg.top_k)[1]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    picked = picked / picked.sum(axis=-1, keepdims=True) * cfg.routed_scaling
    weights = jnp.zeros_like(s).at[jnp.arange(y.shape[0])[:, None], chosen].set(picked)
    first, count = held or (0, cfg.num_experts)
    out = jnp.zeros_like(y)
    for e in range(count):
        hidden = (act(y @ w["e_gate"][e]) * (y @ w["e_up"][e]) if "e_gate" in w
                  else act(y @ w["e_up_t"][e].T))
        out = out + weights[:, first + e, None] * (hidden @ w["e_down"][e])
    hidden = (act(y @ w["s_gate"]) * (y @ w["s_up"]) if "s_gate" in w else act(y @ w["s_up"]))
    return out + hidden @ w["s_down"]


@pytest.mark.parametrize("held", [None, (4, 2), (8, 4)], ids=["whole", "2-of-32", "4-of-32"])
@pytest.mark.parametrize("form", ["gated-silu", "ungated-relu2"])
def test_gated_and_ungated_experts_against_a_dense_loop(layer32, form, held):
    """The expert's FORM is read from the keys a layer holds: with `e_gate` /
    `s_gate` three products and `activation` on the gate; without, two
    products, `relu(y U)^2 D`, the up-projection transposed as `e_up_t`, and a
    shared expert of the same un-gated form. Whole, and as a share that
    compacts (2 of 32: one bound of 256 rows; 4 of 32: 512)."""
    whole, y = layer32
    ks = jax.random.split(jax.random.PRNGKey(45), 3)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    w = {**whole, "s_gate": dense(ks[0], H, 48), "s_up": dense(ks[1], H, 48),
         "s_down": dense(ks[2], 48, H)}
    cfg = EXPERTS
    if form == "ungated-relu2":
        cfg = dataclasses.replace(cfg, activation="relu2")
        w = {k: v for k, v in w.items() if k not in ("e_gate", "s_gate", "e_up")}
        w["e_up_t"] = whole["e_up"].swapaxes(1, 2)
    cfg = dataclasses.replace(cfg, experts_held=held)
    if held:
        w = {k: v[held[0]:held[0] + held[1]] if k.startswith("e_") else v for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, w, cfg, platform="cpu")
        want = _dense_loop(y[0], w, cfg, held, moe.ACTIVATIONS[cfg.activation])
    assert _miss(out[0], want) < TOL
    if held:
        assert int(stats["moved"]) == moe.held_rows_bound(T * K, held[1], E) < T * K
    # the other form's arithmetic on the same weights is another function
    other = moe.ACTIVATIONS["silu" if form == "ungated-relu2" else "relu2"]
    with jax.default_matmul_precision("highest"):
        assert _miss(_dense_loop(y[0], w, cfg, held, other), want) > 0.05
