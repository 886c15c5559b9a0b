"""`moe.moe_mlp` with a chip's share of the experts held (`experts_held`):
the rows it moves are the rows it computes, `moe.held_rows_trip` (an even
router's share and a quarter, static) at a time. In float32 on the CPU,
against the plain reference (benchmarks/reference/kimi_k2_reference.py) and
against the same layer with the trip lifted to every pair (the uncompacted
text): a share inside one trip, a router that sends it more, shares shaped
like the cells' (Nemotron's eighth at 6 a token, Kimi's thirty-second at 8),
and the shapes at which four even shares reach T x k and the function is
what it was."""

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
    1,024 (token, choice) pairs, so a share of 2 or 4 experts moves one
    256-row tile of them a trip."""
    ks = jax.random.split(jax.random.PRNGKey(38), 6)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    whole = {"router": dense(ks[0], H, E), "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "e_gate": dense(ks[2], E, H, M), "e_up": dense(ks[3], E, H, M),
             "e_down": dense(ks[4], E, M, H)}
    return whole, jax.random.normal(ks[5], (1, T, H), jnp.float32)


def _share(whole: dict, first: int, count: int) -> dict:
    return {k: v[first:first + count] if k.startswith("e_") else v for k, v in whole.items()}


def _every_pair(pairs, count, num_experts):
    """`held_rows_trip` lifted: the layer gathers all T x k sorted rows, as
    it did before a share compacted."""
    return pairs


def _miss(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


def _worst_row(got, want) -> tuple[float, int]:
    """(the largest error of any row over the largest value, that row)."""
    per_row = np.abs(np.asarray(got - want)).max(axis=1) / np.abs(np.asarray(want)).max()
    return float(per_row.max()), int(per_row.argmax())


def test_the_trip_is_an_even_share_and_a_quarter_in_whole_row_tiles():
    """At the cells' shapes (ISSUE 47): Nemotron's 4,096 prefill moves 3,840
    rows a trip where it moved 3 T (four even shares, ISSUE 38's bound), its
    2,048 prefill T, Kimi's 2,048 prefill 768 where it moved T, Xing's 512
    prefill two tiles where it moved four; the three decode steps one tile,
    as they did. WHICH shares compact is what it was: under a quarter of the
    pairs, so Xing's 48-slot decode step and the tiny presets move every
    pair."""
    assert ROW_TILE == 256
    assert (moe.COMPACTS_OVER_EVEN, moe.TRIP_OVER_EVEN) == (4, (5, 4))
    assert moe.held_rows_trip(4096 * 6, 16, 128) == 3840
    assert moe.held_rows_trip(2048 * 6, 16, 128) == 2048
    assert moe.held_rows_trip(48 * 6, 16, 128) == 256
    assert moe.held_rows_trip(2048 * 8, 12, 384) == 768
    assert moe.held_rows_trip(128 * 8, 12, 384) == moe.held_rows_trip(64 * 8, 12, 384) == 256
    assert moe.held_rows_trip(512 * 4, 8, 64) == 512
    assert moe.held_rows_trip(256 * 4, 8, 64) == moe.held_rows_trip(128 * 4, 8, 64) == 256
    assert moe.held_rows_trip(48 * 4, 8, 64) == 48 * 4
    assert moe.held_rows_trip(40 * 4, 8, 16) == 40 * 4
    assert moe.held_rows_trip(T * K, 2, E) == 256 == moe.held_rows_trip(T * K, 4, E)
    # a quarter of the experts or more moves every pair (LFM2's 16 of 32),
    # so does a share whose four even shares round up to them; an uneven
    # share rounds up
    assert moe.held_rows_trip(4096 * 4, 16, 32) == 4096 * 4
    assert moe.held_rows_trip(1000, 1, 3) == 1000 == moe.held_rows_trip(1000, 1, 5)
    assert moe.held_rows_trip(4096, 1, 33) == 256 and moe.held_rows_trip(8192, 1, 33) == 512


@pytest.mark.parametrize("first, count, stacked", [(4, 2, False), (8, 4, False), (4, 2, True)],
                         ids=["2-of-32", "4-of-32", "2-of-32-stacked"])
def test_a_share_under_its_bound_gives_the_uncompacted_layer(layer32, first, count, stacked):
    """(1) The held pairs fit one trip: the layer's output is the
    uncompacted layer's and the reference's, `rows` the same count, and
    `moved` says ONE trip of rows was gathered, not T x k. With `stacked`
    the experts are every layer's, read in place, this layer the second."""
    whole, y = layer32
    held = dataclasses.replace(EXPERTS, experts_held=(first, count))
    share, kw = _share(whole, first, count), {}
    if stacked:
        kw["stacked"] = {k: jnp.stack([jnp.zeros_like(share[k]), share[k], share[k] + 1.0])
                         for k in ("e_gate", "e_up", "e_down")}
        share = {**{k: v for k, v in share.items() if not k.startswith("e_")},
                 "stack_index": jnp.int32(1)}
    trip = moe.held_rows_trip(T * K, count, E)
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, share, held, platform="cpu", **kw)
        with mock.patch.object(moe, "held_rows_trip", _every_pair):
            plain, plain_stats = moe.moe_mlp(y, share, held, platform="cpu", **kw)
        want = reference.expert_layer(y[0], {**whole, **_share(whole, first, count)}, MODEL,
                                      first=first, shared=False)
    assert 0 < int(stats["rows"]) <= trip < T * K
    assert int(stats["rows"]) == int(plain_stats["rows"])
    assert (int(stats["moved"]), int(plain_stats["moved"])) == (trip, T * K)
    np.testing.assert_array_equal(np.asarray(stats["experts"]), np.asarray(plain_stats["experts"]))
    np.testing.assert_allclose(np.asarray(stats["load"]), np.asarray(plain_stats["load"]))
    assert _miss(out[0], plain[0]) < TOL and _miss(out[0], want) < TOL


@pytest.mark.parametrize("favoured", [(4, 5), (5,), (4,)],
                         ids=["both-held", "the-second-held", "the-first-held"])
def test_a_router_that_overflows_the_bound_drops_nothing(layer32, favoured):
    """(2) A correction bias that sends EVERY token to held experts: more
    pairs than a trip holds, so the layer takes a second trip (an expert's
    rows straddle the trips' edge, and the last trip is part empty where
    one expert is favoured). Every row still matches the dense reference,
    and `moved` counts both trips."""
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
    assert _worst_row(out[0], want)[0] < TOL, _worst_row(out[0], want)
    assert _miss(out[0], want) < TOL


def test_a_share_that_holds_no_pair_adds_nothing(layer32):
    """No pair routed here (the bias sends every token elsewhere): no trip
    runs, the part of the sum is zero and `moved` is 0."""
    whole, y = layer32
    held = dataclasses.replace(EXPERTS, experts_held=(4, 2))
    share = _share({**whole, "router_bias": whole["router_bias"].at[4:6].add(-100.0)}, 4, 2)
    out, stats = moe.moe_mlp(y, share, held, platform="cpu")
    assert (int(stats["rows"]), int(stats["moved"])) == (0, 0)
    assert not np.asarray(out).any()


def _wider_than_tokens(text: str, tokens: int) -> list:
    """The `[T, n]` arrays of a lowered text with n > T."""
    return [m.group(0) for m in re.finditer(rf"tensor<{tokens}x(\d+)x[a-z]\w*>", text)
            if int(m.group(1)) > tokens]


def _lowered(num_experts: int, count: int, tokens: int, top_k: int = K) -> str:
    held = dataclasses.replace(EXPERTS, num_experts=num_experts, top_k=top_k,
                               experts_held=(0, count))
    w = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    layer = {"router": w(H, num_experts), "router_bias": w(num_experts),
             "e_gate": w(count, H, M), "e_up": w(count, H, M), "e_down": w(count, M, H)}
    return jax.jit(lambda y, layer: moe.moe_mlp(y, layer, held, platform="cpu")).lower(
        w(1, tokens, H), layer).as_text()


@pytest.mark.parametrize("num_experts, count, tokens, compacts", [
    (16, 8, 40, False),      # the tiny preset's halves
    (64, 8, 48, False),      # Xing's decode step: 48 x 4 pairs, 8 of 64 held
    (32, 2, 256, True),      # a share of a sixteenth
    (32, 8, 256, False),     # a quarter of the experts (LFM2's is a half)
], ids=["tiny-8-of-16", "xing-decode-8-of-64", "2-of-32", "8-of-32"])
def test_where_the_bound_reaches_every_pair_the_layer_is_what_it_was(
        num_experts, count, tokens, compacts):
    """(4) The trip is static. Where four even shares reach T x k the lowered
    text holds no loop, no branch, and exactly the two row gathers it always
    had (dispatch, combine) over `[T * k, H]`; where they do not, ONE loop,
    no branch, no array of T * k rows at all and no `[T, n]` array wider than
    T (ISSUE 38's combine multiplied by a `[T, four even shares]` one-hot)."""
    text = _lowered(num_experts, count, tokens)
    pairs = tokens * K
    assert (moe.held_rows_trip(pairs, count, num_experts) < pairs) == compacts
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert text.count("stablehlo.while") == int(compacts)
    wide = re.findall(rf"-> tensor<{pairs}x{H}xf32>", text)
    gathers = re.findall(rf'"stablehlo\.gather".*-> tensor<{pairs}x{H}xf32>', text)
    assert len(gathers) == (0 if compacts else 2), gathers
    assert bool(wide) == (not compacts)
    assert not compacts or not _wider_than_tokens(text, tokens)


def _dense_loop(y, w, cfg, held, act):
    """The layer as a loop over experts, every expert on every token: the
    router's weights as a [T, E] matrix that is zero where an expert was not
    chosen, a gated expert `act(y G) * (y U)`, an un-gated one `act(y U^T)`
    with its up-projection held transposed, the shared expert (where the
    layer holds one) unweighted."""
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
    if "s_up" not in w:
        return out
    hidden = (act(y @ w["s_gate"]) * (y @ w["s_up"]) if "s_gate" in w else act(y @ w["s_up"]))
    return out + hidden @ w["s_down"]


@pytest.mark.parametrize("held", [None, (4, 2), (8, 4)], ids=["whole", "2-of-32", "4-of-32"])
@pytest.mark.parametrize("form", ["gated-silu", "ungated-relu2"])
def test_gated_and_ungated_experts_against_a_dense_loop(layer32, form, held):
    """The expert's FORM is read from the keys a layer holds: with `e_gate` /
    `s_gate` three products and `activation` on the gate; without, two
    products, `relu(y U)^2 D`, the up-projection transposed as `e_up_t`, and a
    shared expert of the same un-gated form. Whole, and as a share that
    compacts (2 or 4 of 32: one trip of 256 rows)."""
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
        assert int(stats["moved"]) == moe.held_rows_trip(T * K, held[1], E) < T * K
    # the other form's arithmetic on the same weights is another function
    other = moe.ACTIVATIONS["silu" if form == "ungated-relu2" else "relu2"]
    with jax.default_matmul_precision("highest"):
        assert _miss(_dense_loop(y[0], w, cfg, held, other), want) > 0.05


# the cells' shares (ISSUE 47), at this file's widths and 256 tokens: (the
# experts' form, top_k, (first, count) of 32, the rows ISSUE 38's bound moved)
CELL_SHARES = {
    # Nemotron: an eighth of the experts at 6 a token, un-gated relu2; an even
    # share is 192 of 1,536 pairs, four of them were 3 T
    "nemotron": ("ungated-relu2", 6, (8, 4), 768),
    # Kimi: a thirty-second at 8 a token, gated; four even shares were T
    "kimi": ("gated-silu", 8, (5, 1), 256),
}


def _cell_share(layer32, name: str, bias: float = 0.0):
    """(cfg, the share's weights, y) of `CELL_SHARES[name]`, with `bias` added
    to the held experts' correction bias."""
    whole, y = layer32
    form, top_k, (first, count), _ = CELL_SHARES[name]
    cfg = dataclasses.replace(EXPERTS, top_k=top_k, experts_held=(first, count))
    whole = {**whole, "router_bias": whole["router_bias"].at[first:first + count].add(bias)}
    if form == "ungated-relu2":
        cfg = dataclasses.replace(cfg, activation="relu2")
        whole = {**{k: v for k, v in whole.items() if k not in ("e_gate", "e_up")},
                 "e_up_t": whole["e_up"].swapaxes(1, 2)}
    return cfg, _share(whole, first, count), y


@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stacked"])
@pytest.mark.parametrize("name", sorted(CELL_SHARES))
def test_a_share_shaped_like_a_cell_s_gives_the_uncompacted_layer(layer32, name, stacked):
    """(5) At Nemotron's share (four even shares were 3 T) and at Kimi's (they
    were T), where the trip is one tile, read from the layer and from every
    layer's stack in place: the output is the uncompacted layer's and the
    dense loop's ROW FOR ROW, `rows` is what the uncompacted layer counts,
    and `moved` the whole trips that hold them."""
    cfg, share, y = _cell_share(layer32, name)
    _, top_k, (first, count), old_bound = CELL_SHARES[name]
    trip = moe.held_rows_trip(T * top_k, count, E)
    assert trip == ROW_TILE <= T and old_bound == min(T * top_k, 4 * T * top_k * count // E)
    layer, kw = share, {}
    if stacked:
        leaves = [k for k in share if k.startswith("e_")]
        kw["stacked"] = {k: jnp.stack([jnp.zeros_like(share[k]), share[k], share[k] + 1.0])
                         for k in leaves}
        layer = {**{k: v for k, v in share.items() if k not in leaves},
                 "stack_index": jnp.int32(1)}
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, layer, cfg, platform="cpu", **kw)
        with mock.patch.object(moe, "held_rows_trip", _every_pair):
            plain, plain_stats = moe.moe_mlp(y, layer, cfg, platform="cpu", **kw)
        want = _dense_loop(y[0], share, cfg, cfg.experts_held, moe.ACTIVATIONS[cfg.activation])
    rows = int(stats["rows"])
    assert 0 < rows == int(plain_stats["rows"])
    assert int(stats["moved"]) == -(-rows // trip) * trip <= old_bound
    assert int(plain_stats["moved"]) == T * top_k
    np.testing.assert_array_equal(np.asarray(stats["experts"]), np.asarray(plain_stats["experts"]))
    for other in (plain[0], want):
        assert _worst_row(out[0], other)[0] < TOL, _worst_row(out[0], other)


@pytest.mark.parametrize("name", sorted(CELL_SHARES))
def test_a_bias_toward_a_cell_shaped_share_drops_nothing(layer32, name):
    """(6) A bias that sends every token to every held expert: `count` pairs a
    token, T x count rows, as many trips as hold them (four at Nemotron's
    shape, where ISSUE 38's bound took two chunks of 768; one full trip at
    Kimi's), every row the dense loop's and `moved` the trips it took."""
    cfg, share, y = _cell_share(layer32, name, bias=100.0)
    _, top_k, (first, count), _ = CELL_SHARES[name]
    trip = moe.held_rows_trip(T * top_k, count, E)
    with jax.default_matmul_precision("highest"):
        out, stats = moe.moe_mlp(y, share, cfg, platform="cpu")
        want = _dense_loop(y[0], share, cfg, cfg.experts_held, moe.ACTIVATIONS[cfg.activation])
    assert int(stats["rows"]) == T * count
    assert int(stats["moved"]) == T * count == (T * count // trip) * trip
    assert _worst_row(out[0], want)[0] < TOL, _worst_row(out[0], want)


@pytest.mark.parametrize("name", sorted(CELL_SHARES))
def test_a_cell_shaped_share_that_holds_no_pair_moves_nothing(layer32, name):
    """(7) The bias sends every token elsewhere: no trip, a zero part."""
    cfg, share, y = _cell_share(layer32, name, bias=-100.0)
    out, stats = moe.moe_mlp(y, share, cfg, platform="cpu")
    assert (int(stats["rows"]), int(stats["moved"])) == (0, 0)
    assert not np.asarray(out).any()


@pytest.mark.parametrize("name", sorted(CELL_SHARES))
def test_a_cell_shaped_share_builds_no_array_wider_than_its_tokens(name):
    """(8) The lowered text of the compacting arm at the cells' shares: one
    loop, no array of T x k rows, and no `[T, n]` array with n > T: the
    one-hot of the combine is `[T, trip]`, where at Nemotron's share ISSUE
    38's was `[T, 3 T]`."""
    _, top_k, (_, count), old_bound = CELL_SHARES[name]
    text = _lowered(E, count, T, top_k=top_k)
    assert text.count("stablehlo.while") == 1
    assert not re.findall(rf"tensor<{T * top_k}x{H}xf32>", text)
    assert not _wider_than_tokens(text, T)
    assert f"tensor<{T}x{ROW_TILE}xf32>" in text
    # the detector sees ISSUE 38's operand where the trip is four even shares
    with mock.patch.object(moe, "held_rows_trip", lambda *a: old_bound):
        wide = _wider_than_tokens(_lowered(E, count, T, top_k=top_k), T)
    assert bool(wide) == (old_bound > T), wide
