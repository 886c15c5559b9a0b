"""models/kimi_k2.py (latent attention over a paged latent pool, read absorbed
at decode; a leading dense layer; sigmoid-routed experts of which this chip
holds a share, beside a shared expert) at a tiny size against the plain
reference (benchmarks/reference/kimi_k2_reference.py). On LOGITS, in float32:
prefill then decode through `forward_paged` and through the paged engine,
absorbed against unabsorbed decode, the shares adding up to the uncut layer,
the routing told apart from its neighbours, YaRN against a table worked out
by hand, the latent kernel interpreted against the dense read, and a latent
pool through the PD hand-off."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import kimi_k2 as family
from benchmarks.reference import kimi_k2_reference as reference
from ray_tpu.models import kimi_k2, llama, model_of, moe
from ray_tpu.ops.paged_attention import latent_decode_attention
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine, page_leaves
from tests.test_paged_attention import PAGE_WRITE_CASES, check_page_write_against_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (absorbed against per-head keys, a cache against a full recompute,
# sorted rows against a dense weighted sum): measured 2e-6 of the logits'
# size. 2e-5 admits that; each wrong program below misses by 0.05 or more.
TOL = 2e-5
BS = 16


@pytest.fixture(scope="module")
def tiny():
    """1 dense + 2 expert layers, hidden 64, 4 heads of 32 + 16 / 32, ranks 48
    and 128, 16 experts of which the second half is held, 4 a token: the
    benchmark's CPU stand-in of the Kimi configuration."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "kimi_k2-serve.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert (cfg.first_k_dense, cfg.base.num_layers, cfg.experts.experts_held) == (1, 2, (8, 8))
    params = jax.jit(lambda k: kimi_k2.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 33))
    # norm weights other than one, so that a norm in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    for stack in ("lead_layers", "layers"):
        params[stack] = {k: noisy(i, v) if k.endswith("_norm") else v
                         for i, (k, v) in enumerate(sorted(params[stack].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 46)
    return model, cfg, params, tokens


def _miss(got, want) -> float:
    """The benchmark's two measures (`serve_cell.check_against_reference`),
    the larger: rms error / rms logit and max error / max logit."""
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


def _prefill_then_decode(params, tokens, cfg, n_prompt: int, slot: int = 1,
                         chunk: int = 1, **decode):
    """Logits of positions n_prompt - 1 .. len(tokens) - 1 of ONE sequence in
    slot `slot` of 2: a prefill of `n_prompt` tokens, then the rest `chunk`
    tokens a step (1: the absorbed decode path, by the read `decode` asks for;
    2: the up-projected path again); and the pool's `moe_rows` a step."""
    pool = kimi_k2.init_kv_pool(cfg, 9, BS)
    assert pool["latent"].shape == (cfg.cache_layers, 9, BS, cfg.latent_row)
    tables = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)
    step = jax.jit(lambda pool, toks, lengths, **kw: kimi_k2.forward_paged(
        params, toks, cfg, pool, tables, lengths, BS, **kw),
        static_argnames=("use_kernel",))
    toks = np.zeros((2, n_prompt), np.int32)
    toks[slot] = tokens[:n_prompt]
    logits, pool = step(pool, jnp.asarray(toks), jnp.zeros(2, jnp.int32))
    rows, counted = [logits[slot, -1]], [int(pool["counters"]["moe_rows"])]
    for t in range(n_prompt, len(tokens), chunk):
        last = np.zeros((2, chunk), np.int32)
        last[slot] = tokens[t:t + chunk]
        lengths = np.zeros(2, np.int32)
        lengths[slot] = t
        logits, pool = step(pool, jnp.asarray(last), jnp.asarray(lengths), **decode)
        rows += list(logits[slot])
        counted.append(int(pool["counters"]["moe_rows"]))
    return np.stack(rows), counted


@pytest.mark.parametrize("decode", [
    dict(use_kernel=True), dict(use_kernel=False), dict(chunk=2)],
    ids=["absorbed-kernel", "absorbed-dense", "unabsorbed"])
def test_prefill_then_six_decode_steps_match_the_reference(tiny, decode):
    """(1) and (2): the prefill takes the up-projected path, the decode steps
    the absorbed one (through the interpreted kernel, or over the gathered
    view) or, two tokens a step, the unabsorbed one: all are the reference's
    logits, so absorbed and unabsorbed decode agree with each other."""
    model, cfg, params, tokens = tiny
    want = reference.logits(params, tokens, model)
    n_prompt = len(tokens) - 6
    got, counted = _prefill_then_decode(params, tokens, cfg, n_prompt, **decode)
    assert got.shape[0] == 7 and _miss(got, want[n_prompt - 1:]) < TOL
    # the counter: pairs routed to the 8 held of 16 experts, 2 layers, both
    # slots' tokens (the idle slot's token 0 is routed too): about half of
    # tokens x 4 x 2, and never more
    assert 0 < counted[0] <= 2 * n_prompt * 4 * 2
    per_step = 2 * decode.get("chunk", 1) * 4 * 2
    assert all(0 <= c <= per_step for c in counted[1:]) and sum(counted[1:]) > 0
    assert model_of(cfg) is kimi_k2.MODEL
    assert set(params["layers"]) == set(kimi_k2.logical_axes(cfg)["layers"])
    assert set(params["lead_layers"]) == set(kimi_k2.logical_axes(cfg)["lead_layers"])


@pytest.mark.parametrize("at_once, chunks", [(2 * 40 * 64, 2), (1, 4)],
                         ids=["two-heads-a-chunk", "one-head-a-chunk"])
def test_a_prefill_cut_into_chunks_of_heads_is_the_uncut_one(tiny, monkeypatch, at_once, chunks):
    """The timed cell's prefill (64 heads x 2,048 x 2,048 scores) runs its
    attention as a `lax.map` over 4 chunks of 16 heads; the tiny size never
    would. With the chunk's limit lowered the 4 heads go 2 or 1 a chunk:
    every position's logits of a 40-token prefill, in a slot whose pages are
    out of order, are the reference's, and the uncut prefill's."""
    model, cfg, params, tokens = tiny
    tables = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)
    toks = np.zeros((2, 40), np.int32)
    toks[1] = tokens[:40]

    def prefill():
        pool = kimi_k2.init_kv_pool(cfg, 9, BS)
        return jax.jit(lambda pool: kimi_k2.forward_paged(
            params, jnp.asarray(toks), cfg, pool, tables, jnp.zeros(2, jnp.int32), BS))(pool)

    assert kimi_k2._head_chunks(4, 40, 64) == 1
    uncut, uncut_pool = prefill()
    monkeypatch.setattr(kimi_k2, "SCORES_AT_ONCE", at_once)
    assert kimi_k2._head_chunks(4, 40, 64) == chunks
    # at the cell's own sizes the limit as shipped gives 4 chunks of 16 heads
    monkeypatch.undo()
    assert kimi_k2._head_chunks(64, 2048, 2048) == 4
    monkeypatch.setattr(kimi_k2, "SCORES_AT_ONCE", at_once)
    cut, cut_pool = prefill()
    want = reference.logits(params, tokens[:40], model)
    assert _miss(cut[1], want) < TOL and _miss(cut[1], uncut[1]) < TOL
    np.testing.assert_allclose(np.asarray(cut_pool["latent"]), np.asarray(uncut_pool["latent"]),
                               rtol=1e-5, atol=1e-5)
    assert int(cut_pool["counters"]["moe_rows"]) == int(uncut_pool["counters"]["moe_rows"])


@pytest.mark.parametrize("case", PAGE_WRITE_CASES)
def test_a_fresh_prefill_s_pages_leave_the_latent_pool_the_row_scatter_left(
        monkeypatch, tiny, case):
    """The latent family's fresh prefill writes its rows `[c_kv | k_rope |
    zeros]` as whole pages (`llama.write_pages`, scope `attn/latent_write`):
    the pool the row scatter left, exactly, outside the garbage block, the
    same logits, and the same next decode step, in `tests/
    test_paged_attention.py`'s four cases."""
    _, cfg, params, _ = tiny
    check_page_write_against_rows(
        monkeypatch, lambda tokens, pool, tables, lengths, **kw: kimi_k2.forward_paged(
            params, tokens, cfg, pool, tables, lengths, **kw),
        lambda blocks, bs: kimi_k2.init_kv_pool(cfg, blocks, bs), cfg.base.vocab_size,
        case)


@pytest.mark.parametrize("use_kernel", [None, True], ids=["dense-own-rows", "flash-interpreted"])
def test_a_fresh_prefill_over_its_own_rows_is_the_table_program_s(tiny, use_kernel):
    """`forward_paged(fresh=True)`: a prompt that starts at position 0
    attends over the rows it has just computed and reads nothing back. A
    64-token bucket whose last 18 rows are padding, in a slot whose pages are
    out of order beside an idle slot: every row's logits are the table
    program's (the live ones the reference's) and the pool holds the SAME
    rows: both write before they read, so the first layer's are the table
    program's bit for bit and a later layer's follow its inputs, the
    attention before it summed in another order. Under the crossover
    and off the TPU (`use_kernel=None`) that is the family's dense product
    over [S, S]; `use_kernel=True` is what the 1,024 bucket up runs on a TPU,
    the flash forward on 48-wide q/k beside 32-wide v with the family's
    `softmax_scale`, interpreted here (`llama.forward_paged`'s convention)."""
    model, cfg, params, tokens = tiny
    tables = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)
    toks = np.zeros((2, 64), np.int32)
    toks[1, :len(tokens)] = tokens
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == 48 and cfg.v_head_dim == 32

    def prefill(**kw):
        pool = kimi_k2.init_kv_pool(cfg, 9, BS)
        return jax.jit(lambda pool: kimi_k2.forward_paged(
            params, jnp.asarray(toks), cfg, pool, tables, jnp.zeros(2, jnp.int32), BS,
            **kw))(pool)

    by_table, table_pool = prefill()
    fresh, fresh_pool = prefill(fresh=True, use_kernel=use_kernel)
    want = reference.logits(params, tokens, model)
    assert _miss(fresh[1, :len(tokens)], want) < TOL
    assert _miss(fresh, by_table) < TOL
    got, held = np.asarray(fresh_pool["latent"]), np.asarray(table_pool["latent"])
    np.testing.assert_array_equal(got[0], held[0])
    np.testing.assert_allclose(got, held, rtol=1e-5, atol=1e-5)
    assert np.abs(held[:, [3, 1, 7], :, :cfg.kv_lora_rank]).min() > 0   # rows were written
    assert int(fresh_pool["counters"]["moe_rows"]) == int(table_pool["counters"]["moe_rows"])
    # what the default picks: the kernel from the 1,024 bucket up on a TPU alone
    assert [llama.flash_pays(S, "tpu") for S in (512, 1024, 2048)] == [False, True, True]
    assert not llama.flash_pays(2048, "cpu")


def test_the_engine_with_two_sequences_live_matches_the_reference(tiny):
    """Through `PagedLLMEngine`: two prompts admitted one after the other and
    decoded together; the logits each was sampled from are the reference's,
    and the engine's records carry the step's `moe_rows`, read with the
    step's ids and not before."""
    from ray_tpu.util import timeline

    model, cfg, params, tokens = tiny
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=9, prefill_buckets=(16, 32)), params=params, external_step=True)
    seen = []
    prefill, decode = eng._prefill, eng._decode

    def keep_prefill(params, pool, toks, table, start):
        logits, pool = prefill(params, pool, toks, table, start)
        seen.append(("prefill", None, np.asarray(logits)))
        return logits, pool

    def keep_decode(params, pool, last, lengths, tables):
        logits, pool = decode(params, pool, last, lengths, tables)
        seen.append(("decode", np.flatnonzero(eng.active), np.asarray(logits)))
        return logits, pool

    eng._prefill, eng._decode = keep_prefill, keep_decode
    prompts = [list(map(int, tokens[:21])), list(map(int, tokens[21:30]))]
    in_flight = []
    try:
        futs = [eng.generate(p, 5) for p in prompts]
        for _ in range(20):
            if all(f.done() for f in futs):
                break
            eng.step_once()
            if eng._flight is not None:
                in_flight.append((eng._flight.counters["moe_rows"],
                                  eng.pool["counters"]["moe_rows"]))
        out = [f.result(0) for f in futs]
    finally:
        eng.shutdown()
    prefills = [l for kind, _, l in seen if kind == "prefill"]
    for slot, (prompt, res) in enumerate(zip(prompts, out)):
        rows = [prefills[slot][0]] + [
            l[slot] for kind, live, l in seen if kind == "decode" and slot in live]
        want = reference.logits(params, prompt + res.token_ids[:-1], model)
        assert len(rows) == 5 and _miss(np.stack(rows), want[len(prompt) - 1:]) < TOL
    records = [e[7] for e in timeline.local_events()
               if e[0] == "span" and e[2] == "engine" and isinstance(e[7], dict)]
    admits = [r for r in records if "outcome" in r]
    steps = [r for r in records if "live" in r]
    assert len(admits) == 2 and all(r["moe_rows"] > 0 for r in admits)
    # the rows the expert layers gathered for them: at this size every pair
    # (a bucket's 16 or 32 tokens x 4 choices x 2 expert layers)
    assert [r["moe_moved"] for r in admits] == [32 * 4 * 2, 16 * 4 * 2]
    # what a step counted comes to the host with its ids, a pass later: the
    # first pass enqueued a step and read nothing. Until then the count lives
    # in a copy of its own, because the next step is given (and donates) the pool
    assert len(steps) == 4 and "moe_rows" not in steps[0]
    assert all(0 < r["moe_rows"] <= r["moe_moved"] == 2 * 4 * 2 for r in steps[1:])
    assert len(in_flight) == 3 and all(mine is not pools for mine, pools in in_flight)
    assert all(int(mine) > 0 for mine, _ in in_flight)
    assert eng.kv_memory_bytes() == cfg.cache_layers * 9 * BS * cfg.latent_row * 4


@pytest.mark.parametrize("E, count, T", [(16, 8, 40), (32, 4, 256)],
                         ids=["halves", "eighths-that-compact"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, E, count, T):
    """(3) The guide's tie: the parts of the routed sum that the shares of
    the E experts give (here through the PROGRAM's `moe_mlp`, each share
    with its own experts' weights), the shared expert counted once, add up
    to what the uncut reference gives for the whole layer. The halves of 16
    move every pair; an eighth of 32 moves its held pairs an even share and a
    quarter at a time, one 256-row tile (`moe.held_rows_trip`)."""
    model, cfg, params, _ = tiny
    h, m = 64, 32
    trip = moe.held_rows_trip(T * 4, count, E)
    compacts = trip < T * 4
    assert compacts == (E == 32)
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    whole = {"router": dense(ks[0], h, E), "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "e_gate": dense(ks[2], E, h, m), "e_up": dense(ks[3], E, h, m),
             "e_down": dense(ks[4], E, m, h), "s_gate": dense(ks[5], h, m),
             "s_up": dense(ks[6], h, m), "s_down": dense(ks[7], m, h)}
    y = jax.random.normal(ks[8], (1, T, h), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.expert_layer(y[0], whole, model, first=0)
        shared = reference.expert_layer(y[0], {**whole, **{
            k: whole[k][:0] for k in ("e_gate", "e_up", "e_down")}}, model, first=0)
        parts, rows, moved, trips = [], 0, 0, 0
        for first in range(0, E, count):
            share = {k: v[first:first + count] if k.startswith("e_") else v
                     for k, v in whole.items() if not k.startswith("s_")}
            held = dataclasses.replace(cfg.experts, num_experts=E, experts_held=(first, count))
            out, stats = moe.moe_mlp(y, share, held, platform="cpu")
            parts.append(out[0])
            rows += int(stats["rows"])
            moved += int(stats["moved"])
            trips += -(-int(stats["rows"]) // trip)
            # the reference, given the same share, gives the same part
            assert _miss(out[0], reference.expert_layer(
                y[0], {**whole, **share}, model, first=first, shared=False)) < TOL
    assert rows == T * 4                     # every pair is some share's
    # a share that compacts gathers whole trips, the halves every pair each
    assert moved == trips * trip and trips >= E // count
    assert _miss(sum(parts) + shared, uncut) < TOL
    assert _miss(parts[0] + shared, uncut) > 0.1    # one share alone is not the layer


def test_sigmoid_routing_is_told_apart_from_its_neighbours(tiny):
    """(4) The bias chooses and does not weigh; the weights are the chosen
    scores renormalised and scaled. By hand on one token, and the program's
    layer against the reference's with softmax scoring, with the bias in the
    weights, and with an unnormalised top-k: each misses."""
    model, cfg, params, _ = tiny
    layer = {k: v[0] for k, v in params["layers"].items()}
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(y[0] @ layer["router"])
        b = layer["router_bias"]
        weights = np.asarray(reference.route(y[0], layer, model))
        for t in (0, 7, 23):
            chosen = np.argsort(-np.asarray(s[t] + b))[:4]
            assert set(np.flatnonzero(weights[t])) == set(chosen)
            by_hand = np.asarray(s[t])[chosen] / np.asarray(s[t])[chosen].sum() * 2.827
            assert np.allclose(weights[t][chosen], by_hand, rtol=1e-5)
        assert np.allclose(weights.sum(axis=1), 2.827, rtol=1e-5)
        # the bias reorders: it is not the top 4 of the scores alone everywhere
        assert any(set(np.argsort(-np.asarray(s[t]))[:4]) != set(np.flatnonzero(weights[t]))
                   for t in range(24))
        got = moe.moe_mlp(y, layer, cfg.experts, platform="cpu")[0][0]
        want = reference.expert_layer(y[0], layer, model, first=8)
        assert _miss(got, want) < TOL
        wrong = {
            "softmax": dataclasses.replace(cfg.experts, score_func="softmax"),
            "unnormalised": dataclasses.replace(cfg.experts, norm_topk_prob=False),
            "unscaled": dataclasses.replace(cfg.experts, routed_scaling=1.0),
        }
        for name, experts in wrong.items():
            assert _miss(moe.moe_mlp(y, layer, experts, platform="cpu")[0][0], want) > 0.05, name
        # nor does the bias weigh: weights taken from s + b are others
        s_b = s + b
        top = jax.lax.top_k(s_b, 4)[1]
        w_b = jnp.take_along_axis(s_b, top, axis=-1)
        w_b = w_b / w_b.sum(-1, keepdims=True) * 2.827
        assert not np.allclose(np.asarray(w_b), np.take_along_axis(
            weights, np.asarray(top), axis=-1), rtol=1e-3)


def test_yarn_frequencies_and_the_softmax_scale_against_a_table():
    """(5) At the published values (rotary 64, theta 50000, factor 64,
    original 4096, beta 32 / 1): pairs 0-9 keep their frequency, pairs from 23
    on turn 64 times slower, a linear ramp between; m = 0.1 ln 64 + 1 and the
    softmax scale is 192^-0.5 m^2."""
    sc = {"factor": 64, "original_max_position_embeddings": 4096, "beta_fast": 32,
          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1, "type": "yarn"}
    # the pair that turns n times in 4096 positions: 64 ln(4096 / (2 pi n)) / (2 ln 50000)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(50000))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(50000))
    assert (math.floor(low), math.ceil(high)) == (8, 20)
    table = []
    for i in range(32):
        plain = 50000 ** (-2 * i / 64)
        slow = min(max((i - 8) / 12, 0.0), 1.0)
        table.append(plain / 64 * slow + plain * (1 - slow))
    assert table[0] == 1.0 and table[8] == pytest.approx(50000 ** -0.25)
    assert table[14] == pytest.approx(50000 ** (-28 / 64) * (0.5 / 64 + 0.5))
    assert table[20] == pytest.approx(50000 ** (-40 / 64) / 64)
    assert table[31] == pytest.approx(50000 ** (-62 / 64) / 64)
    ref = reference.yarn_inverse_frequencies(64, 50000.0, sc)
    got = kimi_k2.yarn_inv_freq(64, 50000.0, (64.0, 4096, 32.0, 1.0, 1.0))
    assert np.allclose(ref, table, rtol=1e-12) and np.allclose(got, table, rtol=1e-6)
    m = 0.1 * math.log(64) + 1
    assert m == pytest.approx(1.4159, abs=1e-4)
    cfg = dataclasses.replace(kimi_k2.KimiK2Config.tiny(), qk_nope_head_dim=128,
                              qk_rope_head_dim=64, yarn=(64.0, 4096, 32.0, 1.0, 1.0))
    want = 192 ** -0.5 * m * m
    assert cfg.softmax_scale == pytest.approx(want) == pytest.approx(0.14468, abs=1e-5)
    assert reference.softmax_scale({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                                    "rope_scaling": sc}) == pytest.approx(want)
    # no scaling: plain rope, plain scale
    assert np.allclose(kimi_k2.yarn_inv_freq(64, 50000.0, None),
                       [50000 ** (-2 * i / 64) for i in range(32)], rtol=1e-6)
    assert dataclasses.replace(cfg, yarn=None).softmax_scale == pytest.approx(192 ** -0.5)


@pytest.mark.parametrize("lengths", [[1, 16, 17, 100], [64, 33, 128, 5]])
def test_the_latent_kernel_interpreted_matches_the_dense_read(lengths):
    """(6) `latent_decode_attention` in interpret mode against softmax over
    the gathered rows: ragged lengths, pages scattered through the pool,
    groups crossed (8 pages a table of 16-token blocks), a layer index."""
    B, H, row, rank, L, NB = 4, 8, 256, 128, 3, 40
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    pool = jax.random.normal(ks[0], (L, NB, BS, row), jnp.float32)
    pool = pool.at[..., 200:].set(0.0)              # the row's padding lanes
    q = jax.random.normal(ks[1], (B, H, row), jnp.float32).at[..., 200:].set(0.0)
    tables = jax.random.permutation(ks[2], jnp.arange(1, 33)).reshape(B, 8).astype(jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    layer, scale = 2, 0.21
    got = latent_decode_attention(q, pool, tables, lengths, layer=jnp.asarray(layer),
                                  rank=rank, scale=scale, interpret=True)
    view = pool[layer][tables].reshape(B, 8 * BS, row)
    s = jnp.einsum("bhr,btr->bht", q, view) * scale
    s = jnp.where(jnp.arange(8 * BS)[None, None] < lengths[:, None, None], s, -1e30)
    want = jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, axis=-1), view[..., :rank])
    assert got.shape == (B, H, rank)
    assert float(jnp.abs(got - want).max()) < 1e-5
    with pytest.raises(ValueError, match="128-lane"):
        latent_decode_attention(q[..., :200], pool[..., :200], tables, lengths,
                                layer=0, rank=rank, scale=scale, interpret=True)


def test_a_latent_pool_goes_through_the_host_hand_off(tiny):
    """The PD hand-off moves a family's page-shaped pool leaves by tree: a
    prefill engine extracts the prompt's latent pages, a decode engine
    attaches them and decodes; the tokens are those of one engine alone."""
    model, cfg, params, tokens = tiny
    prompt = list(map(int, tokens[:21]))
    make = lambda: PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=9, prefill_buckets=(16, 32), kv_transfer="host"), params=params)
    alone, pre, dec = make(), make(), make()
    try:
        want = alone.generate_sync(prompt, 6).token_ids
        handoff = pre.prefill_extract(prompt)
        assert set(handoff["kv"]) == {"latent"} == set(page_leaves(pre.pool))
        assert handoff["kv"]["latent"].shape == (cfg.cache_layers, 2, BS, cfg.latent_row)
        got = dec.attach_sequence(handoff, 6).result(60).token_ids
        assert got == want
        with pytest.raises(ValueError, match="leaves"):
            dec.attach_sequence({**handoff, "kv": {"k": handoff["kv"]["latent"]}}, 2).result(60)
    finally:
        for e in (alone, pre, dec):
            e.shutdown()
