"""models/laguna.py (full and sliding-window attention layers in one stack,
each kind with its own head count, rotation and cache: pages a token beside a
RING a sequence; a gate a head; softmax-routed experts beside a shared one; run
as `llama.decoder_trunk(runs=)` over the one layer) at a tiny size against the
plain reference (benchmarks/reference/laguna_reference.py), on LOGITS in
float32: the cache-less forward; prefill then decode through `forward_paged`
over a real `BlockPool`'s pages of both classes at lengths round the window's
edge and past two turns of the ring, with a padded bucket, stale rings and a
dead row; two sequences in one decode batch; a freed ring handed on; the
engine (rings with the slots, a reservation when either class is short, no
prefix hit, the PD hand-off, the speculative engine's refusal); the shares
adding up; wrong programs that must miss."""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import laguna as family
from benchmarks.reference import laguna_reference as reference
from ray_tpu.models import laguna, llama, moe
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine, page_leaves
from ray_tpu.serve.paged_kv import BlockPool, NoFreeBlocks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (a ring's rows against a full recompute, sorted rows against a dense
# weighted sum, a band's tiles against a masked square): measured 1e-6 to 4e-6
# of the logits' size. 3e-5 admits that; the wrong programs below miss by
# 0.003 or more.
TOL = 3e-5
BS, W = 16, 24


@pytest.fixture(scope="module")
def tiny():
    """8 layers, two periods of full-sliding-sliding-sliding (layer 0 dense),
    hidden 64, 4 query heads in a full layer and 6 in a window layer over 2
    key-value heads of 16 (groups of 2 and 3), a window of 24 (no power of
    two, no multiple of the block), YaRN over half of a full layer's lanes, 8
    experts of which the second half is held, 2 a token, a shared expert of
    48: the benchmark's CPU stand-in of the configuration."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "laguna-serve.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert cfg.kinds == ["lead", "win", "win", "win", "full", "win", "win", "win"]
    assert cfg.experts.experts_held == (4, 4) and cfg.experts.score_func == "softmax"
    assert (cfg.base.num_heads, cfg.window_heads, cfg.window) == (4, 6, W)
    assert cfg.rope_full == laguna.Rope(500000.0, 8, (16.0, 32, 32.0, 1.0), 1.2772588722239782)
    assert cfg.rope_window == laguna.Rope(10000.0, None, None, 1.0)
    params = jax.jit(lambda k: laguna.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 48))
    # norm weights other than one, so that one in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    for stack in set(cfg.kinds):
        params[stack] = {k: noisy(i, v) if k.endswith("_norm") else v
                         for i, (k, v) in enumerate(sorted(params[stack].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 140)
    return model, cfg, params, tokens


def _miss(got, want) -> float:
    """The benchmark's two measures (`serve_cell.check_against_reference`),
    the larger: rms error / rms logit and max error / max logit."""
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


@pytest.fixture(scope="module")
def wanted(tiny):
    model, cfg, params, tokens = tiny
    return np.asarray(reference.logits(params, tokens, model))


def test_the_cache_less_forward_gives_the_reference_s_logits(tiny, wanted):
    """Both kinds of attention (a band of 24 under 140 tokens), both
    rotations, the gate, a dense layer, experts with a share held and the
    shared expert."""
    model, cfg, params, tokens = tiny
    got = jax.jit(lambda t: laguna.forward(params, t, cfg))(jnp.asarray(tokens)[None])
    assert got.shape == (1, len(tokens), cfg.vocab_size)
    assert _miss(got[0], wanted) < TOL
    runs = laguna._runs(cfg, params, {"full": None, "win": None}, "cpu")[1]
    assert [(r.stack, r.first, r.count, r.cache_first) for r in runs] == [
        ("lead", 0, 1, 0), ("win", 0, 3, 0), ("full", 0, 1, 1), ("win", 3, 3, 3)]
    assert [r.attention.scope for r in runs] == ["attn_full/attn", "attn_win/attn"] * 2
    # a kind's head count is its weights': 4 and 6 heads of 16, one gate a head
    assert params["full"]["wq"].shape[-1] == 64 and params["win"]["wq"].shape[-1] == 96
    assert params["lead"]["w_head_gate"].shape == (1, 64, 4)
    assert params["win"]["w_head_gate"].shape == (6, 64, 6)
    assert "router_bias" not in params["win"] and "s_gate" in params["full"]


def test_a_kind_s_rotation_is_the_reference_s(tiny):
    """`Rope.rotate` against the reference's own: half of a full layer's lanes
    under YaRN's blended frequencies with cos and sin scaled, all of a window
    layer's under plain ones; the lanes that do not turn pass through."""
    model, cfg, params, tokens = tiny
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 50, 3, 16))
    at = jnp.arange(50, dtype=jnp.int32)[None]
    for rope, layer_type in ((cfg.rope_full, family.FULL), (cfg.rope_window, family.WINDOW)):
        want = reference._rope(x[0], model["rope_parameters"][layer_type], None)
        assert float(jnp.abs(rope.rotate(x, at)[0] - want).max()) < 1e-5
    assert np.array_equal(np.asarray(cfg.rope_full.rotate(x, at)[..., 8:]), np.asarray(x[..., 8:]))
    ramp = reference.inv_freq(model["rope_parameters"][family.FULL], 8)
    plain = 500000.0 ** (-np.arange(0, 8, 2) / 8)
    assert float(ramp[0]) == pytest.approx(plain[0]) and float(ramp[-1]) < plain[-1]


def _stale(pool):
    """Every page as a sequence that ended left it: nothing is zero."""
    return {**pool, **{k: v + 3.0 for k, v in page_leaves(pool).items()}}


def _step(cfg, params, use_kernel=None):
    def step(pool, tokens, tables, lengths, rings, head=None, fresh=False):
        return laguna.forward_paged(
            params, tokens, cfg, pool, tables, lengths, BS, head_rows=head, fresh=fresh,
            state_pages=rings, use_kernel=use_kernel and tokens.shape[1] == 1)
    return jax.jit(step, static_argnames=("fresh",))


@pytest.mark.parametrize("n_prompt, kernels", [
    (1, False), (W - 1, False), (W, False), (W + 1, False), (61, False), (W + 1, True)],
    ids=["1", "23", "24", "25", "61", "25-kernels"])
def test_prefill_then_decode_through_both_classes_of_page_matches_the_reference(
        tiny, wanted, n_prompt, kernels):
    """A prompt of 1, window - 1, window, window + 1 and 61 tokens (past two
    turns of the ring), padded to a bucket of 64 and prefilled into pages a
    real `BlockPool` handed out (one ring beside the token pages), then a
    token a step to position 139, five turns of the ring on: every row is the
    reference's. The bucket's padding is written to no ring (`head_rows` names
    the last live position), the pages and rings start STALE (a ring's rows
    are masked by the sequence's own length until it has come round), and row
    0 of the batch is a dead row on the garbage pages. With `kernels` the
    decode steps take the chip's path, interpreted: the paged kernel over the
    full layers' pages and, under its own name, over the ring's live rows."""
    model, cfg, params, tokens = tiny
    allocator = BlockPool(33, BS, num_sequences=4)
    allocator.alloc(2), allocator.alloc_sequence()       # someone else's
    blocks, ring = allocator.alloc(-(-len(tokens) // BS)), allocator.alloc_sequence()
    assert ring == 2 and allocator.sequences_in_use == 2
    table = np.zeros((2, 16), np.int32)
    table[1, :len(blocks)] = blocks
    rings = np.array([0, ring], np.int32)
    pool = _stale(laguna.init_kv_pool(cfg, 33, BS, num_sequences=4))
    assert pool["k"].shape == (2, 33, BS, 2 * 128)                       # the FULL layers alone
    assert pool["k_win"].shape == pool["v_win"].shape == (6, 4, W, 2 * 128)
    step = _step(cfg, params, use_kernel=True if kernels else None)
    padded = np.zeros((2, 64), np.int32)
    padded[1, :n_prompt] = tokens[:n_prompt]
    logits, pool = step(pool, padded, table, np.zeros(2, np.int32), rings,
                        head=np.array([0, n_prompt - 1], np.int32), fresh=True)
    rows = [logits[1, 0]]
    for t in range(n_prompt, len(tokens)):
        toks = np.array([[0], [tokens[t]]], np.int32)
        logits, pool = step(pool, toks, table, np.array([0, t], np.int32), rings)
        rows.append(logits[1, 0])
    assert _miss(np.stack(rows), wanted[n_prompt - 1:]) < TOL
    # the other sequence's ring was never touched
    assert float(jnp.abs(pool["k_win"][:, 1] - 3.0).max()) == 0.0
    assert float(jnp.abs(pool["v_win"][:, 1] - 3.0).max()) == 0.0
    counters = jax.tree.map(float, pool["counters"])
    assert counters["moe_rows"] > 0 and counters["moe_moved"] >= counters["moe_rows"]
    # one live sequence of 140 tokens: a ring of 24 live rows in 6 layers beside
    # 144 rows in blocks of the 2 full layers, against 8 layers of 144 rows
    assert counters["win_rings"] == 1 and counters["win_rows"] == W
    row_mb = 2 * 256 * 4 / 1e6
    assert counters["kv_held_mb"] == pytest.approx(row_mb * (2 * 144 + 6 * W))
    assert counters["kv_uniform_mb"] == pytest.approx(row_mb * 8 * 144)


def test_a_ring_holds_the_window_and_nothing_grows_with_the_length(tiny):
    """Contract (1): `ring_rows` names the position each row holds; after a
    prefill of 61 live tokens in a bucket of 64 the ring's rows ARE the last
    24 live positions' keys, position p at row p % 24, and the padding's rows
    are nowhere in it."""
    model, cfg, params, tokens = tiny
    held = np.asarray(llama.ring_rows(jnp.array([0, 1, 23, 24, 25, 61]), W))
    assert (held[0] < 0).all() and list(held[1][:2]) == [0, -23]
    assert list(held[2][:23]) == list(range(23)) and held[2][23] < 0
    assert sorted(held[3]) == list(range(24)) and sorted(held[4]) == list(range(1, 25))
    assert sorted(held[5]) == list(range(37, 61)) and all(p % W == r for r, p in enumerate(held[5]))
    pool = laguna.init_kv_pool(cfg, 9, BS, num_sequences=2)
    table, rings = np.arange(1, 9, dtype=np.int32)[None], np.array([1], np.int32)
    both = {}
    for n_live in (61, 64):   # the same tokens: 3 of them padding, or all live
        _, out = _step(cfg, params)(pool, tokens[None, :64], table, np.zeros(1, np.int32), rings,
                                    head=np.array([n_live - 1], np.int32), fresh=True)
        both[n_live] = np.asarray(out["k_win"][0, 1])
    # layer 0 of the rings sees the same input either way: rows of positions
    # 40..60 agree, rows 13..15 hold 37..39 where the other holds 61..63
    same = [r for r in range(W) if held[5][r] >= 40]
    assert np.array_equal(both[61][same], both[64][same]) and len(same) == 21
    assert not np.array_equal(both[61][13:16], both[64][13:16])


def test_a_bucket_s_padding_goes_to_no_expert_held_here(tiny):
    """41 live tokens in a bucket of 64: the pairs routed to the experts held
    (`moe_rows` over the sparse layers) are those of the 41-token prompt run
    alone, whatever the padding's one token would choose; without `head_rows`
    every row is live and the 23 more are counted."""
    model, cfg, params, tokens = tiny
    pool = laguna.init_kv_pool(cfg, 33, BS, num_sequences=3)
    table, rings = np.arange(1, 17, dtype=np.int32)[None], np.array([1], np.int32)
    step, at0 = _step(cfg, params), np.zeros(1, np.int32)
    rows = lambda out: int(out[1]["counters"]["moe_rows"])
    alone = rows(step(pool, tokens[None, :41], table, at0, rings, fresh=True))
    padded = np.zeros((1, 64), np.int32)
    padded[0, :41] = tokens[:41]
    head = np.array([40], np.int32)
    assert rows(step(pool, padded, table, at0, rings, head=head, fresh=True)) == alone
    # 7 sparse layers, 2 choices of 8 experts, half of them held
    assert 0.5 * 41 * 2 * 7 / 2 < alone < 1.5 * 41 * 2 * 7 / 2
    assert rows(step(pool, padded, table, at0, rings, fresh=True)) > alone


def test_more_than_one_token_over_a_ring_that_holds_a_past_is_refused(tiny):
    model, cfg, params, tokens = tiny
    pool = laguna.init_kv_pool(cfg, 9, BS, num_sequences=2)
    table, rings = np.arange(1, 9, dtype=np.int32)[None], np.array([1], np.int32)
    with pytest.raises(NotImplementedError, match="ring that holds a past"):
        _step(cfg, params)(pool, tokens[None, :4], table, np.array([30], np.int32), rings)
    with pytest.raises(ValueError, match="state_pages"):
        laguna.forward_paged(params, tokens[None, :4], cfg, pool, table,
                             np.zeros(1, np.int32), BS, fresh=True)


def test_a_padded_bucket_written_to_the_ring_misses(tiny, wanted):
    """The same prefill WITHOUT `head_rows`: the bucket's padding rows take
    the ring's rows of live positions, and the first decoded row is wrong."""
    model, cfg, params, tokens = tiny
    n = 41
    pool = laguna.init_kv_pool(cfg, 33, BS, num_sequences=3)
    table, rings = np.arange(1, 17, dtype=np.int32)[None], np.array([1], np.int32)
    step = _step(cfg, params)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n] = tokens[:n]
    _, pool = step(pool, padded, table, np.zeros(1, np.int32), rings, fresh=True)
    logits, _ = step(pool, tokens[None, n:n + 1], table, np.array([n], np.int32), rings)
    assert _miss(logits[0, 0], wanted[n]) > 0.003


def test_two_sequences_of_different_lengths_with_a_dead_row_between(tiny, wanted):
    """One decode batch of three rows: a sequence 11 tokens long (its ring
    has not come round), a dead row (table, length and ring zero), one 83
    long (three turns on); each was prefilled alone. Both give the
    reference's rows step after step for 20 steps, across the shorter one's
    first turn, and the ring handed to nobody is as it was."""
    model, cfg, params, tokens = tiny
    allocator = BlockPool(49, BS, num_sequences=4)
    pool = _stale(laguna.init_kv_pool(cfg, 49, BS, num_sequences=4))
    step = _step(cfg, params)
    table, rings, lens = np.zeros((3, 16), np.int32), np.zeros(3, np.int32), (11, 0, 83)
    for row in (0, 2):
        blocks = allocator.alloc(-(-len(tokens) // BS))
        table[row, :len(blocks)], rings[row] = blocks, allocator.alloc_sequence()
        padded = np.zeros((1, 128), np.int32)
        padded[0, :lens[row]] = tokens[:lens[row]]
        _, pool = step(pool, padded, table[row:row + 1], np.zeros(1, np.int32),
                       rings[row:row + 1], head=np.array([lens[row] - 1], np.int32), fresh=True)
    before = jax.tree.map(np.asarray, page_leaves(pool))
    got = {0: [], 2: []}
    for s in range(20):
        at = np.array([lens[0] + s, 0, lens[2] + s], np.int32)
        toks = np.array([[tokens[at[0]]], [7], [tokens[at[2]]]], np.int32)
        logits, pool = step(pool, toks, table, at, rings)
        for row in (0, 2):
            got[row].append(logits[row, 0])
    for row in (0, 2):
        assert _miss(np.stack(got[row]), wanted[lens[row]:lens[row] + 20]) < TOL
    for name in ("k_win", "v_win"):
        assert np.array_equal(np.asarray(pool[name][:, 3]), before[name][:, 3])
    assert int(pool["counters"]["win_rings"]) == 2
    assert int(pool["counters"]["win_rows"]) == W + W   # 31 and 103 tokens: both past the window


def _engine(cfg, params, slots=3, **kw):
    return PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=slots, max_seq_len=256, block_size=BS,
        prefill_buckets=(64, 128), **kw), params=params)


def test_a_freed_ring_is_handed_on_with_garbage_in_it(tiny, wanted):
    """Through the engine: 3 slots, so 3 rings; five requests one after
    another reuse them, each after another sequence left its rows there, and
    each gives the reference's greedy tokens. The rings come back."""
    model, cfg, params, tokens = tiny
    eng = _engine(cfg, params)
    try:
        assert eng.pool["k_win"].shape[1] == eng.pool["v_win"].shape[1] == 4   # slots + 1
        assert eng.tables.shape == (3, 256 // BS + 1)
        eng.pool = _stale(eng.pool)
        want = [int(t) for t in wanted.argmax(-1)]
        for n in (33, 100, 5, 61, 24):
            out = eng.generate_sync([int(t) for t in tokens[:n]], 1, timeout=300)
            assert out.token_ids == want[n - 1:n]
        # 30 new tokens, past a turn of the ring, are the reference's while its
        # own tokens are fed back
        n = 20
        out = eng.generate_sync([int(t) for t in tokens[:n]], 30, timeout=300)
        seq = np.concatenate([tokens[:n], out.token_ids[:-1]])
        again = np.asarray(reference.logits(params, seq, model))[n - 1:].argmax(-1)
        assert out.token_ids == [int(t) for t in again]
        stats = eng.stats()
        assert stats["state_pages"] == 3 and stats["state_pages_used"] == 0
        assert stats["allocated_blocks"] == 0 and stats["prefix_cache"] is False
        assert sorted(eng.allocator._free_sequences) == [1, 2, 3]
    finally:
        eng.shutdown()


def test_the_engine_takes_no_prefix_hit_and_its_records_carry_both_classes(tiny, wanted):
    """Contract (4) and (6): two requests that share a long prefix (6 whole
    blocks) both run their whole prompt, since the suffix's first queries
    would need the window's rows at the prefix's end; the `admit` records
    carry the blocks reserved and the ring, the `decode` records
    `state_pages_used` (rings in use) beside `blocks` and the pool's own counters."""
    from ray_tpu.util import timeline

    model, cfg, params, tokens = tiny
    timeline.clear()
    eng = _engine(cfg, params, slots=2)
    try:
        shared = [int(t) for t in tokens[:100]]
        want = [int(t) for t in wanted.argmax(-1)]
        futs = [eng.generate(shared, 4), eng.generate(shared[:96] + [5, 6, 7, 8], 4)]
        first, second = (f.result(300) for f in futs)
        assert first.token_ids[0] == want[99]
        other = np.asarray(reference.logits(
            params, np.array(shared[:96] + [5, 6, 7, 8]), model))[-1].argmax()
        assert second.token_ids[0] == int(other)
        stats = eng.stats()
        assert stats["prefix_queries"] == stats["prefix_hits"] == stats["cached_blocks"] == 0
        assert stats["state_pages_used"] == 0 and eng.slot_state_page == [0, 0]
    finally:
        eng.shutdown()
    records = [e for e in timeline.local_events() if e[0] == "span" and e[2] == "engine"]
    admits = [e[7] for e in records if e[3] == "admit"]
    assert [a["cached"] for a in admits] == [0, 0] and {a["reads"] for a in admits} == {"own_rows"}
    assert [a["blocks"] for a in admits] == [7, 7] and {a["state_page"] for a in admits} == {1, 2}
    decodes = [e[7] for e in records if e[3] == "decode"]
    assert decodes and max(d["state_pages_used"] for d in decodes) == 2
    counted = [d for d in decodes if "win_rings" in d]
    assert counted and all(d["win_rows"] == W * d["win_rings"] for d in counted)
    assert all(0 < d["kv_held_mb"] < d["kv_uniform_mb"] for d in counted)


def test_admission_reserves_both_classes_and_requeues_when_either_is_short(tiny, wanted):
    """Contract (2) and (3): with every ring held elsewhere a request is
    requeued and the blocks it took go back; with the rings free and the
    blocks short it is requeued too; it is admitted when both are there, and
    release frees both."""
    from ray_tpu.util import timeline

    model, cfg, params, tokens = tiny
    timeline.clear()
    eng = _engine(cfg, params, slots=2, num_blocks=12)
    try:
        prompt, want = [int(t) for t in tokens[:40]], int(wanted[39].argmax())
        rings = [eng.allocator.alloc_sequence(), eng.allocator.alloc_sequence()]
        fut = eng.generate(prompt, 2)
        time.sleep(0.3)
        assert not fut.done() and eng.stats()["allocated_blocks"] == 0
        eng.allocator.free_sequence(rings.pop())
        assert fut.result(300).token_ids[0] == want
        blocks = eng.allocator.alloc(9)              # 2 of 11 left: 3 are needed
        fut = eng.generate(prompt, 2)
        time.sleep(0.3)
        assert not fut.done() and eng.stats()["state_pages_used"] == 1   # the one held above
        eng.allocator.free(blocks)
        assert fut.result(300).token_ids[0] == want
        eng.allocator.free_sequence(rings.pop())
        stats = eng.stats()
        assert stats["allocated_blocks"] == 0 and stats["state_pages_used"] == 0
    finally:
        eng.shutdown()
    outcomes = [e[7]["outcome"] for e in timeline.local_events()
                if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]
    assert outcomes.count("admitted") == 2 and outcomes.count("requeued") >= 2


def test_the_pd_hand_off_moves_a_sequence_as_its_pages_of_both_classes(tiny, wanted):
    """Contract (5): `prefill_extract` on one engine, `attach_sequence` on
    another whose free pages are other ones: the payload's token leaves carry
    the prompt's blocks, its ring leaves ONE ring, and the decode side
    continues with the reference's tokens past a turn of the ring."""
    model, cfg, params, tokens = tiny
    a, b = _engine(cfg, params, slots=2), _engine(cfg, params, slots=2)
    try:
        n = 61
        b.pool = _stale(b.pool)
        held = b.allocator.alloc(5), b.allocator.alloc_sequence()   # b's pages differ
        handoff = a.prefill_extract([int(t) for t in tokens[:n]], timeout=300)
        kv = handoff["kv"]
        assert kv["k"].shape[1] == kv["v"].shape[1] == -(-n // BS) == handoff["n_prefill_blocks"]
        assert kv["k_win"].shape[:3] == kv["v_win"].shape[:3] == (6, 1, W)
        assert a.stats()["state_pages_used"] == 0 and a.stats()["allocated_blocks"] == 0
        out = b.attach_sequence(handoff, 30).result(300)
        seq = np.concatenate([tokens[:n], out.token_ids[:-1]])
        want = np.asarray(reference.logits(params, seq, model))[n - 1:].argmax(-1)
        assert out.token_ids == [int(t) for t in want]
        assert b.stats()["state_pages_used"] == 1    # the one held above
        b.allocator.free(held[0]), b.allocator.free_sequence(held[1])
        # a payload that lacks a leaf is refused by name
        with pytest.raises(ValueError, match="k_win"):
            b.attach_sequence({**handoff, "kv": {k: kv[k] for k in ("k", "v")}}, 2).result(300)
    finally:
        a.shutdown(), b.shutdown()


def test_the_speculative_engine_refuses_a_pool_with_rings(tiny):
    from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine

    model, cfg, params, tokens = tiny
    draft = dataclasses.replace(llama.LlamaConfig.tiny(), vocab_size=cfg.vocab_size)
    with pytest.raises(ValueError, match=r"\['k_win', 'v_win'\].*ONE page a sequence .* a window layer's ring"):
        SpecDecodeLLMEngine(SpecDecodeConfig(
            model_config=cfg, draft_model_config=draft, max_batch_size=2, max_seq_len=64,
            block_size=BS, prefill_buckets=(32,)), params=params)


def test_the_allocator_counts_rings_as_its_second_class():
    pool = BlockPool(9, BS, num_sequences=3)
    assert [pool.alloc_sequence(), pool.alloc_sequence()] == [1, 2]
    stats = pool.stats()
    assert stats["state_pages"] == 2 and stats["state_pages_used"] == 2
    with pytest.raises(NoFreeBlocks):
        pool.alloc_sequence()
    pool.free_sequence(1)
    assert pool.stats()["state_pages_used"] == 1 and pool.alloc_sequence() == 1


def test_the_shares_parts_sum_to_the_uncut_layer(tiny):
    """A sparse layer's output over both shares of 4 (the shared expert, and
    the attention before it, counted once) is the uncut layer's: what
    `experts_held` leaves out is exactly what the other chip adds."""
    model, cfg, params, tokens = tiny
    whole_cfg = dataclasses.replace(cfg, experts=dataclasses.replace(cfg.experts, experts_held=None))
    full = jax.jit(lambda k: laguna.init(whole_cfg, k))(jax.random.PRNGKey(3))
    assert full["win"]["e_gate"].shape[1:] == (8, 64, 32)
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64))
    layer = jax.tree.map(lambda a: a[1], full["win"])
    want, _ = moe.moe_mlp(y, layer, whole_cfg.experts)
    shared = (jax.nn.silu(y[0] @ layer["s_gate"]) * (y[0] @ layer["s_up"])) @ layer["s_down"]
    parts = []
    for first in (0, 4):
        held = dataclasses.replace(cfg.experts, experts_held=(first, 4))
        part = {**layer, **{k: layer[k][first:first + 4] for k in ("e_gate", "e_up", "e_down")}}
        parts.append(moe.moe_mlp(y, part, held)[0])
    got = parts[0] + parts[1] - shared[None]
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())
    # and the reference's two shares, routed by its own softmax, add up the same way
    w = {k: v.astype(jnp.float32) for k, v in layer.items()}
    uncut = {**model, "num_experts": 8, "share": {**model["share"], "rank": 0}}
    ref_parts = [reference.expert_layer(
        y[0], {**w, **{k: w[k][first:first + 4] for k in ("e_gate", "e_up", "e_down")}},
        {**model, "share": {**model["share"], "rank": first // 4}}, first) for first in (0, 4)]
    ref_whole = reference.expert_layer(y[0], w, uncut, 0)
    assert float(jnp.abs(ref_parts[0] + ref_parts[1] - shared - ref_whole).max()) < 1e-5
    assert float(jnp.abs(ref_whole - want[0]).max()) < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_a_wrong_program_misses(tiny, wanted, wrong):
    """Each departure the cell's `would_fail` lists, at float32 where nothing
    but the departure differs: the window ignored, a window of 23 and of 25
    (the configuration's 511 and 513), the two kinds' rotations swapped, cos
    and sin unscaled, all lanes of a full layer turned, the gate dropped, a
    window layer at the full layers' head count, the shared expert left out,
    sigmoid scores for softmax, no head norms."""
    model, cfg, params, tokens = tiny
    bad = np.asarray(reference.logits(params, tokens[:120], model, wrong=wrong))
    assert _miss(bad, wanted[:120]) > 0.003
