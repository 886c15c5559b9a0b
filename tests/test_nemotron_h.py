"""models/nemotron_h.py (blocks of ONE sub-layer each: Mamba-2 mixers whose
state is a page a SEQUENCE in the paged pool, grouped-query attention without
rotation, un-gated relu^2 experts beside a shared one, run as
`llama.decoder_trunk(runs=)` over the one layer) at a tiny size against the
plain reference (benchmarks/reference/nemotron_h_reference.py), on LOGITS in
float32: the cache-less forward; prefill then decode through `forward_paged`
over a real `BlockPool`'s pages of both classes, with a padded bucket; two
sequences and a dead row in one decode batch; a freed state page handed on
with garbage in it; the engine (state pages with the slots, no prefix hit,
the PD hand-off, the speculative engine's refusal); the shares adding up; the
chunked scan against a scan over tokens; wrong programs that must miss."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import nemotron_h as family
from benchmarks.reference import nemotron_h_reference as reference
from ray_tpu.models import llama, nemotron_h
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine, page_leaves
from ray_tpu.serve.paged_kv import BlockPool, NoFreeBlocks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (a chunked scan against a scan over tokens, a page's state against a
# full recompute, sorted rows against a dense weighted sum): measured 1e-6 to
# 4e-6 of the logits' size. 3e-5 admits that; the wrong programs below miss
# by 0.01 or more.
TOL = 3e-5
BS = 16


@pytest.fixture(scope="module")
def tiny():
    """8 blocks `MEM*EME*` (every kind, runs of one), hidden 64, 4 Mamba
    heads of 16 with a 16-wide state over 2 groups, 4 query over 2 key-value
    heads of 16, 8 experts of which the second half is held, 2 a token, a
    shared expert of 48: the benchmark's CPU stand-in of the configuration."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "nemotron_h-serve.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert cfg.kinds == ["mamba", "experts", "mamba", "attn", "experts", "mamba", "experts", "attn"]
    assert cfg.experts.experts_held == (4, 4) and cfg.experts.activation == "relu2"
    params = jax.jit(lambda k: nemotron_h.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 45))
    # norm weights and D other than one, so that one in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    for stack in set(cfg.kinds):
        params[stack] = {k: noisy(i, v) if k.endswith("_norm") or k == "D" else v
                         for i, (k, v) in enumerate(sorted(params[stack].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 300)
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
    """Every kind of block, un-gated experts, a shared expert and a share
    held, 300 tokens: three chunks of the scan, the last one padded."""
    model, cfg, params, tokens = tiny
    got = jax.jit(lambda t: nemotron_h.forward(params, t, cfg))(jnp.asarray(tokens)[None])
    assert got.shape == (1, len(tokens), cfg.vocab_size)
    assert _miss(got[0], wanted) < TOL
    runs = nemotron_h._runs(cfg, params, {"attn": "a", "mamba": "m"}, "cpu")[1]
    assert [(r.stack, r.first, r.count, r.cache_first) for r in runs] == [
        ("mamba", 0, 1, 0), ("experts", 0, 1, 0), ("mamba", 1, 1, 1), ("attn", 0, 1, 0),
        ("experts", 1, 1, 0), ("mamba", 2, 1, 2), ("experts", 2, 1, 0), ("attn", 1, 1, 1)]
    # a kind's other strategy is None: the sub-layer is not run, its norm not held
    assert all((r.attention is None) != (r.mlp is None) for r in runs)
    assert "mlp_norm" not in params["mamba"] and "attn_norm" not in params["experts"]
    assert "e_gate" not in params["experts"] and "s_gate" not in params["experts"]


def _token_scan(x, dt, A, B, C):
    b, S, H, P = x.shape
    rep = H // B.shape[2]
    Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def step(h, t):
        xt, dtt, Bt, Ct = t
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return h, (h * Ct[:, :, None, :]).sum(-1)

    h, ys = jax.lax.scan(step, jnp.zeros((b, H, P, B.shape[-1])),
                         tuple(t.swapaxes(0, 1) for t in (x, dt, Bh, Ch)))
    return ys.swapaxes(0, 1), h


@pytest.mark.parametrize("S", [1, 127, 128, 300])
def test_the_chunked_scan_is_the_scan_over_tokens(S):
    """`ssd_scan` in chunks of 128 against one token at a time: under a
    chunk, one whole chunk, chunks and a padded tail; decays of e^-3 a step
    down to ones that forget nothing; and a carried-in state."""
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    x = jax.random.normal(ks[0], (2, S, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, S, 4)) - 2)
    A = -jnp.exp(jax.random.uniform(ks[2], (4,), minval=0, maxval=2.7))
    B, C = jax.random.normal(ks[3], (2, S, 2, 16)), jax.random.normal(ks[4], (2, S, 2, 16))
    want_y, want_h = _token_scan(x, dt, A, B, C)
    got_y, got_h = nemotron_h.ssd_scan(x, dt, A, B, C, None, 128)
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(got_y - want_y).max()) < 1e-5 * scale
    assert float(jnp.abs(got_h - want_h).max()) < 1e-5 * float(jnp.abs(want_h).max())
    # the second half from the state the first half left is the whole
    half = S // 2
    if half:
        cut = lambda t, lo, hi: t[:, lo:hi]
        _, h1 = nemotron_h.ssd_scan(*(cut(t, 0, half) for t in (x, dt)), A,
                                    cut(B, 0, half), cut(C, 0, half), None, 128)
        y2, h2 = nemotron_h.ssd_scan(*(cut(t, half, S) for t in (x, dt)), A,
                                     cut(B, half, S), cut(C, half, S), h1, 128)
        assert float(jnp.abs(y2 - want_y[:, half:]).max()) < 1e-5 * scale
        assert float(jnp.abs(h2 - want_h).max()) < 1e-5 * float(jnp.abs(want_h).max())
    # dt = 0 past a live length: the state stands still there
    live = max(S - 5, 1)
    masked = jnp.where(jnp.arange(S)[None, :, None] < live, dt, 0.0)
    _, h_live = nemotron_h.ssd_scan(x, masked, A, B, C, None, 128)
    _, h_cut = _token_scan(x[:, :live], dt[:, :live], A, B[:, :live], C[:, :live])
    assert float(jnp.abs(h_live - h_cut).max()) < 1e-5 * float(jnp.abs(h_cut).max())


def _stale(pool):
    """Every page as a sequence that ended left it: nothing is zero."""
    return {**pool, **{k: v + 3.0 for k, v in page_leaves(pool).items()}}


def _step(cfg, params, use_kernel=None):
    def step(pool, tokens, tables, lengths, pages, head=None, fresh=False):
        return nemotron_h.forward_paged(
            params, tokens, cfg, pool, tables, lengths, BS, head_rows=head, fresh=fresh,
            state_pages=pages, use_kernel=use_kernel and tokens.shape[1] == 1)
    return jax.jit(step, static_argnames=("fresh",))


@pytest.mark.parametrize("n_prompt, fresh, kernels", [
    (37, True, False), (131, True, False), (131, False, False), (250, True, False),
    (131, True, True)], ids=["37", "131", "131-table", "250", "131-kernels"])
def test_prefill_then_decode_through_the_pages_matches_the_reference(tiny, wanted, n_prompt, fresh,
                                                                     kernels):
    """A prompt whose length is no multiple of 128 or of 16, padded to a
    bucket of 256 and prefilled into pages a real `BlockPool` handed out (one
    state page beside the token pages), then a token a step to position 299:
    every row is the reference's. The bucket's padding advances no state
    (`head_rows` names the last live position), the pages start STALE (a
    fresh prefill reads none; the table program masks what it read at length
    0), and row 0 of the batch is a dead row on the garbage pages. With
    `kernels` the decode steps take the chip's path, interpreted: the paged
    attention kernel and the state's in-place update (`ops/ssm_state.py`)."""
    model, cfg, params, tokens = tiny
    allocator = BlockPool(33, BS, num_sequences=4)
    allocator.alloc(2), allocator.alloc_sequence()       # someone else's
    blocks, page = allocator.alloc(-(-len(tokens) // BS)), allocator.alloc_sequence()
    assert page == 2 and allocator.sequences_in_use == 2
    table = np.zeros((2, 24), np.int32)
    table[1, :len(blocks)] = blocks
    pages = np.array([0, page], np.int32)
    pool = _stale(nemotron_h.init_kv_pool(cfg, 33, BS, num_sequences=4))
    assert pool["ssm"].shape == (3, 4, 4, 16, 16) and pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (3, 4, 3 * (64 + 2 * 2 * 16))
    assert pool["k"].shape == (2, 33, BS, 2 * 128)
    step = _step(cfg, params, use_kernel=True if kernels else None)
    padded = np.zeros((2, 256), np.int32)
    padded[1, :n_prompt] = tokens[:n_prompt]
    logits, pool = step(pool, padded, table, np.zeros(2, np.int32), pages,
                        head=np.array([0, n_prompt - 1], np.int32), fresh=fresh)
    rows = [logits[1, 0]]
    for t in range(n_prompt, len(tokens)):
        toks = np.array([[0], [tokens[t]]], np.int32)
        logits, pool = step(pool, toks, table, np.array([0, t], np.int32), pages)
        rows.append(logits[1, 0])
    assert _miss(np.stack(rows), wanted[n_prompt - 1:]) < TOL
    # the other sequence's state page was never touched
    assert float(jnp.abs(pool["ssm"][:, 1] - 3.0).max()) == 0.0
    assert float(jnp.abs(pool["conv"][:, 1] - 3.0).max()) == 0.0
    counters = jax.tree.map(int, pool["counters"])
    assert counters["moe_rows"] > 0 and counters["moe_moved"] >= counters["moe_rows"]


def test_the_state_kernel_steps_each_sequence_s_page_in_place():
    """`ops/ssm_state.py` alone, interpreted: of a pool of 3 layers and 5
    pages, layer 1's pages 2 and 4 advance by `decay h + dx B^T` and give `y =
    h C`; two dead rows write the garbage page 0; a decay of 0 takes nothing
    of a page that holds infinities; every other page of every layer is
    untouched, to the bit."""
    from ray_tpu.ops.ssm_state import ssm_state_step

    L, NS, H, P, N, B = 3, 5, 8, 16, 128, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    ssm = jax.random.normal(ks[0], (L, NS, H, P, N)).at[1, 4].set(jnp.inf)
    pages = jnp.array([2, 0, 4, 0], jnp.int32)
    decay = jax.random.uniform(ks[1], (B, H)).at[2].set(0.0)
    dx, b, c = (jax.random.normal(k, shape) for k, shape in
                zip(ks[2:5], ((B, H, P), (B, H, N), (B, H, N))))
    out, y = jax.jit(lambda s, layer: ssm_state_step(
        s, layer, pages, decay, dx, b, c, interpret=True))(ssm, jnp.int32(1))
    h = jnp.where(decay[..., None, None] > 0, decay[..., None, None] * ssm[1, pages], 0.0)
    h = h + dx[..., None] * b[:, :, None, :]
    for row, page in ((0, 2), (2, 4)):
        assert float(jnp.abs(out[1, page] - h[row]).max()) < 1e-5
        assert float(jnp.abs(y[row] - (h[row] * c[row][:, None, :]).sum(-1)).max()) < 1e-4
    assert bool(jnp.isfinite(out[1, 4]).all())
    for layer, page in ((0, slice(None)), (2, slice(None)), (1, 1), (1, 3)):
        assert np.array_equal(np.asarray(out[layer, page]), np.asarray(ssm[layer, page]))


def test_a_padded_bucket_that_advances_the_state_misses(tiny, wanted):
    """The same prefill WITHOUT `head_rows`: the bucket's padding runs on
    through h and the convolution's rows, and the first decoded row is wrong."""
    model, cfg, params, tokens = tiny
    n = 131
    pool = nemotron_h.init_kv_pool(cfg, 33, BS, num_sequences=3)
    table = np.arange(1, 25, dtype=np.int32)[None]
    pages = np.array([1], np.int32)
    step = _step(cfg, params)
    padded = np.zeros((1, 256), np.int32)
    padded[0, :n] = tokens[:n]
    _, pool = step(pool, padded, table, np.zeros(1, np.int32), pages, fresh=True)
    logits, _ = step(pool, tokens[None, n:n + 1], table, np.array([n], np.int32), pages)
    assert _miss(logits[0, 0], wanted[n]) > 0.01


def test_two_sequences_of_different_lengths_with_a_dead_row_between(tiny, wanted):
    """One decode batch of three rows: a sequence 41 tokens long, a dead row
    (table, length and state page zero), one 163 long; each was prefilled
    alone. Both give the reference's rows step after step, and what the dead
    row writes lands on page 0 of both classes alone."""
    model, cfg, params, tokens = tiny
    allocator = BlockPool(49, BS, num_sequences=4)
    pool = _stale(nemotron_h.init_kv_pool(cfg, 49, BS, num_sequences=4))
    step = _step(cfg, params)
    table, pages, lens = np.zeros((3, 24), np.int32), np.zeros(3, np.int32), (41, 0, 163)
    for row in (0, 2):
        blocks = allocator.alloc(-(-len(tokens) // BS))
        table[row, :len(blocks)], pages[row] = blocks, allocator.alloc_sequence()
        padded = np.zeros((1, 256), np.int32)
        padded[0, :lens[row]] = tokens[:lens[row]]
        _, pool = step(pool, padded, table[row:row + 1], np.zeros(1, np.int32),
                       pages[row:row + 1], head=np.array([lens[row] - 1], np.int32), fresh=True)
    before = jax.tree.map(np.asarray, page_leaves(pool))
    got = {0: [], 2: []}
    for s in range(6):
        at = np.array([lens[0] + s, 0, lens[2] + s], np.int32)
        toks = np.array([[tokens[at[0]]], [7], [tokens[at[2]]]], np.int32)
        logits, pool = step(pool, toks, table, at, pages)
        for row in (0, 2):
            got[row].append(logits[row, 0])
    for row in (0, 2):
        assert _miss(np.stack(got[row]), wanted[lens[row]:lens[row] + 6]) < TOL
    # state page 3 was handed to nobody: it is as it was
    for name in ("ssm", "conv"):
        assert np.array_equal(np.asarray(pool[name][:, 3]), before[name][:, 3])


def test_a_freed_state_page_is_handed_on_with_garbage_in_it(tiny, wanted):
    """Through the engine: 3 slots, so 3 state pages; five requests one after
    another reuse them, each after another sequence left its state there, and
    each gives the reference's greedy tokens. The pages come back."""
    model, cfg, params, tokens = tiny
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=3, max_seq_len=512, block_size=BS,
        prefill_buckets=(64, 256)), params=params)
    try:
        assert eng.pool["ssm"].shape[1] == eng.pool["conv"].shape[1] == 4   # slots + 1
        assert eng.tables.shape == (3, 512 // BS + 1)
        assert eng.kv_memory_bytes() == sum(
            leaf.nbytes for name, leaf in eng.pool.items() if name != "counters")
        assert eng.kv_memory_bytes() > 2 * eng.pool["k"].nbytes
        eng.pool = _stale(eng.pool)
        want = [int(t) for t in wanted.argmax(-1)]
        for n in (33, 150, 61, 200, 45):
            out = eng.generate_sync([int(t) for t in tokens[:n]], 1, timeout=300)
            assert out.token_ids == want[n - 1:n]
        # 8 new tokens are the reference's while its own tokens are fed back
        n = 131
        out = eng.generate_sync([int(t) for t in tokens[:n]], 8, timeout=300)
        seq = np.concatenate([tokens[:n], out.token_ids[:-1]])
        again = np.asarray(reference.logits(params, seq, model))[n - 1:].argmax(-1)
        assert out.token_ids == [int(t) for t in again]
        stats = eng.stats()
        assert stats["state_pages"] == 3 and stats["state_pages_used"] == 0
        assert stats["allocated_blocks"] == 0 and stats["prefix_cache"] is False
        assert sorted(eng.allocator._free_sequences) == [1, 2, 3]
    finally:
        eng.shutdown()


def test_the_engine_takes_no_prefix_hit_and_counts_its_state_pages(tiny, wanted):
    """Two requests that share a long prefix (12 whole blocks) both run their
    whole prompt: no prefix is looked up or registered over a pool with pages
    a sequence's, both give the reference's tokens, the records carry
    `state_pages_used`, and the pages return with the slots."""
    from ray_tpu.util import timeline

    model, cfg, params, tokens = tiny
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=512, block_size=BS,
        prefill_buckets=(256,)), params=params)
    try:
        shared = [int(t) for t in tokens[:200]]
        want = [int(t) for t in wanted.argmax(-1)]
        futs = [eng.generate(shared, 4), eng.generate(shared[:196] + [5, 6, 7, 8], 4)]
        first, second = (f.result(300) for f in futs)
        assert first.token_ids[0] == want[199]
        other = np.asarray(reference.logits(
            params, np.array(shared[:196] + [5, 6, 7, 8]), model))[-1].argmax()
        assert second.token_ids[0] == int(other)
        stats = eng.stats()
        assert stats["prefix_queries"] == stats["prefix_hits"] == stats["cached_blocks"] == 0
        assert stats["state_pages_used"] == 0 and eng.slot_state_page == [0, 0]
    finally:
        eng.shutdown()
    records = [e for e in timeline.local_events() if e[0] == "span" and e[2] == "engine"]
    admits = [e[7] for e in records if e[3] == "admit"]
    assert [a["cached"] for a in admits] == [0, 0] and {a["reads"] for a in admits} == {"own_rows"}
    used = [e[7]["state_pages_used"] for e in records if e[3] == "decode"]
    assert used and max(used) == 2 and all("moe_rows" in e[7] or not e[7]["ahead"]
                                           for e in records if e[3] == "decode")


def test_the_pd_hand_off_moves_a_sequence_as_its_pages_of_both_classes(tiny, wanted):
    """`prefill_extract` on one engine, `attach_sequence` on another whose
    free pages are other ones: the payload's token leaves carry the prompt's
    blocks, its sequence leaves ONE page, and the decode side continues with
    the reference's tokens."""
    model, cfg, params, tokens = tiny
    conf = PagedLLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=512,
                          block_size=BS, prefill_buckets=(256,))
    a, b = PagedLLMEngine(conf, params=params), PagedLLMEngine(conf, params=params)
    try:
        n = 131
        b.pool = _stale(b.pool)
        held = b.allocator.alloc(5), b.allocator.alloc_sequence()   # b's pages differ
        handoff = a.prefill_extract([int(t) for t in tokens[:n]], timeout=300)
        kv = handoff["kv"]
        assert kv["k"].shape[1] == kv["v"].shape[1] == -(-n // BS) == handoff["n_prefill_blocks"]
        assert kv["ssm"].shape[:2] == (3, 1) and kv["conv"].shape[:2] == (3, 1)
        assert a.stats()["state_pages_used"] == 0 and a.stats()["allocated_blocks"] == 0
        out = b.attach_sequence(handoff, 6).result(300)
        seq = np.concatenate([tokens[:n], out.token_ids[:-1]])
        want = np.asarray(reference.logits(params, seq, model))[n - 1:].argmax(-1)
        assert out.token_ids == [int(t) for t in want]
        assert b.stats()["state_pages_used"] == 1    # the one held above
        b.allocator.free(held[0]), b.allocator.free_sequence(held[1])
        # a payload that lacks a leaf is refused by name
        with pytest.raises(ValueError, match="ssm"):
            b.attach_sequence({**handoff, "kv": {k: kv[k] for k in ("k", "v")}}, 2).result(300)
    finally:
        a.shutdown(), b.shutdown()


def test_the_speculative_engine_refuses_a_pool_with_pages_a_sequence_s(tiny):
    from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine

    model, cfg, params, tokens = tiny
    draft = dataclasses.replace(llama.LlamaConfig.tiny(), vocab_size=cfg.vocab_size)
    with pytest.raises(ValueError, match=r"\['conv', 'ssm'\].*ONE page a sequence"):
        SpecDecodeLLMEngine(SpecDecodeConfig(
            model_config=cfg, draft_model_config=draft, max_batch_size=2, max_seq_len=64,
            block_size=BS, prefill_buckets=(32,)), params=params)


def test_the_allocator_s_second_class_of_page():
    pool = BlockPool(9, BS, num_sequences=3)
    assert [pool.alloc_sequence(), pool.alloc_sequence()] == [1, 2]
    assert pool.sequences_in_use == 2 and pool.stats()["state_pages"] == 2
    with pytest.raises(NoFreeBlocks):
        pool.alloc_sequence()
    pool.free_sequence(1)
    assert pool.alloc_sequence() == 1 and pool.in_use == 0
    assert BlockPool(9, BS).stats()["state_pages"] == 0
    with pytest.raises(NoFreeBlocks):
        BlockPool(9, BS).alloc_sequence()


def test_the_shares_parts_sum_to_the_uncut_layer(tiny):
    """An expert block's output over both shares of 4 (the shared expert
    counted once) is the uncut block's: what `experts_held` leaves out is
    exactly what the other chip adds."""
    model, cfg, params, tokens = tiny
    whole_cfg = dataclasses.replace(cfg, experts=dataclasses.replace(cfg.experts, experts_held=None))
    full = jax.jit(lambda k: nemotron_h.init(whole_cfg, k))(jax.random.PRNGKey(3))
    assert full["experts"]["e_up_t"].shape[1:] == (8, 32, 64)
    from ray_tpu.models import moe

    y = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64))
    layer = jax.tree.map(lambda a: a[1], full["experts"])
    want, _ = moe.moe_mlp(y, layer, whole_cfg.experts)
    shared = moe.ACTIVATIONS["relu2"](y[0] @ layer["s_up"]) @ layer["s_down"]
    parts = []
    for first in (0, 4):
        held = dataclasses.replace(cfg.experts, experts_held=(first, 4))
        part = {**layer, "e_up_t": layer["e_up_t"][first:first + 4],
                "e_down": layer["e_down"][first:first + 4]}
        parts.append(moe.moe_mlp(y, part, held)[0])
    got = parts[0] + parts[1] - shared[None]
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(jnp.abs(want).max())


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_a_wrong_program_misses(tiny, wanted, wrong):
    """Each departure the cell's `would_fail` lists, at float32 where nothing
    but the departure differs: one tap dropped, B and C swapped, silu for
    relu^2, the shared expert left out, the gate norm over all of d_inner,
    rotary applied."""
    model, cfg, params, tokens = tiny
    bad = np.asarray(reference.logits(params, tokens[:120], model, wrong=wrong))
    assert _miss(bad, wanted[:120]) > 0.01


def test_a_bfloat16_state_drifts_from_the_float32_one(tiny, wanted):
    """`ssm_dtype` bfloat16: the running sum rounded at every decode step
    reads further from the reference than the float32 state by far."""
    model, cfg, params, tokens = tiny
    n, miss = 37, {}
    for dtype in (jnp.float32, jnp.bfloat16):
        c = dataclasses.replace(cfg, ssm_dtype=dtype)
        pool = nemotron_h.init_kv_pool(c, 33, BS, num_sequences=2)
        assert pool["ssm"].dtype == dtype
        step = _step(c, params)
        table, pages = np.arange(1, 25, dtype=np.int32)[None], np.array([1], np.int32)
        padded = np.zeros((1, 64), np.int32)
        padded[0, :n] = tokens[:n]
        _, pool = step(pool, padded, table, np.zeros(1, np.int32), pages,
                       head=np.array([n - 1], np.int32), fresh=True)
        rows = []
        for t in range(n, n + 40):
            logits, pool = step(pool, tokens[None, t:t + 1], table, np.array([t], np.int32), pages)
            rows.append(logits[0, 0])
        miss[dtype] = _miss(np.stack(rows), wanted[n:n + 40])
    assert miss[jnp.float32] < TOL and miss[jnp.bfloat16] > 100 * miss[jnp.float32]
