"""The serving engine (`serve/llm_paged.py::PagedLLMEngine`, the one every
builder constructs): correctness vs the plain forward, what a plain
`LLMConfig` gets from each builder, prefix caching, memory headroom, PD
disaggregation handoff (reference: vLLM paged KV / automatic prefix caching /
pd_server.py — native here)."""

import dataclasses

import numpy as np
import pytest

from ray_tpu.models import Model, llama
from ray_tpu.serve.llm import LLMConfig, build_llm_deployment
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine, prefill_writes
from ray_tpu.serve.openai_api import build_openai_app


@pytest.fixture(scope="module")
def shared_params():
    import jax

    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.init(cfg, jax.random.PRNGKey(7))


def _paged(cfg, params, **kw):
    pc = PagedLLMConfig(model_config=cfg, max_batch_size=4, max_seq_len=128,
                        block_size=16, **kw)
    return PagedLLMEngine(pc, params=params)


# ------------------------------------------------- one engine, whoever builds it
def _replica(app):
    """The replica object a bound application would run, made in place."""
    d = app.deployment
    return d.func_or_class(*d.init_args, **d.init_kwargs)


def _from_app(build):
    """`build` is a builder of a bound application whose replica holds `engine`."""
    def make(config):
        replica = _replica(build(config))
        return replica.engine, replica.shutdown
    return make


def _from_processor(config):
    from ray_tpu.data.llm import Processor, ProcessorConfig

    engine = Processor(ProcessorConfig(llm_config=config))._get_engine()
    return engine, engine.shutdown


@pytest.mark.parametrize("build", [
    pytest.param(_from_app(build_llm_deployment), id="build_llm_deployment"),
    pytest.param(_from_app(build_openai_app), id="build_openai_app"),
    pytest.param(_from_processor, id="data.llm.Processor"),
])
def test_a_plain_config_is_served_by_the_one_engine(build):
    """Every builder hands a plain `LLMConfig` the engine the benchmark
    measures: the class object itself, its pool, its records' fields."""
    engine, shutdown = build(LLMConfig(max_batch_size=2, max_seq_len=64))
    try:
        assert type(engine) is PagedLLMEngine
        assert engine.generate_sync([5, 9, 13], 4).num_generated == 4
        stats = engine.stats()
        assert stats["num_blocks"] == 2 * 64 // 16 + 1 and stats["prefix_queries"] == 1
    finally:
        shutdown()


def test_one_config_class_and_one_cache_contract():
    """`PagedLLMConfig` is a name of `LLMConfig`, whose ten fields and
    defaults are what the two classes had between them; the model record has
    the paged contract alone; the engine has no base class to hop through."""
    assert PagedLLMConfig is LLMConfig
    fields = {f.name: f.default for f in dataclasses.fields(LLMConfig)}
    assert list(fields) == [
        "model_config", "max_batch_size", "max_seq_len", "max_new_tokens_default",
        "temperature", "eos_token_id", "prefill_buckets", "block_size", "num_blocks",
        "kv_transfer"]
    assert list(fields.values())[1:] == [8, 256, 32, 0.0, -1, (32, 128), 16, 0, "host"]
    assert Model._fields == ("init", "logical_axes", "loss", "forward_paged", "init_kv_pool",
                             "sequence_leaves")
    assert PagedLLMEngine.__mro__ == (PagedLLMEngine, object)


def test_a_max_seq_len_that_is_no_multiple_of_the_block_size_is_refused_by_name():
    with pytest.raises(ValueError, match="max_seq_len 40 must be a block_size 16 multiple"):
        PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=40))


def test_the_default_pool_holds_every_slot_at_max_seq_len(shared_params):
    """`num_blocks` 0 is the old slot cache's capacity: `max_batch_size`
    requests of `max_seq_len` tokens each, prompts that share nothing, are
    all admitted at once and none waits for blocks (no `requeued` record)."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    B, S, new = 3, 64, 8
    eng = PagedLLMEngine(LLMConfig(model_config=cfg, max_batch_size=B, max_seq_len=S),
                         params=params, external_step=True)
    rng = np.random.default_rng(5)
    timeline.clear()
    try:
        futs = [eng.generate([int(t) for t in rng.integers(1, cfg.vocab_size, S - new)], new)
                for _ in range(B)]
        eng.step_once()
        assert eng.stats()["pending"] == 0 and all(s is not None for s in eng.slots)
        while not all(f.done() for f in futs):
            eng.step_once()
        assert [f.result().num_generated for f in futs] == [new] * B
    finally:
        eng.shutdown()
    outcomes = [e[7]["outcome"] for e in timeline.local_events()
                if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]
    assert outcomes == ["admitted"] * B


def test_prefix_cache_reuses_blocks(shared_params):
    cfg, params = shared_params
    eng = _paged(cfg, params)
    try:
        shared_prefix = list(range(1, 33))  # two full 16-token blocks
        r1 = eng.generate_sync(shared_prefix + [40, 41], 8)
        s0 = eng.allocator.stats()
        assert s0["cached_blocks"] >= 2
        r2 = eng.generate_sync(shared_prefix + [50, 51, 52], 8)
        s1 = eng.allocator.stats()
        assert s1["prefix_hits"] >= 1  # second request reused the prefix
        # same shared context must not change the continuation determinism
        r3 = eng.generate_sync(shared_prefix + [40, 41], 8)
        assert r3.token_ids == r1.token_ids
    finally:
        eng.shutdown()


def test_memory_headroom_vs_dense(shared_params):
    """VERDICT criterion: >=2x memory headroom at mixed sequence lengths.
    A paged pool sized at HALF the dense cache serves the same mixed-length
    workload (memory scales with actual tokens, not slots x max_seq_len)."""
    cfg, params = shared_params
    B, S, bs = 4, 128, 16
    dense_blocks_equiv = B * (S // bs)  # dense reserves B x S always
    eng = _paged(cfg, params, num_blocks=dense_blocks_equiv // 2 + 1)
    try:
        itemsize = 4 if "float32" in str(cfg.dtype) else 2
        # token for token at the pool's row width: a head under 128 lanes takes
        # a 128-lane tile (`llama.init_kv_pool`; the device pads the dense
        # cache's rows in HBM just the same)
        row = cfg.num_kv_heads * llama.pool_head_dim(cfg.hd)
        assert eng.pool["k"].shape == (cfg.num_layers, dense_blocks_equiv // 2 + 1, bs, row)
        dense_bytes = 2 * cfg.num_layers * B * S * row * itemsize
        assert eng.kv_memory_bytes() < 0.6 * dense_bytes
        # mixed short sequences: 4 concurrent x (24 prompt + 8 new) = 2 blocks
        # each -> fits the half-size pool with room to spare
        futs = [eng.generate(list(range(1, 25)), 8) for _ in range(4)]
        outs = [f.result(timeout=120) for f in futs]
        assert all(o.num_generated == 8 for o in outs)
        # and capacity queues rather than fails when oversubscribed
        more = [eng.generate([7] * 100, 8) for _ in range(3)]
        outs2 = [f.result(timeout=240) for f in more]
        assert all(o.num_generated == 8 for o in outs2)
    finally:
        eng.shutdown()


def test_blocks_released_on_finish(shared_params):
    cfg, params = shared_params
    eng = _paged(cfg, params)
    try:
        before = eng.allocator.stats()["allocated_blocks"]
        eng.generate_sync([4, 5, 6, 7], 4)
        after = eng.allocator.stats()
        # non-cacheable remainder blocks return to the free list; only full
        # prompt blocks may stay cached (ref 0, reusable)
        assert after["allocated_blocks"] == before
    finally:
        eng.shutdown()


def test_pd_disaggregation_handoff(shared_params):
    """Prefill on engine A, decode on engine B -> same tokens as a single
    engine end-to-end (the PD smoke per VERDICT)."""
    cfg, params = shared_params
    prefiller = _paged(cfg, params)
    decoder = _paged(cfg, params)
    ref_engine = _paged(cfg, params)
    prompt = list(range(2, 30))
    try:
        expect = ref_engine.generate_sync(prompt, 10).token_ids
        handoff = prefiller.prefill_extract(prompt)
        assert handoff["prompt_len"] == len(prompt)
        # the payload is the prompt's pages as the pool holds them, block axis 1:
        # [L, n, block_size, Hkv * Dp]
        n = handoff["n_prefill_blocks"]
        assert n == -(-len(prompt) // prefiller.config.block_size)
        assert handoff["kv"]["k"].shape == handoff["kv"]["v"].shape == (
            cfg.num_layers, n, *prefiller.pool["k"].shape[2:])
        fut = decoder.attach_sequence(handoff, 10)
        got = fut.result(timeout=120)
        assert got.token_ids == expect
        assert got.num_prompt_tokens == len(prompt)
    finally:
        prefiller.shutdown()
        decoder.shutdown()
        ref_engine.shutdown()


# ------------------------------------------------- the prefill's one row
# A prefill runs the output head on the last live position alone and hands
# back [1, vocab] (PERF.md section 6, PR 32). What the engines sample must be
# what the plain forward over the whole sequence, with no cache and every
# position's logits, says: the greedy continuation by `llama.forward`.
def _plain_greedy(cfg, params, prompt, n):
    import jax.numpy as jnp

    seq = list(prompt)
    for _ in range(n):
        logits = llama.forward(params, jnp.asarray([seq], jnp.int32), cfg)
        seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return seq[len(prompt):]


def _spec(cfg, params):
    from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine

    return SpecDecodeLLMEngine(SpecDecodeConfig(
        model_config=cfg, draft_model_config=cfg, max_batch_size=4, max_seq_len=128,
        block_size=16, num_speculative_tokens=3), params=params, draft_params=params)


@pytest.mark.parametrize("make", [
    pytest.param(_paged, id="paged"),
    pytest.param(_spec, id="speculative"),
])
def test_engines_greedy_output_is_the_plain_forwards(shared_params, make):
    """Prompts that end inside a bucket (5 of 32; 39 and 120 of 128) and on a
    bucket's last position (32 of 32)."""
    cfg, params = shared_params
    eng = make(cfg, params)
    rng = np.random.default_rng(3)
    try:
        for n in (5, 32, 39, 120):
            prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, n)]
            got = eng.generate_sync(prompt, 6).token_ids
            assert got == _plain_greedy(cfg, params, prompt, 6), f"prompt of {n}"
    finally:
        eng.shutdown()


def test_whole_prompt_cached_admission_samples_from_the_recomputed_blocks_last_row(
        shared_params):
    """A prompt of two full blocks admitted twice: the second time all of it
    is cached, the last block is recomputed (the span starts at 16, 16 tokens
    live of a 32 bucket), and the first token comes from row 15 of that
    suffix, not from the bucket's last row nor the prompt's index."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    prompt = [int(t) for t in np.random.default_rng(4).integers(1, cfg.vocab_size, 32)]
    want = _plain_greedy(cfg, params, prompt, 4)
    eng = _paged(cfg, params)
    timeline.clear()
    try:
        first = eng.generate_sync(prompt, 4).token_ids
        again = eng.generate_sync(prompt, 4).token_ids
    finally:
        eng.shutdown()
    assert first == want and again == want
    admits = [e[7] for e in timeline.local_events()
              if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]
    assert [(a["cached"], a["bucket"], a["reads"], a["writes"]) for a in admits] == [
        (0, 32, "own_rows", "pages"), (16, 32, "table", "rows")]


@pytest.mark.parametrize("start, bucket, block_size, writes", [
    (0, 2048, 16, "pages"), (0, 32, 16, "pages"), (0, 16, 16, "pages"),
    (0, 40, 16, "rows"),       # a bucket that ends inside a block
    (16, 2048, 16, "rows"),    # a suffix behind a cached prefix: its start is traced
    (0, 8, 16, "rows"),
])
def test_prefill_writes_names_the_write_the_program_takes(start, bucket, block_size, writes):
    """`prefill_writes` says on the host what the traced program decides from
    the same two static facts (`llama.writes_pages`): a prefill told `fresh`
    whose tokens fill whole blocks writes pages, anything else rows."""
    assert prefill_writes(start, bucket, block_size) == writes


def test_an_engine_whose_bucket_is_no_whole_blocks_writes_rows_and_generates_the_same(
        shared_params):
    """A bucket of 40 over blocks of 16: the fresh program's rows are no whole
    pages, so it scatters them (`writes: rows`), and the tokens are the plain
    forward's all the same."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    prompt = [int(t) for t in np.random.default_rng(8).integers(1, cfg.vocab_size, 35)]
    eng = _paged(cfg, params, prefill_buckets=(40, 128))
    timeline.clear()
    try:
        got = eng.generate_sync(prompt, 5).token_ids
    finally:
        eng.shutdown()
    assert got == _plain_greedy(cfg, params, prompt, 5)
    admits = [e[7] for e in timeline.local_events()
              if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]
    assert [(a["bucket"], a["reads"], a["writes"]) for a in admits] == [(40, "own_rows", "rows")]


@pytest.mark.parametrize("before, cached, reads", [
    pytest.param([], 0, "own_rows", id="fresh"),
    pytest.param([40, 41], 32, "table", id="prefix-hit"),
])
def test_an_admission_notes_which_prefill_it_ran_and_generates_the_plain_forwards_tokens(
        shared_params, before, cached, reads):
    """The engine's one `_prefill` chooses its program from the span it is
    handed, on the host: a prompt no block of which is cached attends over its
    own rows, one whose first two blocks another request left in the prefix
    cache reads them back through the table. The `admit` record says which
    (`reads`), every admission goes through the one callable with its five
    arguments, the span a host array, and either way the tokens are the
    cache-less forward's."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    shared = [int(t) for t in np.random.default_rng(6).integers(1, cfg.vocab_size, 32)]
    prompt = shared + [50, 51, 52]
    eng = _paged(cfg, params)
    prefill, spans = eng._prefill, []

    def spy(params, pool, tokens, table, span):
        spans.append(span)
        return prefill(params, pool, tokens, table, span)

    eng._prefill = spy
    timeline.clear()
    try:
        if before:
            eng.generate_sync(shared + before, 2)
        got = eng.generate_sync(prompt, 6).token_ids
    finally:
        eng.shutdown()
    assert got == _plain_greedy(cfg, params, prompt, 6)
    admits = [e[7] for e in timeline.local_events()
              if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]
    assert len(admits) == len(spans) == 1 + bool(before)
    assert (admits[-1]["cached"], admits[-1]["reads"]) == (cached, reads)
    assert admits[0]["reads"] == "own_rows"
    # a span that starts at 0 in a bucket of whole blocks writes whole pages
    assert [a["writes"] for a in admits] == ["pages", "rows"][:len(admits)]
    assert all(isinstance(sp, np.ndarray) and sp.dtype == np.int32 for sp in spans)
    assert spans[-1].tolist() == [cached, len(prompt) - cached]


def test_prefill_extract_hands_over_the_first_token_of_the_plain_forward(shared_params):
    """`prefill_extract` reads the one row too: its first token, and what the
    engine that attaches the pages decodes after it."""
    cfg, params = shared_params
    prompt = [int(t) for t in np.random.default_rng(5).integers(1, cfg.vocab_size, 37)]
    want = _plain_greedy(cfg, params, prompt, 6)
    prefiller, decoder = _paged(cfg, params), _paged(cfg, params)
    try:
        handoff = prefiller.prefill_extract(prompt)
        assert handoff["first_token"] == want[0]
        assert decoder.attach_sequence(handoff, 6).result(timeout=120).token_ids == want
    finally:
        prefiller.shutdown()
        decoder.shutdown()


# ------------------------------------------------- BlockPool under pressure
def test_alloc_rollback_under_pressure_releases_evicted_cache_blocks():
    """An alloc that evicts cached-prefix blocks and STILL comes up short
    must roll the whole grab back — evicted-from-cache blocks return to the
    free list (not leaked as phantom refs) and full capacity stays
    allocatable."""
    from ray_tpu.serve.paged_kv import BlockPool, NoFreeBlocks

    pool = BlockPool(num_blocks=6, block_size=4)  # blocks 1..5 usable
    prompt = list(range(8))  # 2 full blocks
    ids = pool.alloc(2)
    pool.register_prefix(prompt, ids)
    pool.free(ids)  # cached at refcount 0: reusable until evicted
    assert pool.stats()["cached_blocks"] == 2
    assert pool.stats()["free_blocks"] == 5

    with pytest.raises(NoFreeBlocks):
        pool.alloc(6)  # 3 plain free + 2 evictable cached < 6
    st = pool.stats()
    assert st["free_blocks"] == 5, "rollback leaked blocks"
    assert st["allocated_blocks"] == 0
    # the failed attempt consumed the cache entries of the blocks it evicted
    # (they were reclaimed mid-grab; rollback returns them as PLAIN free)
    got = pool.alloc(5)  # full capacity still allocatable
    assert len(set(got)) == 5
    pool.free(got)


def test_alloc_eviction_prefers_lru_zero_ref_cached_block():
    from ray_tpu.serve.paged_kv import BlockPool

    pool = BlockPool(num_blocks=4, block_size=4)  # 3 usable
    pa, pb, pc = [list(range(i, i + 4)) for i in (0, 10, 20)]
    a = pool.alloc(1); pool.register_prefix(pa, a); pool.free(a)
    b = pool.alloc(1); pool.register_prefix(pb, b); pool.free(b)
    c = pool.alloc(1); pool.register_prefix(pc, c); pool.free(c)
    # touch A so B becomes the LRU zero-ref entry
    hit, n = pool.lookup_prefix(pa)
    assert hit == a and n == 4
    got = pool.alloc(1)  # free list empty: must evict LRU (B)
    assert got == b
    # B's cache entry is gone; A (referenced) and C survive
    assert pool.lookup_prefix(pb) == ([], 0)
    assert pool.lookup_prefix(pc)[1] == 4
    pool.free(hit); pool.free(got); pool.free(pool.lookup_prefix(pa)[0])
    pool.free(pool.lookup_prefix(pc)[0])


def test_register_prefix_with_partially_cached_prompt():
    """skip_blocks: re-registering a prompt whose prefix was already cached
    must neither duplicate entries nor rebind the cached block."""
    from ray_tpu.serve.paged_kv import BlockPool

    pool = BlockPool(num_blocks=8, block_size=4)
    prompt = list(range(12))  # 3 full blocks
    first = pool.alloc(1)
    pool.register_prefix(prompt[:4], first)
    pool.free(first)

    hit, cached_len = pool.lookup_prefix(prompt)
    assert hit == first and cached_len == 4  # partial: 1 of 3 blocks cached
    fresh = pool.alloc(2)
    block_ids = hit + fresh
    pool.register_prefix(prompt, block_ids, skip_blocks=cached_len // 4)
    st = pool.stats()
    assert st["cached_blocks"] == 3, "suffix blocks not content-addressed"

    # the whole prompt now resolves, through the ORIGINAL first block
    pool.free(block_ids)
    hit2, cached2 = pool.lookup_prefix(prompt)
    assert cached2 == 12 and hit2[0] == first[0]
    assert hit2[1:] == fresh
    pool.free(hit2)


def test_engine_admission_rolls_back_cached_hit_refs_when_pool_full():
    """_admit_one under pool pressure: a request that took prefix-hit refs
    but can't get its fresh blocks must drop those refs (the cached blocks
    stay evictable — not pinned by a request that never ran)."""
    from ray_tpu.serve.paged_kv import BlockPool, NoFreeBlocks

    pool = BlockPool(num_blocks=6, block_size=4)
    prompt = list(range(8))
    ids = pool.alloc(2)
    pool.register_prefix(prompt, ids)
    pool.free(ids)
    # simulate _admit_one's sequence: take the hit refs, fail the alloc
    hit, _ = pool.lookup_prefix(prompt)
    assert len(hit) == 2
    with pytest.raises(NoFreeBlocks):
        pool.alloc(6)
    for b in hit:  # the engine's rollback path
        pool.free([b])
    # every cached block is back at refcount 0 -> still evictable/reusable
    st = pool.stats()
    assert st["free_blocks"] == 5 and st["allocated_blocks"] == 0


def test_admission_is_in_order_of_arrival_when_blocks_bind(shared_params):
    """A pool smaller than its slots (6 usable blocks of 16, 4 slots): a
    request that finds no blocks keeps its place at the HEAD of the line. So
    admission is in order of arrival, a request larger than the free blocks
    is not overtaken by the smaller ones behind it (which would fit), and
    while it waits `requeued` is recorded once a pass, for it alone."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=4, max_seq_len=128, block_size=16,
        num_blocks=7, prefill_buckets=(32, 64)), params=params, external_step=True)
    # (prompt tokens, new tokens) -> blocks: 2, 4 (the pool is full), 5, 1, 1
    asks = [(20, 12), (40, 24), (50, 30), (10, 6), (11, 5)]
    timeline.clear()

    def admits():
        return [(e[7]["outcome"], e[7]["prompt"]) for e in timeline.local_events()
                if e[0] == "span" and e[2] == "engine" and e[3] == "admit"]

    try:
        futs = [eng.generate(list(range(1, n + 1)), new) for n, new in asks]
        per_pass = []
        for _ in range(200):
            if all(f.done() for f in futs):
                break
            before = len(admits())
            # the running count is what the walk over every block finds
            assert eng.allocator.in_use == eng.stats()["allocated_blocks"]
            eng.step_once()
            per_pass.append(admits()[before:])
        assert [f.result(0).num_generated for f in futs] == [new for _, new in asks]
        assert eng.stats()["allocated_blocks"] == eng.allocator.in_use == 0
    finally:
        eng.shutdown()
    admitted = [n for outcome, n in admits() if outcome == "admitted"]
    assert admitted == [n for n, _ in asks]
    # the first pass admits 20 and 40 and stops at 50; nothing behind it is tried
    assert per_pass[0] == [("admitted", 20), ("admitted", 40), ("requeued", 50)]
    waiting = [p for p in per_pass if ("requeued", 50) in p]
    assert len(waiting) == 23            # until the 4-block request has finished
    assert all(p == [("requeued", 50)] for p in waiting[1:])
    # 50 goes in with 10 behind it (5 + 1 blocks); 11 waits its turn at the head
    after = per_pass[len(waiting)]
    assert after == [("admitted", 50), ("admitted", 10), ("requeued", 11)]
    assert {n for p in per_pass for outcome, n in p if outcome == "requeued"} == {50, 11}


# ---- the decode loop reads one step behind itself (ISSUE 34) ----
def _stepped(cfg, params, **kw):
    """An engine driven from the test, a pass a `step_once()`, whose calls of
    `_decode` are counted (through the wrapper the benchmark's check puts on)."""
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=128, block_size=16, **kw),
        params=params, external_step=True)
    eng.calls = []
    decode = eng._decode

    def counted(params, pool, last_tokens, lengths, tables):
        eng.calls.append(np.flatnonzero(eng.active).tolist())
        return decode(params, pool, last_tokens, lengths, tables)

    eng._decode = counted
    return eng


def _decode_records():
    from ray_tpu.util import timeline

    return [e[7] for e in timeline.local_events()
            if e[0] == "span" and e[2] == "engine" and e[3] == "decode"]


@pytest.mark.parametrize("n", [1, 2, 3, 6])
def test_n_tokens_take_n_minus_1_decode_calls_and_the_last_is_read_in_its_pass(
        shared_params, n):
    """A sequence that ends by count is known to before its last step is read:
    no step is enqueued for a row whose last token is in flight, and the pass
    that enqueues the last step of everything live reads it too, so a
    `step_once()` driver needs no pass more than steps."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    prompt = [5, 9, 13, 2, 7]
    timeline.clear()
    eng = _stepped(cfg, params)
    try:
        fut = eng.generate(prompt, n)
        passes = 0
        while not fut.done():
            assert passes < n and eng.step_once()
            passes += 1
            if not fut.done():  # a step is in flight, its ids unread
                assert eng._flight is not None and eng.stats()["active_slots"] == 1
        assert eng._flight is None and not eng.step_once()
        assert fut.result(0).token_ids == _plain_greedy(cfg, params, prompt, n)
        assert (eng.calls, passes) == ([[0]] * (n - 1), max(1, n - 1))
        assert eng.last_tokens[0, 0] == 0 and eng.stats()["allocated_blocks"] == 0
    finally:
        eng.shutdown()
    records = _decode_records()
    assert [r["ahead"] for r in records] == [False] + [True] * (n - 2) if n > 1 else not records
    assert all(r["late_rows"] == 0 for r in records)


def test_an_eos_is_found_one_step_late_and_the_row_in_flight_is_dropped(shared_params):
    from ray_tpu.util import timeline

    cfg, params = shared_params
    prompt = [3, 3, 8]
    plain = _plain_greedy(cfg, params, prompt, 8)
    stop = next(k for k in range(2, 7) if plain[k] not in plain[:k])
    timeline.clear()
    eng = _stepped(cfg, params, eos_token_id=plain[stop])
    try:
        fut = eng.generate(prompt, 8)
        while not fut.done():
            assert eng.step_once()
        out = fut.result(0)
        assert (out.token_ids, out.finish_reason) == (plain[:stop + 1], "stop")
        # the token after the stop was already being decoded when it was read
        assert len(eng.calls) == stop + 1
        assert eng._flight is None and eng.stats()["allocated_blocks"] == 0
    finally:
        eng.shutdown()
    assert [r["late_rows"] for r in _decode_records()] == [0] * stop + [1]


@pytest.mark.parametrize("readmit", [False, True], ids=["cancelled", "re-admitted"])
def test_a_step_in_flight_gives_nothing_to_a_row_released_since(shared_params, readmit):
    """An id goes only to the `_Slot` its step was enqueued for: a row
    cancelled while its step was in flight, and a new request admitted into
    that row before the step is read, get nothing from it; the new request's
    first step takes its first token from the host, not the stale id."""
    from ray_tpu.util import timeline

    cfg, params = shared_params
    timeline.clear()
    eng = _stepped(cfg, params)
    try:
        gone = eng.generate(list(range(1, 30)), 20)
        assert eng.step_once()                    # admitted, step 1 enqueued
        st = eng.slots[0]
        assert eng._flight.rows == {0: st} and len(st.generated) == 1
        assert eng.cancel_future(gone)
        if readmit:
            prompt = [5, 9, 13, 2, 7]
            fut = eng.generate(prompt, 5)
            while not fut.done():
                assert eng.step_once()
            assert fut.result(0).token_ids == _plain_greedy(cfg, params, prompt, 5)
            assert eng.calls == [[0]] * 5         # one of the old row, four of the new
        else:
            assert eng.step_once() and not eng.step_once()   # the stale step is read, once
            assert eng.calls == [[0]]
        assert len(st.generated) == 1 and not gone.done()
        assert eng._flight is None and eng.stats()["allocated_blocks"] == 0
    finally:
        eng.shutdown()
    records = _decode_records()
    assert [r["late_rows"] for r in records[:2]] == [0, 1]
    assert sum(r["late_rows"] for r in records) == 1
    if not readmit:
        assert (records[1]["live"], records[1]["ahead"]) == (0, False)


def test_a_row_admitted_beside_a_step_in_flight_starts_from_its_own_token(shared_params):
    """Two requests a pass apart, decoded together from the second's first
    step on: each reads what it reads alone (the tokens of the step enqueued
    are the device's ids with the newcomer's first token written over its
    row), and the shorter one's row sits out the steps after its last."""
    cfg, params = shared_params
    asks = [(list(range(1, 40)), 7), ([5, 9, 13, 2, 7], 4)]
    eng = _stepped(cfg, params)
    try:
        first = eng.generate(*asks[0])
        assert eng.step_once()
        second = eng.generate(*asks[1])
        while not (first.done() and second.done()):
            assert eng.step_once()
        for fut, (prompt, n) in zip((first, second), asks):
            assert fut.result(0).token_ids == _plain_greedy(cfg, params, prompt, n)
        assert eng.calls == [[0]] + [[0, 1]] * 3 + [[0]] * 2
    finally:
        eng.shutdown()


def test_shutdown_leaves_no_step_unread(shared_params):
    cfg, params = shared_params
    eng = _stepped(cfg, params)
    fut = eng.generate([5, 9, 13, 2, 7], 10)
    assert eng.step_once() and eng._flight is not None
    eng.shutdown()
    assert eng._flight is None
    with pytest.raises(RuntimeError, match="shut down"):
        fut.result(0)


def test_a_temperature_draws_from_the_softmax_on_the_device():
    """Temperature 0.7 over a vocabulary of 8: 600 second tokens after one
    prompt, drawn by `pick` from the engine's own key, against the softmax of
    the plain forward's logits, by count. The bound is 5 standard deviations
    of a count, and 600 equal draws (a key that never advances) would miss it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), vocab_size=8)
    params = llama.init(cfg, jax.random.PRNGKey(11))
    prompt, first, T, draws = [1, 2, 3, 4, 5], {}, 0.7, 600
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=4, max_seq_len=64, block_size=16,
        temperature=T), params=params, seed=5)
    try:
        futs = [eng.generate(prompt, 2) for _ in range(draws)]
        for f in futs:
            a, b = f.result(timeout=300).token_ids
            first.setdefault(a, []).append(b)
    finally:
        eng.shutdown()
    a, seconds = max(first.items(), key=lambda kv: len(kv[1]))
    logits = llama.forward(params, jnp.asarray([prompt + [a]], jnp.int32), cfg)
    p = np.asarray(jax.nn.softmax(logits[0, -1] / T))
    n = len(seconds)
    counts = np.bincount(seconds, minlength=8)
    assert n > 100 and (np.abs(counts - n * p) <= 5 * np.sqrt(n * p * (1 - p)) + 1).all()
    assert (counts > 0).sum() >= 3
