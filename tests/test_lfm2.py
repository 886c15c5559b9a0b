"""models/lfm2.py (a stack of two kinds of mixer, gated short convolutions
whose state rides in the paged pool's pages beside grouped-query attention's
keys and values, run as `llama.decoder_trunk(runs=)` over the one layer) at a
tiny size against the plain reference (benchmarks/reference/lfm2_reference.py).
On LOGITS, in float32: the cache-less forward; prefill then decode through
`forward_paged` and a real `BlockPool` at the state rows' edges; a cached
prefix resuming from the pages through the engine; the PD hand-off; a freed
page's stale state; the two shares adding up; the per-head norm; wrong
programs that must miss. (That the trunk over runs is the old trunk, letter
for letter, for every family of one kind is tests/test_lowered_text.py's.)"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import lfm2 as family
from benchmarks.reference import lfm2_reference as reference
from ray_tpu.models import lfm2, llama, moe
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine, page_leaves
from ray_tpu.serve.paged_kv import BlockPool
from tests.test_paged_attention import PAGE_WRITE_CASES, check_page_write_against_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (shifted multiply-adds over a cache's rows against three shifted
# copies, a cache against a full recompute, sorted rows against a dense
# weighted sum): measured 2e-7 to 5e-7 of the logits' size (unit-rms rows of
# a tied embedding give logits of ~70). 1e-5 admits that; the wrong programs
# below miss by 0.02 or more.
TOL = 1e-5
BS = 16


def _tiny_file() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "lfm2-serve.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    """8 layers (conv, conv, attn, conv x 3, attn, conv: a whole stack of one
    dense layer, runs of one, a run of three), hidden 64, 4 query and 2
    key-value heads of 16, 8 experts of which the second half is held, 2 a
    token: the benchmark's CPU stand-in of the LFM2 configuration."""
    file = _tiny_file()
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert cfg.kinds == ["conv_dense", "conv_moe", "attn_moe", "conv_moe", "conv_moe",
                         "conv_moe", "attn_moe", "conv_moe"]
    assert cfg.experts.experts_held == (4, 4) and cfg.experts.norm_topk_eps == 1e-6
    params = jax.jit(lambda k: lfm2.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 40))
    # norm weights other than one, so that one in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    for stack in set(cfg.kinds):
        params[stack] = {k: noisy(i, v) if k.endswith("_norm") else v
                         for i, (k, v) in enumerate(sorted(params[stack].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 56)
    return model, cfg, params, tokens


def _miss(got, want) -> float:
    """The benchmark's two measures (`serve_cell.check_against_reference`),
    the larger: rms error / rms logit and max error / max logit."""
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


def test_the_cache_less_forward_gives_the_reference_s_logits(tiny):
    """(1) Every kind of run (a whole stack scanned, runs of one as the bare
    body, a run of three taken out of its stack by place)."""
    model, cfg, params, tokens = tiny
    want = reference.logits(params, tokens, model)
    got = jax.jit(lambda t: lfm2.forward(params, t, cfg))(jnp.asarray(tokens)[None])
    assert got.shape == (1, len(tokens), cfg.vocab_size)
    assert _miss(got[0], want) < TOL
    runs = lfm2._runs(cfg, params, {"attn": None, "conv": None}, "cpu")[1]
    assert [(r.stack, r.first, r.count, r.cache_first) for r in runs] == [
        ("conv_dense", 0, 1, 0), ("conv_moe", 0, 1, 1), ("attn_moe", 0, 1, 0),
        ("conv_moe", 1, 3, 2), ("attn_moe", 1, 1, 1), ("conv_moe", 4, 1, 5)]


def _paged(params, cfg, pool, tokens, table, start, **kw):
    """One sequence's tokens [S] at positions [start, start + S) through
    `forward_paged`, in row 1 of 2 (row 0 is an empty slot: table and length
    zero, the garbage block)."""
    row1 = lambda a: jnp.zeros((2, *jnp.shape(a)), jnp.int32).at[1].set(a)   # traced or not
    head = kw.pop("head", None)
    if head is not None:
        kw["head_rows"] = row1(head)
    logits, pool = lfm2.forward_paged(
        params, row1(tokens), cfg, pool, row1(table), row1(start), BS, **kw)
    return logits[1], pool


def _stale(pool):
    """Every page as a sequence that ended left it: nothing is zero."""
    return {**pool, **{k: v + 3.0 for k, v in page_leaves(pool).items()}}


@pytest.mark.parametrize("n_prompt, fresh", [(16, True), (17, False), (18, True), (31, False),
                                             (32, True), (47, False)])
def test_prefill_then_decode_through_the_pages_matches_the_reference(tiny, n_prompt, fresh):
    """(2) A prompt of 0, 1, 2, 15, 0 and 15 blocks-and-rows modulo the block
    size (the state rows' edges: the last live position first, second or last
    in its block, of either parity), padded to a bucket of 48 and prefilled
    into pages a real `BlockPool` handed out, then a token a step: every row
    is the reference's. The bucket's padding writes no state (`head_rows`
    names the last live position), and the pages start STALE."""
    model, cfg, params, tokens = tiny
    want = np.asarray(reference.logits(params, tokens, model))
    allocator = BlockPool(9, BS)
    allocator.alloc(2)                                   # someone else's
    table = np.zeros(4, np.int32)
    blocks = allocator.alloc(-(-len(tokens) // BS))
    table[:len(blocks)] = blocks
    pool = _stale(lfm2.init_kv_pool(cfg, 9, BS))
    assert pool["conv"].shape == (6, 9, 2, 64) and pool["k"].shape == (2, 9, BS, 2 * 128)
    padded = np.zeros(48, np.int32)
    padded[:n_prompt] = tokens[:n_prompt]
    step = jax.jit(lambda pool, toks, start, **kw: _paged(params, cfg, pool, toks, table,
                                                          start, **kw),
                   static_argnames=("fresh",))
    logits, pool = step(pool, padded, 0, head=n_prompt - 1, fresh=fresh)
    rows = [logits[0]]
    for t in range(n_prompt, len(tokens)):
        logits, pool = step(pool, tokens[t:t + 1], t)
        rows.append(logits[0])
    assert _miss(np.stack(rows), want[n_prompt - 1:]) < TOL
    # a FULL block's two rows are u at its positions 14 and 15 whoever wrote
    # them: the prefill of 47 and the prefill of 16 then decode agree on block 0
    counters = jax.tree.map(int, pool["counters"])
    assert 0 < counters["moe_rows"] <= counters["moe_moved"] == 2 * 2 * 7


def test_a_continued_prefill_resumes_from_the_block_before_it(tiny):
    """(2, the table program) A suffix that starts at a block's edge reads the
    two rows of the block before it; one that starts inside a block reads its
    own block's; both give the reference's rows, and the state a prefill of
    the whole prompt leaves in a FULL block is the state the chunks left."""
    model, cfg, params, tokens = tiny
    want = np.asarray(reference.logits(params, tokens, model))
    table = np.asarray([5, 2, 7, 1], np.int32)
    whole = _paged(params, cfg, _stale(lfm2.init_kv_pool(cfg, 9, BS)), tokens[:48], table, 0,
                   fresh=True)[1]
    for cut in (32, 21):
        pool = _stale(lfm2.init_kv_pool(cfg, 9, BS))
        _, pool = _paged(params, cfg, pool, tokens[:cut], table, 0)
        logits, pool = _paged(params, cfg, pool, tokens[cut:48], table, cut)
        assert _miss(logits, want[cut:48]) < TOL
        for block in (5, 2, 7):
            np.testing.assert_allclose(pool["conv"][:, block], whole["conv"][:, block],
                                       rtol=1e-5, atol=1e-5)


def _engine(cfg, params, **kw):
    return PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS, num_blocks=13,
        prefill_buckets=(16, 32, 64), **kw), params=params, external_step=True)


def _generate(eng, prompt, n: int):
    """(token ids, the logits every token was chosen from) of one request,
    driven by hand: the prefill's row and each decode step's."""
    kept = []
    prefill, decode = eng._prefill, eng._decode

    def keep_prefill(*a):
        logits, pool = prefill(*a)
        kept.append(np.asarray(logits)[0])
        return logits, pool

    def keep_decode(params, pool, last, lengths, tables):
        logits, pool = decode(params, pool, last, lengths, tables)
        kept.append(np.asarray(logits)[int(np.flatnonzero(np.asarray(lengths))[0])])
        return logits, pool

    eng._prefill, eng._decode = keep_prefill, keep_decode
    try:
        fut = eng.generate(prompt, n)
        for _ in range(4 * n):
            if fut.done():
                break
            eng.step_once()
        return fut.result(0).token_ids, np.stack(kept)
    finally:
        eng._prefill, eng._decode = prefill, decode


def test_a_cached_prefix_resumes_from_the_state_in_its_pages(tiny):
    """(3) Through `PagedLLMEngine`, prefix caching ON as for every family: a
    second prompt that shares two full blocks with a first prefills its
    suffix alone (the table program, from position 32) and gives the logits
    of the same prompt admitted into an empty engine, which are the
    reference's: the convolutions' state resumed from block 2's two rows."""
    model, cfg, params, tokens = tiny
    first = list(map(int, tokens[:40]))
    second = first[:32] + list(map(int, tokens[41:52]))
    want = np.asarray(reference.logits(params, second, model))
    eng, empty = _engine(cfg, params), _engine(cfg, params)
    try:
        _generate(eng, first, 3)
        ids, got = _generate(eng, second, 6)
        assert eng.allocator.stats()["prefix_hits"] == 1
        alone_ids, alone = _generate(empty, second, 6)
        assert empty.allocator.stats()["prefix_hits"] == 0
    finally:
        eng.shutdown()
        empty.shutdown()
    assert ids == alone_ids and _miss(got, alone) < TOL
    assert _miss(got[0], want[len(second) - 1]) < TOL
    assert ids[0] == int(np.argmax(want[len(second) - 1]))


def test_the_state_goes_through_the_host_hand_off_with_its_pages(tiny):
    """(4) The PD hand-off moves a family's page-shaped pool leaves by tree,
    whatever their third axis: `prefill_extract` on one engine ships `k`, `v`
    and `conv` pages, `attach_sequence` on another lands them, and the tokens
    are those of one engine alone."""
    model, cfg, params, tokens = tiny
    prompt = list(map(int, tokens[:21]))
    make = lambda: PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=9, prefill_buckets=(16, 32), kv_transfer="host"), params=params)
    alone, pre, dec = make(), make(), make()
    try:
        want = alone.generate_sync(prompt, 6).token_ids
        handoff = pre.prefill_extract(prompt)
        assert set(handoff["kv"]) == {"k", "v", "conv"} == set(page_leaves(pre.pool))
        assert handoff["kv"]["conv"].shape == (6, 2, 2, 64)
        assert handoff["kv"]["k"].shape == (2, 2, BS, 2 * 128)
        assert dec.attach_sequence(handoff, 6).result(60).token_ids == want
    finally:
        for e in (alone, pre, dec):
            e.shutdown()


def test_a_freed_block_s_stale_state_changes_nothing(tiny):
    """(5) A sequence that starts at position 0 in blocks another sequence has
    just freed, through the program that READS the table (not told `fresh`):
    the rows before position 0 are masked, not trusted to be zero."""
    model, cfg, params, tokens = tiny
    allocator = BlockPool(5, BS)
    pool = lfm2.init_kv_pool(cfg, 5, BS)
    table = np.zeros(4, np.int32)
    table[:3] = blocks = allocator.alloc(3)
    _, pool = _paged(params, cfg, pool, tokens[8:48], table, 0)      # someone's sequence
    assert float(jnp.abs(pool["conv"][:, blocks[0]]).min()) > 0
    allocator.free(blocks)
    again = allocator.alloc(2)
    assert set(again) <= set(blocks)
    table = np.zeros(4, np.int32)
    table[:2] = again
    want = np.asarray(reference.logits(params, tokens[:20], model))
    logits, pool = _paged(params, cfg, pool, tokens[:19], table, 0)
    last, _ = _paged(params, cfg, pool, tokens[19:20], table, 19)
    assert _miss(np.concatenate([logits, last]), want) < TOL


def test_the_two_shares_add_up_to_the_uncut_layer(tiny):
    """(6) The guide's tie at this model's cut: the parts of the routed sum
    that experts [0, 4) and [4, 8) give through the program's `moe_mlp` add up
    to what the uncut reference gives for the whole layer (no shared expert;
    the mixers are no part of the layer's sum), and each is the reference's
    given the same share."""
    model, cfg, params, _ = tiny
    h, m, E, T = 64, 32, 8, 40
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    whole = {"router": dense(ks[0], h, E), "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "e_gate": dense(ks[2], E, h, m), "e_up": dense(ks[3], E, h, m),
             "e_down": dense(ks[4], E, m, h)}
    y = jax.random.normal(ks[5], (1, T, h), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = reference.expert_layer(y[0], whole, {**model, "num_experts": E}, first=0)
        parts, rows = [], 0
        for first in (0, 4):
            share = {k: v[first:first + 4] if k.startswith("e_") else v for k, v in whole.items()}
            held = dataclasses.replace(cfg.experts, experts_held=(first, 4))
            out, stats = moe.moe_mlp(y, share, held, platform="cpu")
            parts.append(out[0])
            rows += int(stats["rows"])
            assert _miss(out[0], reference.expert_layer(y[0], share, model, first=first)) < TOL
    assert rows == T * 2                      # every pair is one share's
    assert _miss(parts[0] + parts[1], uncut) < TOL
    assert _miss(parts[0], uncut) > 0.1       # one share alone is not the layer


@pytest.mark.parametrize("norm, same", [("per_head", True), ("whole_vector", False),
                                        ("after_rope", False)])
def test_the_query_and_key_norm_is_over_each_head_s_lanes_before_rope(tiny, norm, same):
    """(7) `gqa_attention` with a weight of `head_dim` norms each head's 16
    lanes, before the rotation; one norm over the whole projected vector
    (OLMoE's, which a weight as wide as the projection still gets) or a norm
    after the rotation is another model."""
    model, cfg, params, tokens = tiny
    layer = jax.tree.map(lambda a: a[0], {k: v for k, v in params["attn_moe"].items()
                                          if not k.startswith("e_")})
    assert layer["q_norm"].shape == (16,) and layer["wq"].shape == (64, 64)
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64), jnp.float32)
    positions = jnp.arange(24, dtype=jnp.int32)[None]
    o, _ = llama.plain_attend()(cfg.base, y, layer, None, positions, None)
    got = o.reshape(24, -1) @ layer["wo"]
    with jax.default_matmul_precision("highest"):
        want = reference.attention(y[0], layer, model, norm=norm)
    assert (_miss(got, want) < TOL) == same
    if not same:
        assert _miss(got, want) > 0.02
    # a weight as wide as the whole projection is OLMoE's norm, as it was
    wide = {**layer, "q_norm": jnp.tile(layer["q_norm"], 4), "k_norm": jnp.tile(layer["k_norm"], 2)}
    o, _ = llama.plain_attend()(cfg.base, y, wide, None, positions, None)
    with jax.default_matmul_precision("highest"):
        whole = reference.attention(y[0], layer, model, norm="whole_vector")
    assert _miss(o.reshape(24, -1) @ layer["wo"], whole) < TOL


def _wrong_reference(name: str, params, tokens, model):
    """The reference with ONE thing wrong, by name."""
    import unittest.mock as mock

    conv, attn, route = reference.short_conv, reference.attention, reference.route
    patch = {
        "a tap dropped": ("short_conv", lambda y, w: conv(y, w, taps=(None, 1, 2))),
        "the taps reversed": ("short_conv", lambda y, w: conv(y, w, taps=(2, 1, 0))),
        "B * x without the gate C": ("short_conv", lambda y, w: conv(y, w, gated=False)),
        "the norm over the whole vector": ("attention", lambda y, w, m: attn(y, w, m, "whole_vector")),
        "the norm after rope": ("attention", lambda y, w, m: attn(y, w, m, "after_rope")),
        "softmax for sigmoid": ("route", lambda y, w, m: route(y, w, m, score="softmax")),
        "the bias weighing": ("route", lambda y, w, m: route(y, w, m, bias="weighs")),
    }[name]
    with mock.patch.object(reference, *patch):
        # `_block` is compiled by its static arguments: a wrong piece needs its own trace
        reference._block.clear_cache()
        try:
            return np.asarray(reference.logits(params, tokens, model))
        finally:
            reference._block.clear_cache()


@pytest.mark.parametrize("name, at_least", [
    ("a tap dropped", 0.05), ("the taps reversed", 0.05), ("B * x without the gate C", 0.05),
    ("the norm over the whole vector", 0.02), ("the norm after rope", 0.005),
    ("softmax for sigmoid", 0.05), ("the bias weighing", 0.02)])
def test_a_wrong_program_misses_the_reference(tiny, name, at_least):
    """What the chip's check is set to tell apart (the configuration file's
    `check.would_fail`), here in float32 at eight layers, where the program
    itself reads 3e-7: the program against a reference with one piece wrong
    misses by `at_least` or more. The norm after rope is told only by a norm
    WEIGHT that is not one (the fixture's are 1 +- 0.1): a rotation keeps a
    head's rms, so with a fresh model's unit weights the two are the same
    program, and the chip's check cannot tell them."""
    model, cfg, params, tokens = tiny
    got = jax.jit(lambda t: lfm2.forward(params, t, cfg))(jnp.asarray(tokens)[None])[0]
    assert _miss(got, _wrong_reference(name, params, tokens, model)) > at_least
    assert _miss(got, reference.logits(params, tokens, model)) < TOL


@pytest.mark.parametrize("wrong", ["the other parity", "a bucket's padding taken for live"])
def test_state_from_the_wrong_row_misses(tiny, wrong):
    """The paged state's two ways to be wrong: a step that reads u_{t-1} and
    u_{t-2} from each other's rows, and a padded prefill that writes the `u` of
    its padding over its last live rows (no `head_rows` to say where it ends)."""
    model, cfg, params, tokens = tiny
    want = np.asarray(reference.logits(params, tokens[:20], model))
    table = np.asarray([3, 1, 0, 0], np.int32)
    pool = _stale(lfm2.init_kv_pool(cfg, 5, BS))
    padded = np.zeros(32, np.int32)
    padded[:19] = tokens[:19]
    _, honest = _paged(params, cfg, pool, padded, table, 0, head=18)
    if wrong == "the other parity":
        wrong_pool = {**honest, "conv": honest["conv"][:, :, ::-1]}
    else:
        _, wrong_pool = _paged(params, cfg, pool, padded, table, 0)
    got, _ = _paged(params, cfg, wrong_pool, tokens[19:20], table, 19)
    right, _ = _paged(params, cfg, honest, tokens[19:20], table, 19)
    assert _miss(right, want[19:]) < TOL < 0.02 < _miss(got, want[19:])


@pytest.mark.parametrize("case", PAGE_WRITE_CASES)
def test_a_fresh_prefill_s_pages_leave_k_and_v_the_row_scatter_s_beside_the_state(
        monkeypatch, tiny, case):
    """The attention layers are `llama.paged_attend`'s, so a fresh prefill
    writes their keys and values as whole pages; the convolutions' state (2
    rows a block, the last live position of each parity, nothing of the
    padding) is written as it was. All three leaves hold what the row
    scatter's program left, exactly, outside the garbage block; the same
    logits and the same next decode step, which reads the state back."""
    _, cfg, params, _ = tiny
    check_page_write_against_rows(
        monkeypatch, lambda tokens, pool, tables, lengths, **kw: lfm2.forward_paged(
            params, tokens, cfg, pool, tables, lengths, **kw),
        lambda blocks, bs: lfm2.init_kv_pool(cfg, blocks, bs), cfg.base.vocab_size,
        case)


def test_speculative_decoding_refuses_a_pool_with_rows_a_block(tiny):
    """A rejected window is rewound by `lengths` alone, which rows a block do
    not survive: the engine refuses the pool by what it HOLDS and says why;
    a pool of rows a token (the Llama family's) is taken as before."""
    from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine

    model, cfg, params, _ = tiny
    draft = llama.LlamaConfig.tiny()
    make = lambda target, **kw: SpecDecodeLLMEngine(SpecDecodeConfig(
        model_config=target, draft_model_config=draft, max_batch_size=2, max_seq_len=64,
        block_size=BS, num_blocks=9, prefill_buckets=(16, 32)), **kw)
    with pytest.raises(ValueError, match=r"\['conv'\] as rows a block.*2 rows where block_size is 16"):
        make(cfg, params=params)
    make(draft).shutdown()
