"""Pallas paged decode-attention kernel tests (interpret mode on CPU).

Reference analog: the vLLM paged_attention kernel the reference delegates
serving to; here native (ops/paged_attention.py), validated against the
dense cached-attention math in models/llama.py.

These run the kernel interpreted. Compiled, it was checked on the chip by
chip_smoke.py's kernels phase (PR 28, 2026-09-27, TPU v5 lite, jax 0.9.0): a
forward_paged decode step at llama_1b widths (32 query / 8 KV heads of 64,
block 16, a 128-block table, bf16, 2 layers) with ragged lengths 1, 2, 16, 17,
38, 1028, 2047 and 2048 gave logits within 7.2e-2 of the dense arm's (largest
logit 5.14, tolerance 8 bf16 eps of it = 1.6e-1; the kernel before PR 28 read
5.5e-2: p now enters the second product in bf16), and the compiled step held
the Mosaic call; on the token-major pool (PR 30, 2026-09-28) 5.6e-2 of 1.5e-1.
tests/test_tpu_aot.py compiles it for a v5e from this host at D=64, D=128 and
the serving cells' exact shape.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.paged_attention import pages_per_group, paged_decode_attention


def _scatter_pages(seqs, tables, block_size, num_pool_blocks):
    """A [B, S, H, D] sequence layout a layer -> the token-major paged pool
    [L, NB, BS, H * Dp], head h in lanes [h * Dp, h * Dp + D) of a row."""
    B, S, H, D = seqs[0].shape
    dp = llama.pool_head_dim(D)
    pool = np.zeros((len(seqs), num_pool_blocks, block_size, H, dp), np.float32)
    for li, seq in enumerate(seqs):
        for b in range(B):
            for s in range(S):
                pool[li, tables[b, s // block_size], s % block_size, :, :D] = seq[b, s]
    return jnp.asarray(pool.reshape(*pool.shape[:3], H * dp))


def _case(lengths, *, g=4, Hkv=8, D=128, BS=16, max_blocks, dtype=jnp.bfloat16,
          empty=(), layers=1, layer=0):
    return dict(lengths=lengths, g=g, Hkv=Hkv, D=D, BS=BS, max_blocks=max_blocks,
                dtype=dtype, empty=empty, layers=layers, layer=layer)


# The cell's head shape (8 KV heads, 4 query heads each, 128 wide, block 16,
# bfloat16) walks 16 pages = 256 tokens a group; the small float32 shape
# (block 8) 32 pages = 256 tokens, or the whole table if that is narrower.
_CASES = {
    # the three cases this test had: a 4-block table, one group, exact
    "g1": _case([5, 17, 32], g=1, Hkv=2, D=16, BS=8, max_blocks=4, dtype=jnp.float32),
    "g2": _case([5, 17, 32], g=2, Hkv=2, D=16, BS=8, max_blocks=4, dtype=jnp.float32),
    "g4": _case([5, 17, 32], g=4, Hkv=2, D=16, BS=8, max_blocks=4, dtype=jnp.float32),
    # several groups, exact: 1 token, a page, a page + 1, one group, one group
    # + 1, a full table that is not a multiple of the group (40 pages of 8
    # against 32 a group; 40 of 16 against 16)
    "f32-groups": _case([1, 8, 9, 256, 257, 320], g=2, Hkv=2, D=16, BS=8,
                        max_blocks=40, dtype=jnp.float32),
    "cell-bf16": _case([1, 16, 17, 256, 257, 640], max_blocks=40),
    # a 64-wide head through the pool's 128-wide tiles
    "cell-bf16-d64": _case([1, 16, 17, 256, 257, 640], D=64, max_blocks=40),
    "olmoe-bf16-g1": _case([1, 17, 129, 320], g=1, Hkv=16, max_blocks=20),
    "table-narrower-than-a-group": _case([1, 17, 64], max_blocks=4),
    "table-of-one-group": _case([255, 256], max_blocks=16),
    # an empty slot as the engine leaves it: lengths + 1 == 1, table all zeros
    # (it reads the garbage page 0; nobody reads its row, which must be finite)
    "empty-slots": _case([1, 1, 33], max_blocks=20, empty=(0, 1)),
    # one layer of a pool that holds other bytes in every layer: length 1, a
    # page edge, a page + 1, a full table. A wrong layer index reads the
    # same pages of another layer
    "first-of-3-layers": _case([1, 16, 17, 96], max_blocks=6, layers=3, layer=0),
    "last-of-3-layers": _case([1, 16, 17, 96], max_blocks=6, layers=3, layer=2),
    "middle-layer-f32-d64": _case([1, 8, 9, 48], g=2, Hkv=2, D=64, BS=8,
                                  max_blocks=6, dtype=jnp.float32, layers=3, layer=1),
    # Nemotron-H's heads: 32 query heads over 2 key-value heads of 128, a
    # group of 16, one of several cache layers, a context past four page groups
    "nemotron-bf16-g16": _case([1, 16, 17, 257, 1130], g=16, Hkv=2, max_blocks=72,
                               layers=2, layer=1),
    "nemotron-f32-g16": _case([1, 9, 300], g=16, Hkv=2, D=128, BS=8, max_blocks=40,
                              dtype=jnp.float32),
    # Laguna's heads: 48 and 72 query heads over 8 key-value heads of 128,
    # groups of 6 and 9 (no power of two; padded to 8 and 16 sublanes)
    "laguna-full-bf16-g6": _case([1, 17, 257, 300], g=6, max_blocks=20, layers=2, layer=1),
    "laguna-window-f32-g9": _case([1, 9, 300], g=9, Hkv=2, D=128, BS=8, max_blocks=40,
                                  dtype=jnp.float32),
}


def _inputs(case, seed=0):
    """q, k_seq, v_seq [B, S, Hkv, D] of the case's layer as float32 arrays of
    values the case's dtype holds exactly (the float32 reference sees what the
    kernel sees), a table with its pages out of order across the pool, and the
    pools of all layers scattered from it in the case's dtype."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(case["lengths"], np.int32)
    B, Hkv, D, BS, mb = len(lengths), case["Hkv"], case["D"], case["BS"], case["max_blocks"]
    NB = B * mb + 1
    tables = rng.permutation(np.arange(1, NB)).reshape(B, mb).astype(np.int32)
    tables[list(case["empty"])] = 0
    to = lambda a: jnp.asarray(a).astype(case["dtype"])
    exact = lambda shape: np.asarray(
        to(rng.standard_normal(shape, np.float32)).astype(jnp.float32))
    q = exact((B, Hkv * case["g"], D))
    k_seqs = [exact((B, mb * BS, Hkv, D)) for _ in range(case["layers"])]
    v_seqs = [exact((B, mb * BS, Hkv, D)) for _ in range(case["layers"])]
    pools = (to(q), to(_scatter_pages(k_seqs, tables, BS, NB)),
             to(_scatter_pages(v_seqs, tables, BS, NB)))
    return q, k_seqs[case["layer"]], v_seqs[case["layer"]], tables, lengths, pools


def _run_kernel(pools, tables, lengths, layer=0):
    # the layer index reaches the kernel traced, as the layer scan hands it over
    run = jax.jit(lambda layer: paged_decode_attention(
        *pools, jnp.asarray(tables), jnp.asarray(lengths), layer=layer,
        interpret=True))
    return np.asarray(run(jnp.int32(layer)).astype(jnp.float32))


def _dense(case, q, k_seq, v_seq, lengths):
    """Dense reference: q position = lengths-1, KV valid prefix = lengths."""
    return np.asarray(llama._cached_attention(
        jnp.asarray(q)[:, None], jnp.asarray(k_seq), jnp.asarray(v_seq),
        jnp.asarray(lengths - 1), jnp.asarray(lengths - 1)[:, None])[:, 0])


# float32: nothing is rounded. bfloat16: p and the output are rounded to 8
# bits, on values up to ~4 (a length-1 row is a row of v itself)
_atol = lambda case: 2e-5 if case["dtype"] == jnp.float32 else 3e-2


@pytest.mark.parametrize("name", list(_CASES))
def test_paged_decode_matches_dense(name):
    case = _CASES[name]
    q, k_seq, v_seq, tables, lengths, pools = _inputs(case)
    out = _run_kernel(pools, tables, lengths, case["layer"])
    ref = _dense(case, q, k_seq, v_seq, lengths)
    real = [b for b in range(len(lengths)) if b not in case["empty"]]
    np.testing.assert_allclose(out[real], ref[real], atol=_atol(case))
    assert np.isfinite(out).all()


@pytest.mark.parametrize("name", ["first-of-3-layers", "last-of-3-layers"])
def test_paged_decode_wrong_layer_is_seen(name):
    """The layered cases mean something: the same call on another layer's
    pages is far from the reference."""
    case = _CASES[name]
    q, k_seq, v_seq, tables, lengths, pools = _inputs(case)
    out = _run_kernel(pools, tables, lengths, 1)
    assert np.abs(out - _dense(case, q, k_seq, v_seq, lengths)).max() > 0.5


def test_paged_decode_group_follows_the_shapes():
    # the cell: 16 pages (256 tokens); OLMoE's 16 KV heads the same; float32
    # pages of 32 KV heads halve it (VMEM); never wider than the table
    assert pages_per_group(16, 128, 8, 2, 128) == 16
    assert pages_per_group(16, 64, 8, 2, 128) == 16
    assert pages_per_group(16, 128, 16, 2, 128) == 16
    assert pages_per_group(16, 128, 32, 4, 128) == 8
    assert pages_per_group(8, 16, 2, 4, 40) == 32
    assert pages_per_group(16, 128, 8, 2, 4) == 4
    assert pages_per_group(256, 128, 8, 2, 8) == 1


@pytest.mark.parametrize("name", ["f32-groups", "cell-bf16"])
def test_paged_decode_never_reads_past_the_last_live_page(name):
    """Table entries past a sequence's last live page may hold anything valid
    (the allocator's stale ids, another sequence's pages): the output is the
    same to the bit, because those pages are not read at all."""
    case = _CASES[name]
    *_, tables, lengths, pools = _inputs(case)
    # the pools are scattered from the ORIGINAL table; only what the kernel is
    # told about the dead entries changes
    stale = tables.copy()
    rng = np.random.default_rng(1)
    for b, n in enumerate(-(-lengths // case["BS"])):
        stale[b, n:] = rng.integers(0, pools[1].shape[1], case["max_blocks"] - n)
    assert (stale != tables).any()
    np.testing.assert_array_equal(_run_kernel(pools, stale, lengths),
                                  _run_kernel(pools, tables, lengths))


def test_forward_paged_kernel_path_matches_gather_path():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    bs = 8
    max_blocks = cfg.max_seq_len // bs
    pool = llama.init_kv_pool(cfg, num_blocks=2 * max_blocks + 1, block_size=bs)
    tables = jnp.asarray(
        np.arange(1, 2 * max_blocks + 1).reshape(2, max_blocks), jnp.int32)

    # prefill (gather path) then one decode step via both paths
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0, cfg.vocab_size)
    _, pool = llama.forward_paged(params, prompt, cfg, pool, tables,
                                  jnp.zeros(2, jnp.int32), bs)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, cfg.vocab_size)
    lens = jnp.full((2,), 11, jnp.int32)
    lg_gather, _ = llama.forward_paged(params, tok, cfg, pool, tables, lens, bs,
                                       use_kernel=False)
    lg_kernel, _ = llama.forward_paged(params, tok, cfg, pool, tables, lens, bs,
                                       use_kernel=True)
    np.testing.assert_allclose(np.asarray(lg_kernel), np.asarray(lg_gather),
                               atol=2e-4)


def _families():
    from ray_tpu.models import moe

    tiny = llama.LlamaConfig.tiny()
    olmoe = moe.MoEConfig(base=tiny, num_experts=4, top_k=2, qk_norm=True)
    return {
        "llama": (tiny, lambda key: llama.init(tiny, key),
                  functools.partial(llama.forward_paged, cfg=tiny),
                  lambda params, tokens: llama.forward(params, tokens, tiny)),
        # OLMoE's block: QK-norm over the whole projected vector, the expert
        # layer with its weights read in place (`moe.unstacked_experts`)
        "olmoe": (tiny, lambda key: moe.init(olmoe, key),
                  functools.partial(moe.forward_paged, cfg=olmoe),
                  lambda params, tokens: moe.forward(params, tokens, olmoe)[0]),
    }


@pytest.mark.parametrize("use_kernel", [False, True], ids=["gathered-view", "kernel"])
@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_prefill_then_decode_steps_give_the_cacheless_logits(family, use_kernel):
    """A prefill and then a token a step through `forward_paged`, the decode
    steps through the gathered view or the kernel (interpreted): the logits of
    the family's cache-less forward over the same tokens. The pool is the
    carried, token-major one, 64-wide heads in 128-wide tiles, its pages out
    of order, rows of unequal length."""
    cfg, init, forward_paged, plain = _families()[family]
    params = init(jax.random.PRNGKey(0))
    B, S, bs, mb, prefill = 2, 14, 4, 4, 9
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    want = np.asarray(plain(params, tokens))
    pool = llama.init_kv_pool(cfg, 1 + B * mb, bs)
    assert pool["k"].shape == (cfg.num_layers, 1 + B * mb, bs, cfg.num_kv_heads * 128)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + B * mb)).reshape(B, mb), jnp.int32)
    step = jax.jit(lambda toks, pool, lengths, kernel: forward_paged(
        params, toks, pool=pool, tables=tables, lengths=lengths, block_size=bs,
        use_kernel=kernel), static_argnums=3)
    got = []
    for start, stop in [(0, prefill)] + [(i, i + 1) for i in range(prefill, S)]:
        logits, pool = step(tokens[:, start:stop], pool, jnp.full((B,), start, jnp.int32),
                            use_kernel and stop - start == 1)
        got.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), want,
                               rtol=1e-4, atol=2e-4)
    # the garbage block took nothing: every position was inside the table
    assert not np.asarray(pool["k"][:, 0]).any()


@pytest.mark.parametrize("branch", ["dense", "flash"])
@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_a_fresh_prefill_over_its_own_rows_is_the_gathered_table_prefill(family, branch):
    """A prefill whose sequences all start at position 0, told so (`fresh`):
    its attention reads the rows in hand and nothing of the pool, and gives
    the logits of the prefill that reads the whole table back AND leaves the
    same pool. Once through `auto_attention`, which off the TPU and under the
    1,024 crossover is the dense [S, S] product, and once through the flash
    forward interpreted (`use_kernel`), the branch a TPU takes from the 1,024
    bucket up and no benchmark check reaches. The shape of an admission: a
    bucket longer than what is live, the head on the last live row, pages out
    of order, 64-wide heads in 128-wide tiles."""
    cfg, init, forward_paged, _ = _families()[family]
    params = init(jax.random.PRNGKey(0))
    B, bucket, bs, mb = 2, 40, 4, 12
    live = jnp.asarray([29, 38], jnp.int32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, bucket), 0, cfg.vocab_size)
    tokens = jnp.where(jnp.arange(bucket)[None] < live[:, None], tokens, 0)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + B * mb)).reshape(B, mb), jnp.int32)
    step = jax.jit(lambda fresh, kernel: forward_paged(
        params, tokens, pool=llama.init_kv_pool(cfg, 1 + B * mb, bs), tables=tables,
        lengths=jnp.zeros(B, jnp.int32), block_size=bs, head_rows=live - 1,
        fresh=fresh, use_kernel=kernel), static_argnums=(0, 1))
    kernel = True if branch == "flash" else None   # None: `auto_attention` decides
    want, want_pool = step(False, None)
    got, got_pool = step(True, kernel)
    assert got.shape == (B, 1, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-4)
    for name in ("k", "v"):
        assert np.asarray(want_pool[name][:, 1:]).any()
        np.testing.assert_allclose(np.asarray(got_pool[name]), np.asarray(want_pool[name]),
                                   rtol=1e-4, atol=2e-4)
    # what names each read in a profile: no pool is read under `prompt_attend`
    text = step.lower(True, kernel).as_text(debug_info=True)
    assert "attn/prompt_attend" in text and "attn/kv_write" in text
    assert "attn/kv_read" not in text
    assert ("flash_attention_fwd" in text) == (branch == "flash")


# a fresh prefill's write (PR 43): (live tokens, bucket, blocks allocated,
# table width), block 4
PAGE_WRITE_CASES = [
    pytest.param((29, 32, 8, 10), id="ends-mid-block"),
    # and a ninth block reserved for the first decoded token
    pytest.param((32, 32, 9, 10), id="fills-its-last-block"),
    pytest.param((9, 32, 3, 10), id="padding-spills-past-the-allocation"),
    pytest.param((21, 32, 6, 6), id="padding-spills-past-the-table"),
]


def assert_same_pool_but_block_0(got: dict, want: dict, written, bs: int) -> None:
    """Two pools hold the SAME values, exactly, in every block but the garbage
    block 0; `written`: the block ids in which a leaf of a row a token holds
    something, and it holds nothing in any other."""
    for name in want:
        if name == "counters":
            continue
        g, w = np.asarray(got[name]), np.asarray(want[name])
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        if w.shape[2] == bs:
            held = np.flatnonzero(w[:, 1:].reshape(w.shape[0], w.shape[1] - 1, -1)
                                  .any(axis=(0, 2))) + 1
            assert sorted(held) == sorted(written), (name, held)


def scatter_index_shapes(text: str) -> set:
    """The index operands' shapes of the scatters in a lowered StableHLO
    text: `2x8x2` is 8 pages a sequence by (layer, block), `2x32x3` 32 rows
    a sequence by (layer, block, row)."""
    return set(re.findall(r"\(tensor<[\dx]+x\w+>, tensor<([\dx]+)xi32>, tensor<[\dx]+x\w+>\) ->",
                          text))


def check_page_write_against_rows(monkeypatch, forward_paged, init_pool, vocab: int, case,
                                  bs: int = 4):
    """What every family's fresh prefill is held to (PR 43). `forward_paged`
    (tokens, pool, tables, lengths, **kw) and `init_pool` (num_blocks, bs) are
    the family's with its weights and configuration bound; `case` one of
    `PAGE_WRITE_CASES`. B = 2 sequences prefilled `fresh`, which writes whole
    pages (the lowered text scatters bucket / bs indices a sequence, not
    bucket), then one decode step; the same again with `llama.writes_pages`
    saying no, which is the row scatter's program: the pools EQUAL outside
    block 0, only the touched blocks written, and the prefill's and the
    step's logits equal."""
    live, bucket, blocks, mb = case
    B = 2
    ids = np.random.default_rng(live).permutation(np.arange(1, 1 + B * blocks)).reshape(B, blocks)
    table = np.zeros((B, mb), np.int32)
    table[:, :blocks] = ids
    tables = jnp.asarray(table)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, bucket), 1, vocab)
    tokens = jnp.where(jnp.arange(bucket)[None] < live, tokens, 0)

    def run():
        prefill = jax.jit(lambda pool: forward_paged(
            tokens, pool, tables, jnp.zeros(B, jnp.int32), block_size=bs,
            head_rows=jnp.full((B,), live - 1, jnp.int32), fresh=True))
        text = prefill.lower(init_pool(1 + B * blocks, bs)).as_text()
        logits, pool = prefill(init_pool(1 + B * blocks, bs))
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
        step, _ = jax.jit(lambda pool: forward_paged(
            nxt, pool, tables, jnp.full((B,), live, jnp.int32), block_size=bs))(pool)
        return scatter_index_shapes(text), logits, pool, step

    by_page, by_row = f"{B}x{bucket // bs}x2", f"{B}x{bucket}x3"
    scatters, logits, pool, step = run()
    assert by_page in scatters and by_row not in scatters, scatters
    monkeypatch.setattr(llama, "writes_pages", lambda *a: False)
    scatters, rows_logits, rows_pool, rows_step = run()
    assert by_row in scatters and by_page not in scatters, scatters
    touched = -(-min(bucket, mb * bs) // bs)
    assert_same_pool_but_block_0(pool, rows_pool, ids[:, :touched].ravel(), bs)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(rows_logits))
    np.testing.assert_array_equal(np.asarray(step), np.asarray(rows_step))


@pytest.mark.parametrize("S, mb", [(32, 8), (32, 5), (16, 1)],
                         ids=["inside-the-table", "past-the-table", "one-page"])
def test_write_pages_is_the_row_scatter_at_page_rows_places(S, mb):
    """`llama.write_pages` alone, on a bfloat16 leaf with float32 rows (cast
    as the row scatter casts them), the layer traced: `leaf.at[layer, blk_idx,
    blk_off].set(rows)` at `page_rows`'s places from position 0, exactly, in
    every block but block 0, which takes the pages whose table entry is 0 and
    those past the table's end; the other layers are not touched."""
    B, bs, row = 2, 16, 256
    leaf = jax.random.normal(jax.random.PRNGKey(0), (3, 12, bs, row), jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(1), (B, S, row), jnp.float32)
    table = np.zeros((B, mb), np.int32)
    table[0, :1], table[1, :min(mb, 2)] = [7], [3, 9][:min(mb, 2)]
    tables, zero = jnp.asarray(table), jnp.zeros(B, jnp.int32)
    got = jax.jit(lambda leaf, layer: llama.write_pages(leaf, layer, tables, rows, bs))(leaf, 1)
    _, blk_idx, blk_off = llama.page_rows(tables, zero, S, bs)
    want = leaf.at[1, blk_idx, blk_off].set(rows.astype(leaf.dtype))
    assert got.dtype == leaf.dtype
    f32 = lambda a: np.asarray(a, np.float32)
    got, want, was = f32(got), f32(want), f32(leaf)
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    np.testing.assert_array_equal(got[::2], was[::2])
    assert (got[1, 7] != was[1, 7]).any() and (got[1, 3] != was[1, 3]).any()
    # block 0 holds ONE of the pages sent there, whole
    sent = [f32(rows[b, j * bs:(j + 1) * bs].astype(leaf.dtype))
            for b in range(B) for j in range(S // bs) if j >= mb or table[b, j] == 0]
    if sent:
        assert any((got[1, 0] == page).all() for page in sent)
    else:
        np.testing.assert_array_equal(got[1, 0], was[1, 0])


@pytest.mark.parametrize("case", PAGE_WRITE_CASES)
@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_a_fresh_prefill_s_pages_leave_the_pool_the_row_scatter_left(monkeypatch, family, case):
    """A prefill told `fresh` whose bucket fills whole blocks writes its keys
    and values as bucket / block_size whole pages (`llama.write_pages`) and
    leaves the pool the row scatter left, EXACTLY, in every block but the
    garbage block 0: a prompt that ends inside a block (the padding behind it
    lies in the block as it did), one that fills its last block beside a block
    reserved for decoding (untouched), a bucket whose padding runs past the
    allocation (table entries 0) or past the table itself (all to block 0).
    Then the next decode step reads either pool to the same logits."""
    cfg, init, forward_paged, _ = _families()[family]
    params = init(jax.random.PRNGKey(0))
    check_page_write_against_rows(
        monkeypatch, lambda tokens, pool, tables, lengths, **kw: forward_paged(
            params, tokens, pool=pool, tables=tables, lengths=lengths, **kw),
        functools.partial(llama.init_kv_pool, cfg), cfg.vocab_size, case)


@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_a_fresh_prefill_that_ends_inside_a_block_writes_rows(family):
    """`fresh` with S % block_size != 0 (an engine whose bucket is no multiple
    of its block size): the rows are no whole pages, so the write is the row
    scatter (`llama.writes_pages`), and the pool and the logits are the table
    program's."""
    cfg, init, forward_paged, _ = _families()[family]
    params = init(jax.random.PRNGKey(0))
    B, S, bs, mb = 2, 30, 4, 8
    assert not llama.writes_pages(True, S, bs) and llama.writes_pages(True, 32, bs)
    assert not llama.writes_pages(False, 32, bs)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + B * mb)).reshape(B, mb), jnp.int32)
    step = jax.jit(lambda fresh: forward_paged(
        params, tokens, pool=llama.init_kv_pool(cfg, 1 + B * mb, bs), tables=tables,
        lengths=jnp.zeros(B, jnp.int32), block_size=bs, fresh=fresh), static_argnums=0)
    scatters = scatter_index_shapes(step.lower(True).as_text())
    assert f"{B}x{S}x3" in scatters and not [sh for sh in scatters if sh.endswith("x2")], scatters
    (got, got_pool), (want, want_pool) = step(True), step(False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-4)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_pool[name][0]), np.asarray(want_pool[name][0]))
        np.testing.assert_allclose(np.asarray(got_pool[name]), np.asarray(want_pool[name]),
                                   rtol=1e-4, atol=2e-4)


@pytest.mark.parametrize("family", ["llama", "olmoe"])
def test_a_prompt_that_continues_a_cached_prefix_reads_it_through_the_table(family):
    """The first two blocks of a prompt prefilled fresh (own rows), then its
    suffix at positions 8.. through the program that reads the table: the
    logits of the cache-less forward over the whole prompt, so what the fresh
    prefill left in the pool is what the table program finds there."""
    cfg, init, forward_paged, plain = _families()[family]
    params = init(jax.random.PRNGKey(0))
    B, S, bs, mb, cached = 2, 19, 4, 6, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    want = np.asarray(plain(params, tokens))
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + B * mb)).reshape(B, mb), jnp.int32)
    step = jax.jit(lambda toks, pool, start, fresh: forward_paged(
        params, toks, pool=pool, tables=tables, lengths=jnp.full((B,), start, jnp.int32),
        block_size=bs, fresh=fresh), static_argnums=(2, 3))
    head, pool = step(tokens[:, :cached], llama.init_kv_pool(cfg, 1 + B * mb, bs), 0, True)
    tail, pool = step(tokens[:, cached:], pool, cached, False)
    assert "attn/kv_read" in step.lower(tokens[:, cached:], pool, cached, False).as_text(
        debug_info=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([head, tail], axis=1)), want,
                               rtol=1e-4, atol=2e-4)


# ---- a window layer's ring (models/laguna.py)

@pytest.mark.parametrize("window, g, dtype", [(512, 9, jnp.bfloat16), (24, 9, jnp.float32),
                                              (40, 6, jnp.float32)],
                         ids=["cell-512-g9", "24-g9", "40-g6"])
def test_window_decode_reads_a_ring_s_live_rows(window, g, dtype):
    """`window_decode_attention` over rings [L, NS, window, row], layer 1 of
    3: a ring that has not come round (1 row, window - 1 rows: the rest hold
    another sequence's rows and are not seen), full rings, a dead row on the
    garbage ring; keys in ring order against a dense softmax over the same
    rows. A window of 512 is four pages of 128 rows, 24 three pages of 8."""
    from ray_tpu.ops.paged_attention import window_decode_attention

    rng = np.random.default_rng(window)
    Hkv, D, L, NS = 2, 128, 3, 6
    to = lambda a: jnp.asarray(a).astype(dtype)
    exact = lambda shape: np.asarray(to(rng.standard_normal(shape, np.float32)).astype(jnp.float32))
    rings = np.array([3, 0, 5, 1, 4], np.int32)
    rows = np.array([1, 1, window - 1, window, window], np.int32)
    q = exact((len(rings), Hkv * g, D))
    k_ring, v_ring = exact((L, NS, window, Hkv * D)), exact((L, NS, window, Hkv * D))
    got = jax.jit(lambda layer: window_decode_attention(
        to(q), to(k_ring), to(v_ring), jnp.asarray(rings), jnp.asarray(rows), layer=layer,
        interpret=True))(jnp.int32(1))
    view = lambda ring: jnp.asarray(ring[1, rings]).reshape(len(rings), window, Hkv, D)
    want = llama._cached_attention(
        jnp.asarray(q)[:, None], view(k_ring), view(v_ring), jnp.asarray(rows - 1),
        jnp.asarray(rows - 1)[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), np.asarray(want), rtol=0,
                               atol=2e-5 if dtype == jnp.float32 else 3e-2)
    # another name than the full layers' kernel in a profile
    text = str(jax.make_jaxpr(lambda: window_decode_attention(
        to(q), to(k_ring), to(v_ring), jnp.asarray(rings), jnp.asarray(rows), layer=1,
        interpret=False))())
    assert "paged_attention_window" in text and "paged_attention_decode" not in text
