"""Paged decode attention as a Pallas TPU kernel.

The serving-side hot op (PAPERS.md ragged/paged attention): one query token
per sequence attends over a KV cache stored in block_size-token PAGES scattered
through a pool. Pages are read IN PLACE, with none of the
[B, max_blocks*block_size] gathered-view materialization the XLA fallback pays
(models/llama.py forward_paged).

How the kernel walks the pool:

* One grid step a sequence, all heads. The K and V pools stay in HBM, WHOLE,
  every layer of them (`memory_space=pl.ANY`); the block table, the lengths
  and the layer's index ride in scalar-prefetch memory, and the kernel issues
  its own copies. A page is `k_hbm.at[layer, page]`, `[block_size, Hkv * Dp]`:
  ONE contiguous run of the pool (32 KB at the serving cells' shape), all KV
  heads of its tokens side by side. Head h's keys are the lane tiles
  `[:, h * Dp:(h + 1) * Dp]` of the buffer, a static slice; the slices are
  stacked, and a step serves every query head of its sequence: `[Hkv, Gp,
  Dp]` against `[Hkv, group, Dp]`, batched over heads.
* Many pages a step. A GROUP of pages (`pages_per_group`: up to
  `MAX_GROUP_TOKENS` tokens, inside `VMEM_BUDGET`, worked out from the shapes)
  lands in one of two VMEM buffers; the next group's copies start before this
  group's products.
* Live pages only. The in-kernel loop runs to the sequence's own
  `ceil(pages / pages_per_group)`, and inside the last group a page past the
  sequence's end is not copied (its rows of the V buffer are zeroed, so that
  a masked probability of 0 never meets stale bytes). A call's time follows
  the live context, not `slots x max_blocks`; an empty slot (length 1) costs
  one page.
* Operands as stored, statistics in float32. q, k and v enter both products in
  the pool's dtype (`preferred_element_type=float32`); scores, running max,
  denominator and accumulator are float32; p is cast to the pool's dtype for
  the second product. With a bfloat16 pool each product is one MXU pass; with
  float32 inputs nothing is rounded.

Layout contract (`models/llama.py::init_kv_pool` allocates it, `forward_paged`
writes it): a pool is `[L, num_blocks, block_size, Hkv * Dp]`, token major, Dp
= head_dim rounded up to 128 lanes, head h in lanes `[h * Dp, h * Dp +
head_dim)` of a row and zeros in the rest of its tile. Mosaic slices an HBM ref
only in whole lane tiles, which is why a narrow head is ALLOCATED 128 wide;
nothing is padded or copied a call. The kernel takes the whole pool and a
layer index because the model carries the whole pool through its layer scan
and a slice of it would be a copy (ROADMAP S7, PERF.md section 6, PR 30: a
head-major pool `[L, Hkv, NB, BS, D]` made XLA:TPU re-lay the pool around
every layer's write, 80% of a decode step).

Measured on a TPU v5e (my chip runs, PR 28 and PR 30; PERF.md section 6;
scripts/bench_paged_attention_ab.py), one layer's call at the serving cells'
widths (32 slots, 32 query / 8 KV heads of 128, block 16, a 128-block table,
a 4,097-block pool, bfloat16), us a call with 20 live slots of ~485 tokens /
32 slots of ~1,480 / every slot empty:

    this kernel, token-major pool            114 / 322 / 58   (43% / 74% of
                                                              what the K/V bytes
                                                              need at 819 GB/s)
    PR 28's kernel, its head-major pages     118 / 327 / 59
    the copies alone, no products             97 / 301 / 35   (head-major: 99 /
                                                              306 / 39)
    a 2-D product and a carry a head         176 / 504 / 96   (not shipped: the
      in place of the stacked, batched one                    products then bound
                                                              the call)

PR 28, on its head-major pages: the kernel it replaced (grid (B, Hkv,
max_blocks), one (16, 128) page a step through a BlockSpec, float32 operands)
5,345 / 13,296 / 3,333; pages a group 1 -> 8 -> 16: 305 -> 120 -> 116 us
(chat); a copy a (head, page) in place of one over all heads x 1.7-2.4; dead
pages walked x 4.1; float32 operands nothing (the copies bound it: without the
products a call still takes 99 us); jax's own kernel 352-449.

Reference: vLLM's paged_attention CUDA kernel is the analog (the reference
delegates serving to vLLM); jax.experimental.pallas.ops.tpu.paged_attention
keeps head-major pages and copies one (head, page) at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.platform import target_platform

NEG_INF = -1e30   # finite: exp(NEG_INF - m) underflows to 0, no inf - inf
LANES = 128
# of the 16 MiB a v5e kernel gets by default: the four group buffers (K and V,
# two each); scores and probabilities of a group are a few hundred KB beside
VMEM_BUDGET = 8 * 2 ** 20
# measured on a v5e at the serving cells' shape (PERF.md section 6, PR 30; PR
# 28's head-major kernel read the same): a call at 128 / 256 / 512 tokens a
# group takes 113 / 114 / 128 us with 20 live slots of ~485 tokens and 345 /
# 322 / 329 us with 32 of ~1,480; past 256 the last, part-filled group of
# every sequence costs more than longer runs of copies save
MAX_GROUP_TOKENS = 256


def lane_tiles(head_dim: int) -> int:
    """A head's width in the pool and in VMEM: whole 128-lane tiles
    (`llama.pool_head_dim`, the layout's owner, says the same)."""
    return -(-head_dim // LANES) * LANES


def pages_per_group(block_size: int, head_dim: int, num_kv_heads: int,
                    itemsize: int, max_blocks: int) -> int:
    """Pages one loop step fetches and multiplies: the most (a power of two)
    whose tokens stay within MAX_GROUP_TOKENS and whose four buffers
    (K and V, double buffered, all KV heads) fit VMEM_BUDGET; never more than
    the table is wide."""
    page_bytes = 2 * 2 * num_kv_heads * block_size * lane_tiles(head_dim) * itemsize
    pages = 1
    while (2 * pages * block_size <= MAX_GROUP_TOKENS
           and 2 * pages * page_bytes <= VMEM_BUDGET):
        pages *= 2
    return min(pages, max_blocks)


def _decode_kernel(tables_ref, lens_ref, layer_ref,   # scalar-prefetch (SMEM)
                   q_ref,                      # [1, Hkv, Gp, Dp] block
                   k_hbm, v_hbm,               # whole pools [L, NB, BS, Hkv*Dp], in HBM
                   o_ref,                      # [1, Hkv, Gp, Dp] block
                   k_buf, v_buf, sems, *,      # [2, group, Hkv*Dp] x 2, DMA (2, 2)
                   pages: int, block_size: int, max_blocks: int, scale: float):
    """Grid (B,): streaming softmax over the live page groups of sequence b."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    seq_len = lens_ref[b]
    n_pages = pl.cdiv(seq_len, block_size)
    n_groups = pl.cdiv(n_pages, pages)
    group = pages * block_size
    Hkv, gp, dp = q_ref.shape[1:]

    def is_live(gi, j):
        return gi * pages + j < n_pages

    def page_copies(gi, buf, j):
        """The K and the V copy of page j of group gi into buffer `buf`: one
        contiguous [BS, Hkv*Dp] run of the pool each."""
        page = tables_ref[b * max_blocks + gi * pages + j]
        rows = pl.ds(j * block_size, block_size)
        return (pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[buf, rows],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[buf, rows],
                                      sems.at[1, buf]))

    def start(gi, buf):
        for j in range(pages):
            @pl.when(is_live(gi, j))
            def _copy():
                for copy in page_copies(gi, buf, j):
                    copy.start()

            # a page past the end is not copied: p is 0 there, and 0 x stale
            # bytes could be NaN
            @pl.when(jnp.logical_not(is_live(gi, j)))
            def _zero():
                v_buf[buf, pl.ds(j * block_size, block_size)] = jnp.zeros(
                    (block_size, v_buf.shape[2]), v_buf.dtype)

    def wait(gi, buf):
        for j in range(pages):
            @pl.when(is_live(gi, j))
            def _arrived():
                for copy in page_copies(gi, buf, j):
                    copy.wait()

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    def heads(buf_ref, buf):
        """[group, Hkv * Dp] rows of a buffer -> [Hkv, group, Dp]: head h is
        the lane tiles [h * Dp, (h + 1) * Dp) of every row, a static slice."""
        return jnp.stack([buf_ref[buf, :, pl.ds(h * dp, dp)] for h in range(Hkv)])

    q = q_ref[0]                                           # [Hkv, Gp, Dp]

    def step(gi, carry):
        m_prev, l_prev, acc = carry
        buf = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _next():
            start(gi + 1, 1 - buf)

        wait(gi, buf)
        # the heads' slices stacked and ONE batched product a side: a product
        # and a running statistic a head, each carried on its own, took 176 us
        # where this takes 115 (PERF.md section 6, PR 30)
        k, v = heads(k_buf, buf), heads(v_buf, buf)        # [Hkv, group, Dp]
        s = jnp.einsum("hgd,htd->hgt", q, k,
                       preferred_element_type=jnp.float32) * scale
        kpos = gi * group + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        # a group that is walked holds a live key in its first row, so the
        # running max is finite from the first group on
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=2, keepdims=True)
        acc = acc * corr + jnp.einsum("hgt,htd->hgd", p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    stat = (Hkv, gp, 1)
    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros(q.shape, jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _decode_call(q4, k_pool, v_pool, tables, lengths, layer, *, pages: int,
                 scale: float, interpret: bool, name: str = "paged_attention_decode"):
    """q4 [B, Hkv, Gp, Dp] -> [B, Hkv, Gp, Dp]: softmax(scale * q k^T) v over
    layer `layer` of the pools, `pages` pages a group; `name` is the kernel's
    in a profile."""
    B, Hkv, gp, dp = q4.shape
    BS = k_pool.shape[2]
    max_blocks = tables.shape[1]
    kernel = functools.partial(_decode_kernel, pages=pages, block_size=BS,
                               max_blocks=max_blocks, scale=scale)
    q_spec = pl.BlockSpec((1, Hkv, gp, dp), lambda b, *prefetch: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages * BS, Hkv * dp), k_pool.dtype),
            pltpu.VMEM((2, pages * BS, Hkv * dp), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        interpret=interpret,
        name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",))}),
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q4, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths, *, layer,
                           interpret: bool | None = None,
                           name: str = "paged_attention_decode"):
    """q [B, Hq, D]; k/v_pool [L, NB, BS, Hkv * Dp], the whole pools, of which
    layer `layer` (an int or a traced scalar) is read; tables [B, max_blocks]
    (pool block id per sequence block; entries past a sequence's last live
    page are never read); lengths [B] = valid KV tokens (incl. the token being
    decoded). Returns [B, Hq, D]. Dp is D rounded up to 128 lanes, and Hkv is
    the row's width over it. `interpret=None` compiles the kernel when the
    inputs are placed on a TPU and interprets it anywhere else.
    """
    if interpret is None:
        interpret = target_platform(q, k_pool, v_pool) != "tpu"
    B, Hq, D = q.shape
    _, _, BS, row = k_pool.shape
    dp = lane_tiles(D)
    Hkv = row // dp
    g = Hq // Hkv
    gp = -(-g // 8) * 8  # pad the per-kv-head query group to a sublane multiple
    q4 = q.reshape(B, Hkv, g, D).astype(k_pool.dtype)
    # a head under 128 wide sits in the first D of its 128 lanes in the pool
    # (`llama.init_kv_pool`); q's zero lanes meet whatever the others hold
    q4 = jnp.pad(q4, [(0, 0), (0, 0), (0, gp - g), (0, dp - D)])
    pages = pages_per_group(BS, dp, Hkv, k_pool.dtype.itemsize, tables.shape[1])
    out = _decode_call(q4, k_pool, v_pool, tables, lengths, layer, pages=pages,
                       scale=1.0 / math.sqrt(D), interpret=interpret, name=name)
    return out[:, :, :g, :D].reshape(B, Hq, D).astype(q.dtype)


# A window layer's rows (`models/llama.py::window_attend`) are a RING a
# sequence, `[L, NS, window, Hkv * Dp]`: position p at row p % window, so the
# ring holds the last `window` positions and nothing else, whatever the
# sequence's length. Softmax does not care in what order it meets its keys
# (they were rotated before they were cached), so the ring is read as
# `window / RING_PAGE` pages of a pool `[L, NS * window / RING_PAGE, RING_PAGE,
# row]` (a reshape that moves nothing) by the kernel above, under ITS OWN name
# in a profile: a window call's time is divided by a window's bytes, never by
# a whole context's.
RING_PAGE = 128


def window_decode_attention(q, k_ring, v_ring, rings, rows, *, layer,
                            interpret: bool | None = None):
    """q [B, Hq, D]; k/v_ring [L, NS, window, Hkv * Dp], every layer's and
    every sequence's rings, of which ring `rings[b]` [B] of layer `layer` is
    read; rows [B] = the ring's live rows, `min(tokens, window)` with the
    token being decoded among them: rows [0, rows[b]) are attended to, the
    rest (a ring that has not come round yet) are neither copied nor seen.
    Returns [B, Hq, D]."""
    L, NS, W, row = k_ring.shape
    page = math.gcd(W, RING_PAGE)
    per_ring = W // page
    as_pages = lambda ring: ring.reshape(L, NS * per_ring, page, row)
    tables = rings[:, None] * per_ring + jnp.arange(per_ring, dtype=jnp.int32)[None, :]
    return paged_decode_attention(q, as_pages(k_ring), as_pages(v_ring), tables, rows,
                                  layer=layer, interpret=interpret,
                                  name="paged_attention_window")


# ------------------------------------------------------------ latent (MLA)
#
# Absorbed decode over a LATENT pool (`models/kimi_k2.py`): a token caches one
# row `[c_kv (rank) | k_rope | zeros]` of `row` lanes (576 values in 640 at
# the published sizes) and nothing per head. With the key up-projection
# absorbed into the query, every query head attends over ONE shared "KV head"
# of width `row` whose VALUES are the first `rank` lanes of its keys: one copy
# a page where the kernel above makes two, and two products a group,
# `[Hq, row] x [row, tokens]` and `[Hq, tokens] x [tokens, rank]`. Same walk:
# one grid step a sequence, live pages only, many pages a group, double
# buffered, operands as stored, float32 statistics.

# Tokens a group. On a v5e at the latent cell's shape (64 sequences, 98,441
# live tokens, rows of 640 bfloat16 lanes, 64 heads; microseconds a call, the
# least of five timings of 20 x 8 calls, where the rows' bytes need 154):
# 128: 575, 256: 443, 512: 378, 1,024: 382, 2,048 (the whole table one group,
# 5.2 MB of buffers): 355. 512 is the knee (PERF.md section 6, PR 33).
LATENT_GROUP_TOKENS = 512


def latent_pages_per_group(block_size: int, max_blocks: int) -> int:
    pages = 1
    while 2 * pages * block_size <= LATENT_GROUP_TOKENS:
        pages *= 2
    return min(pages, max_blocks)


def _latent_kernel(tables_ref, lens_ref, layer_ref,   # scalar-prefetch (SMEM)
                   q_ref,                      # [1, Hp, row] block
                   lat_hbm,                    # the whole pool [L, NB, BS, row], in HBM
                   o_ref,                      # [1, Hp, rank] block
                   buf, sems, *,               # [2, group, row], DMA (2,)
                   pages: int, block_size: int, max_blocks: int, scale: float):
    """Grid (B,): streaming softmax over the live page groups of sequence b."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    seq_len = lens_ref[b]
    n_pages = pl.cdiv(seq_len, block_size)
    n_groups = pl.cdiv(n_pages, pages)
    group = pages * block_size
    rank = o_ref.shape[2]

    def is_live(gi, j):
        return gi * pages + j < n_pages

    def page_copy(gi, slot, j):
        page = tables_ref[b * max_blocks + gi * pages + j]
        return pltpu.make_async_copy(
            lat_hbm.at[layer, page],
            buf.at[slot, pl.ds(j * block_size, block_size)], sems.at[slot])

    def start(gi, slot):
        for j in range(pages):
            @pl.when(is_live(gi, j))
            def _copy():
                page_copy(gi, slot, j).start()

            # a page past the end is not copied: its rows are values too, and
            # a probability of 0 times stale bytes could be NaN
            @pl.when(jnp.logical_not(is_live(gi, j)))
            def _zero():
                buf[slot, pl.ds(j * block_size, block_size)] = jnp.zeros(
                    (block_size, buf.shape[2]), buf.dtype)

    def wait(gi, slot):
        for j in range(pages):
            @pl.when(is_live(gi, j))
            def _arrived():
                page_copy(gi, slot, j).wait()

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]                                           # [Hp, row]

    def step(gi, carry):
        m_prev, l_prev, acc = carry
        slot = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _next():
            start(gi + 1, 1 - slot)

        wait(gi, slot)
        rows = buf[slot]                                   # [group, row]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = gi * group + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc = acc * corr + jnp.dot(p.astype(rows.dtype), rows[:, :rank],
                                   preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    hp = q.shape[0]
    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full((hp, 1), NEG_INF, jnp.float32), jnp.zeros((hp, 1), jnp.float32),
         jnp.zeros((hp, rank), jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def latent_decode_attention(q, pool, tables, lengths, *, layer, rank: int,
                            scale: float, interpret: bool | None = None):
    """Absorbed latent decode. q [B, Hq, row]: a head's absorbed query
    `[q_nope W_uk^T (rank) | q_rope | zeros]`, laid out as the pool's rows;
    pool [L, NB, BS, row], the whole latent pool, of which layer `layer` (an
    int or a traced scalar) is read; tables [B, max_blocks]; lengths [B] =
    valid tokens (incl. the one being decoded). Returns the heads' outputs in
    the latent space, [B, Hq, rank] = softmax(scale * q rows^T) rows[:, :rank];
    the caller up-projects them (`W_uv`). `row` and `rank` are whole 128-lane
    tiles. `interpret=None` compiles the kernel when the inputs are placed on
    a TPU and interprets it anywhere else."""
    if interpret is None:
        interpret = target_platform(q, pool) != "tpu"
    B, Hq, row = q.shape
    BS, max_blocks = pool.shape[2], tables.shape[1]
    if row != pool.shape[3] or row % LANES or rank % LANES:
        raise ValueError(f"latent rows of {row} lanes (pool {pool.shape[3]}), rank "
                         f"{rank}: both must be whole 128-lane tiles")
    hp = -(-Hq // 8) * 8
    qp = jnp.pad(q.astype(pool.dtype), [(0, 0), (0, hp - Hq), (0, 0)])
    pages = latent_pages_per_group(BS, max_blocks)
    kernel = functools.partial(_latent_kernel, pages=pages, block_size=BS,
                               max_blocks=max_blocks, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, hp, row), lambda b, *prefetch: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hp, rank), lambda b, *prefetch: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, pages * BS, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, hp, rank), q.dtype),
        interpret=interpret,
        name="latent_attention_decode",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",))}),
    )(tables.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), qp, pool)
    return out[:, :Hq]
