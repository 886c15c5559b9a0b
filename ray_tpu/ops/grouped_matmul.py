"""Grouped matrix product (forward + backward) as Pallas TPU kernels.

`grouped_matmul(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]`: the
rows of `lhs` lie sorted by group, group `e` owns rows
`sum(group_sizes[:e]) .. sum(group_sizes[:e + 1])`, and each row is multiplied
by its group's matrix `rhs[e]`. It is the expert layer of a sparse
mixture-of-experts model after its tokens were sorted by expert
(`models/moe.py`): no capacity, no padding to a fixed number of rows an
expert, nothing dropped. `sum(group_sizes)` is `M` where a layer holds every
expert, and LESS where it holds a chip's share of them (PR 33): the rows past
`sum(group_sizes)` are then in no group, no kernel visits a tile that holds
only such rows, and what the result holds there is unspecified (`moe_mlp` masks
them, and its `M` is a bound of rows, not every pair); a pass costs the live
rows' tiles, not `M`'s. The backward kernels are held to `sum(group_sizes) ==
M` (no caller trains a share). `jax.lax.ragged_dot` has the same meaning,
leaves those rows zero, and is the dense path off the TPU (`ops/platform.py`
decides, as it does for attention).

A `jax.custom_vjp`: d lhs is the same product against `rhs` transposed
(contracted in the kernel, no transposed copy), d rhs is per group
`lhs[rows]^T @ d out[rows]`, `[E, K, N]`. The three `pallas_call`s are named
`grouped_matmul_fwd`, `grouped_matmul_dlhs` and `grouped_matmul_drhs`, so a
device trace finds them.

How the kernels use the chip:

* Group sizes are data, shapes are not. Rows are cut into tiles of `tm`; a
  VISIT is one (group, row tile) pair in which the group has a row, listed in
  group order. There are at most `M / tm + E - 1` of them: that bound is the
  grid, the lists (group and tile of each visit, the count of real visits, the
  groups' offsets) ride in as scalar-prefetch tables that the index maps read,
  and a grid step past the last real visit does nothing and moves nothing.
* A group boundary inside a tile is MASKED: both groups visit the tile, each
  writes only its own rows (forward, d lhs) or zeroes the other's rows before
  the product (d rhs). A tile that lies whole inside one group, which most
  do, takes no mask. Consecutive visits of one group leave `rhs[e]` where it
  is (same block index, no DMA), so a pass reads the expert weights once.
* An empty group is never visited by forward and d lhs. d rhs visits it once,
  without a product, to write its zero block.
* Operands in the input's dtype, accumulation in float32
  (`preferred_element_type`): with bfloat16 inputs each product is one MXU
  pass. Forward and d lhs contract over the whole K (or N) in one step; d rhs
  accumulates a `[tk, tn]` float32 block over a group's visits.
* Tiles from the shapes (`choose_tiles`), no option and no environment
  variable; explicit `tiles=` wins (the tests cross tile edges with it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.platform import target_platform

LANES = 128
VMEM_LIMIT = 48 * 2 ** 20        # asked of Mosaic (a v5e core has 128 MiB)
VMEM_BUDGET = 36 * 2 ** 20       # what choose_tiles lets the blocks take of it
ROW_TILE = 256                   # rows a visit; see choose_tiles
_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


# ------------------------------------------------------------------ tiles

def _edges(dim: int, cap: int) -> list[int]:
    """Tile edges for a lane dimension: multiples of 128 that divide `dim`,
    at most `cap`, largest first; the whole dimension when there is none."""
    edges = [t for t in range(LANES, min(dim, cap) + 1, LANES) if dim % t == 0]
    return sorted(edges, reverse=True) or [dim]


def tile_vmem_bytes(kernel: str, tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM one grid step holds: double-buffered blocks plus the float32
    product (and, for d rhs, the float32 accumulator and the masked copy)."""
    if kernel == "drhs":   # lhs [tm, tk], d out [tm, tn], out [tk, tn]
        blocks = 2 * itemsize * (tm * tk + tm * tn + tk * tn)
        return blocks + 2 * 4 * tk * tn + itemsize * tm * min(tk, tn)
    # fwd, dlhs: lhs [tm, tk] (tk is the whole contraction), rhs [tk, tn]
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def choose_tiles(m: int, k: int, n: int, itemsize: int,
                 kernel: str) -> tuple[int, int, int]:
    """(tm, tk, tn) for `kernel` ("fwd", "dlhs" or "drhs") on a product of
    `[m, k]` rows with `[k, n]` matrices (for "dlhs", `k` is the contraction
    and `n` the output width of THAT product, i.e. the forward's N and K).

    Rows: `ROW_TILE`. A tile that a group boundary crosses is computed once
    for each group in it, so a pass costs `m / tm + groups` visits of `tm`
    rows and small tiles waste least. Measured on a v5e at the OLMoE shapes
    (65,536 rows, 64 groups; PERF.md section 6, PR 27): 128 and 256 rows a
    visit take the same time (2.06 and 2.05 ms forward), 512 is 12% slower;
    256 ships, with half the grid steps and table entries of 128.
    Columns: forward and d lhs contract over all of `k` and take the widest
    `tn` that fits VMEM_BUDGET, so `lhs` is re-read `n / tn` times, mostly
    once; d rhs takes the largest `[tk, tn]` accumulator that fits."""
    tm = ROW_TILE if m >= ROW_TILE else -(-m // 16) * 16
    if kernel == "drhs":
        fits = [(tk * tn, tn, tk) for tk in _edges(k, 2048) for tn in _edges(n, 2048)
                if tile_vmem_bytes(kernel, tm, tk, tn, itemsize) <= VMEM_BUDGET]
        _, tn, tk = max(fits) if fits else (0, _edges(n, LANES)[0], _edges(k, LANES)[0])
        return tm, tk, tn
    fits = [tn for tn in _edges(n, 4096)
            if tile_vmem_bytes(kernel, tm, k, tn, itemsize) <= VMEM_BUDGET]
    return tm, k, (fits[0] if fits else _edges(n, LANES)[-1])


# ----------------------------------------------------------------- visits

def _visits(group_sizes, m_tiles: int, tm: int, every_group: bool):
    """The scalar-prefetch tables of a pass: group and row tile of each visit
    (group order; entries past the last real visit repeat it, so they move no
    block), the number of real visits, and the groups' row offsets [E + 1].
    `every_group` gives an empty group one visit (d rhs writes its zeros)."""
    E = group_sizes.shape[0]
    gs = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(gs)
    starts = ends - gs
    first = jnp.minimum(starts // tm, m_tiles - 1)
    count = jnp.where(gs > 0, (ends - 1) // tm - starts // tm + 1, int(every_group))
    vend = jnp.cumsum(count)
    n_visits = m_tiles + E - (0 if every_group else 1)
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32), jnp.maximum(vend[-1] - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(vend, v, side="right").astype(jnp.int32), E - 1)
    tid = first[gid] + (v - (vend - count)[gid])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, tid, vend[-1:], offsets


def _group_rows(offs, g, t, tm):
    """Of visit (g, t): whether the tile lies whole inside the group, and the
    [tm, 1] mask of the tile's rows that are the group's."""
    start, end = offs[g], offs[g + 1]
    rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    whole = jnp.logical_and(start <= t * tm, end >= (t + 1) * tm)
    return whole, jnp.logical_and(rows >= start, rows < end)


# ---------------------------------------------------------------- kernels

def _rows_kernel(gid, tid, nvis, offs, lhs_ref, rhs_ref, out_ref, *, tm, dims):
    """Forward and d lhs: grid (column tiles, visits). out[tile rows of the
    group] = lhs[tile] @ rhs[group] (`dims` says which side of rhs)."""
    v = pl.program_id(1)

    @pl.when(v < nvis[0])
    def _visit():
        g, t = gid[v], tid[v]
        res = jax.lax.dot_general(lhs_ref[...], rhs_ref[0], dims,
                                  preferred_element_type=jnp.float32)
        res = res.astype(out_ref.dtype)
        whole, mine = _group_rows(offs, g, t, tm)

        @pl.when(whole)
        def _():
            out_ref[...] = res

        @pl.when(jnp.logical_not(whole))
        def _():
            out_ref[...] = jnp.where(mine, res, out_ref[...])


def _drhs_kernel(gid, tid, nvis, offs, lhs_ref, dout_ref, out_ref, acc, *,
                 tm, n_visits, mask_lhs):
    """d rhs: grid (K tiles, N tiles, visits). Accumulates lhs[tile]^T @
    d out[tile] over a group's visits and writes the block at its last."""
    v = pl.program_id(2)

    @pl.when(v < nvis[0])
    def _visit():
        g, t = gid[v], tid[v]
        first = jnp.logical_or(v == 0, gid[jnp.maximum(v - 1, 0)] != g)
        last = jnp.logical_or(v == nvis[0] - 1,
                              gid[jnp.minimum(v + 1, n_visits - 1)] != g)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        whole, mine = _group_rows(offs, g, t, tm)

        def add(lhs, dout):
            acc[...] += jax.lax.dot_general(lhs, dout, _TN,
                                            preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _():
            add(lhs_ref[...], dout_ref[...])

        # a tile another group shares: the other's rows count as zero (the
        # narrower operand is masked); an empty group has no row and no product
        @pl.when(jnp.logical_and(jnp.logical_not(whole), offs[g + 1] > offs[g]))
        def _():
            lhs, dout = lhs_ref[...], dout_ref[...]
            if mask_lhs:
                lhs = jnp.where(mine, lhs, jnp.zeros_like(lhs))
            else:
                dout = jnp.where(mine, dout, jnp.zeros_like(dout))
            add(lhs, dout)

        @pl.when(last)
        def _():
            out_ref[0] = acc[...].astype(out_ref.dtype)


# --------------------------------------------------------------- plumbing

def _pad_rows(x, tm):
    pad = -x.shape[0] % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _compiler_params(interpret, semantics):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT)}


def _rows_call(lhs, rhs, group_sizes, *, transpose_rhs, name, tiles, interpret):
    """lhs [M, C] x rhs[e] ([C, W], or [W, C] with `transpose_rhs`) -> [M, W]."""
    M, C = lhs.shape
    W = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if tiles:   # (tm, tk, tn) of the forward: d lhs is K wide, forward N
        tm, tw = tiles[0], tiles[1 if transpose_rhs else 2]
    else:
        tm, _, tw = choose_tiles(M, C, W, lhs.dtype.itemsize,
                                 "dlhs" if transpose_rhs else "fwd")
    lhs = _pad_rows(lhs, tm)
    m_tiles = lhs.shape[0] // tm
    tables = _visits(group_sizes, m_tiles, tm, every_group=False)
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, tw, C), lambda n, v, gid, *_: (gid[v], n, 0))
    else:
        rhs_spec = pl.BlockSpec((1, C, tw), lambda n, v, gid, *_: (gid[v], 0, n))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(W // tw, tables[0].shape[0]),
            in_specs=[pl.BlockSpec((tm, C), lambda n, v, gid, tid, *_: (tid[v], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tw), lambda n, v, gid, tid, *_: (tid[v], n))),
        out_shape=jax.ShapeDtypeStruct((lhs.shape[0], W), lhs.dtype),
        interpret=interpret, name=name,
        **_compiler_params(interpret, ("parallel", "arbitrary")),
    )(*tables, lhs, rhs)
    return out[:M]


def _drhs_call(lhs, dout, group_sizes, num_groups, *, tiles, interpret):
    """Per group lhs[rows]^T @ d out[rows]: [M, K], [M, N] -> [E, K, N]."""
    (M, K), N = lhs.shape, dout.shape[1]
    tm, tk, tn = tiles or choose_tiles(M, K, N, lhs.dtype.itemsize, "drhs")
    lhs, dout = _pad_rows(lhs, tm), _pad_rows(dout, tm)
    m_tiles = lhs.shape[0] // tm
    tables = _visits(group_sizes, m_tiles, tm, every_group=True)
    n_visits = tables[0].shape[0]
    return pl.pallas_call(
        functools.partial(_drhs_kernel, tm=tm, n_visits=n_visits, mask_lhs=tk <= tn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(K // tk, N // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k, n, v, gid, tid, *_: (tid[v], k)),
                pl.BlockSpec((tm, tn), lambda k, n, v, gid, tid, *_: (tid[v], n))],
            out_specs=pl.BlockSpec((1, tk, tn),
                                   lambda k, n, v, gid, *_: (gid[v], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), lhs.dtype),
        interpret=interpret, name="grouped_matmul_drhs",
        **_compiler_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(*tables, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, tiles, interpret):
    return _rows_call(lhs, rhs, group_sizes, transpose_rhs=False,
                      name="grouped_matmul_fwd", tiles=tiles, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, tiles, interpret):
    return _gmm(lhs, rhs, group_sizes, tiles, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(tiles, interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _rows_call(dout, rhs, group_sizes, transpose_rhs=True,
                      name="grouped_matmul_dlhs", tiles=tiles, interpret=interpret)
    drhs = _drhs_call(lhs, dout, group_sizes, rhs.shape[0], tiles=tiles,
                      interpret=interpret)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, platform: str | None = None,
                   tiles: tuple[int, int, int] | None = None,
                   interpret: bool = False, transpose_rhs: bool = False):
    """lhs [M, K] (rows sorted by group), rhs [E, K, N], group_sizes [E]
    (whole numbers that sum to M, or to less: the rows past the sum are in no
    group and their result is unspecified) -> [M, N]. Differentiable in lhs
    and rhs where the sizes sum to M.

    `transpose_rhs`: rhs is [E, N, K] and a group's product is `lhs @
    rhs[e].T` (the d lhs kernel's product, as a forward; not differentiable).
    For a weight whose N is no whole number of 128-lane tiles (1,856): the
    TPU's own layout of an `[E, K, 1856]` parameter puts K on the lanes, and a
    Mosaic call that takes it row-major makes XLA copy the whole stack a step
    (PERF.md section 6, PR 45); stored `[E, 1856, K]` it is read in place.

    The Pallas kernels where the computation is placed on a TPU, or anywhere
    with `interpret=True` (the tests); `jax.lax.ragged_dot` otherwise.
    `platform` is where it runs: callers that know their mesh pass it, None
    derives it from the operands' placement (ops/platform.py). `tiles` is
    (tm, tk, tn) for all three kernels; tk is used by d rhs alone."""
    if platform is None:
        platform = target_platform(lhs, rhs)
    if platform != "tpu" and not interpret:
        return jax.lax.ragged_dot(lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs,
                                  group_sizes.astype(jnp.int32))
    if transpose_rhs:
        return _rows_call(lhs, rhs.astype(lhs.dtype), group_sizes, transpose_rhs=True,
                          name="grouped_matmul_fwd_nt", tiles=tiles, interpret=interpret)
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes, tiles, interpret)
