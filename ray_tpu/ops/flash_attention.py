"""Flash attention (forward + backward) as Pallas TPU kernels.

Blockwise streaming softmax in VMEM scratch, so the [S, S] score matrix never
reaches HBM, with a `jax.custom_vjp`: the forward kernel emits the per-row
logsumexp, the backward runs one kernel that accumulates dQ over key tiles and
one that accumulates dK/dV over query tiles (P = exp(S - lse),
delta = rowsum(dO * O), dS = P * (dP - delta)). Memory stays O(S * D) a head.

How the three kernels use the chip:

* Operands in the input's dtype, accumulation in float32. Every product takes
  its tiles as they arrive (`preferred_element_type=float32`); P and dS are
  cast to the operand dtype for the second product; running max, denominator,
  accumulators, `lse` and `delta` stay float32. With bfloat16 inputs each
  product is one MXU pass; with float32 inputs nothing is rounded.
* Tiles from the shapes (`choose_tiles`): the largest tiles that divide the
  padded length, keep Mosaic's (8, 128) layout rule and fit `VMEM_BUDGET`
  with double buffering (1024 x 1024 at S = 4096 in bfloat16: 10 live tiles a
  head where 128 x 128 had 528). No option, no environment variable; an
  explicit `block_q`/`block_k` wins (the tests cross tile edges with it).
* Only live tiles are visited. The grid is (heads, live tile pairs): the
  (query tile, key tile) pairs a causal mask leaves alive are listed at trace
  time and ride in as scalar-prefetch tables that the index maps read, so a
  dead tile costs neither a grid step nor a DMA. Masks (causal, padded keys)
  are applied only in tiles that cross the diagonal or the padded end.
* K and V stay [B * Hkv, S, D]: a query head reads its group's K/V through the
  index map (`head // g`), and dK/dV accumulate over the g query heads of a
  group inside the kernel (group member and query tile share the sequential
  axis). Nothing is repeated in HBM and nothing is summed afterwards.
* `1/sqrt(D)`, or the caller's `scale`, is folded into q before the kernels
  (XLA fuses it into the head transpose that is there anyway; autodiff scales
  dQ the same way), so no kernel multiplies a score tile by it.
* The FORWARD takes two widths: q and k [., S, D] beside v and o [., S, Dv]
  (latent attention's 192 = 128 + 64 rotary beside 128). The kernel's body
  reads its shapes from its refs; the v and o blocks, the accumulator and the
  output are Dv wide, and `choose_tiles` counts both widths in whole 128-lane
  tiles. 192 is no whole number of them and rides as the array's full last
  dimension, which Mosaic compiles for a v5e (tests/test_tpu_aot.py). The
  backward kernels keep one width; differentiating two raises.
* dK/dV works on transposed tiles (S^T = K Q^T), so P^T dO and dS^T Q are plain
  products and `lse`/`delta` broadcast along sublanes as [1, block_q] rows;
  no tile is transposed in any kernel.

Measured on a TPU v5e (my chip runs, PR 25; PERF.md section 6): at the
training cells' shape, [3, 4096, 32/8, 128] bfloat16, a layer's forward takes
3.85 ms, dQ 4.33, dK/dV 5.60 (54%, 72%, 75% of the MXU's peak for the products
each runs); the 128 x 128 kernels they replace took 69, 33 and 48 (ledger,
PR 24). Kernels alone, the tile size is worth x 5.5, visiting live tiles only
x 1.14-1.47, bfloat16 operands x 1.15, K/V by group 4%, diagonal-only masks
under 1%. `train_tok_s_chip`: 8,808 -> 20,853 on one chip, 3,003 -> 7,226 on four.

Every row's first tile holds a live key (key 0 is real and, under a causal
mask, visible to every query), so the running max is finite after the first
tile and fully masked rows need no special case.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.platform import target_platform

NEG_INF = -1e30   # finite: exp(NEG_INF - m) underflows to 0, no inf - inf
_NT = (((1,), (1,)), ((), ()))   # a @ b.T

# ------------------------------------------------------------------ tiles

LANES = 128                      # Mosaic: a block's last dim, and a tile edge
VMEM_BUDGET = 12 * 2 ** 20       # of the 16 MiB a v5e kernel gets by default
# Past 1024 a tile only loses (measured on a v5e at S=4096, D=128: 2048 x 1024
# and 1024 x 2048 are 15% slower than 1024 x 1024): more of every diagonal
# tile is masked work, and there are already only 10 live tiles a head.
MAX_TILE = 1024
# per kernel: [block_q, D] blocks and [block_k, D] blocks it moves (in and
# out), float32 [block_q, D] and [block_k, D] accumulators
_TILE_USE = {"fwd": (2, 2, 1, 0), "dq": (3, 2, 1, 0), "dkv": (2, 4, 0, 2)}


def padded_len(seq_len: int) -> int:
    """The length the kernels see: a multiple of 128 (a lane tile), or of 16
    (a bfloat16 sublane tile) when the whole sequence is one short tile."""
    unit = LANES if seq_len > LANES else 16
    return -(-seq_len // unit) * unit


def tile_vmem_bytes(kernel: str, block_q: int, block_k: int, head_dim: int,
                    itemsize: int, v_dim: int | None = None) -> int:
    """VMEM one grid step holds: double-buffered blocks, accumulators, and two
    float32 score-sized temporaries (what Mosaic keeps live of S, P, dP, dS;
    from lowering `vmem_limit_bytes` until the v5e compile fails, the three
    kernels need 10, 8 and 8 MiB at 1024 x 1024, D=128, bfloat16, where this
    says 10.5, 11 and 12). Widths count in whole 128-lane tiles. `v_dim` is
    the forward's second width (v and o beside q and k): one of its two
    blocks a side, and its accumulator."""
    q_blocks, k_blocks, q_accs, k_accs = _TILE_USE[kernel]
    d = -(-head_dim // LANES) * LANES
    dv = d if v_dim is None else -(-v_dim // LANES) * LANES
    blocks = itemsize * (d + dv) * (q_blocks * block_q + k_blocks * block_k)
    accs = 4 * dv * (q_accs * block_q + k_accs * block_k)
    return blocks + accs + 2 * block_q * block_k * 4


def choose_tiles(seq_len: int, head_dim: int, itemsize: int,
                 kernel: str, v_dim: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) for `kernel` ("fwd", "dq" or "dkv"): the fewest grid
    steps whose tiles divide `padded_len(seq_len)`, are multiples of 128 (or
    the whole padded sequence) and fit VMEM_BUDGET. Among equals the key tile
    is the larger: the forward's per-step cost of the softmax statistics
    follows the query tile (measured: 512 x 1024 runs in 0.6 of the time of
    1024 x 512), and the backward kernels do not care."""
    S = padded_len(seq_len)
    edges = [t for t in range(LANES, min(S, MAX_TILE) + 1, LANES) if S % t == 0]
    edges = edges or [S]
    fits = [(bq * bk, bk, bq) for bq in edges for bk in edges
            if tile_vmem_bytes(kernel, bq, bk, head_dim, itemsize, v_dim) <= VMEM_BUDGET]
    _, bk, bq = max(fits) if fits else (0, edges[0], edges[0])
    return bq, bk


def _live_tiles(seq_len: int, block_q: int, block_k: int, causal: bool,
                window: int | None = None):
    """(query tile, key tile) of every pair with a live entry, row-major.
    With a `window` (query i sees key j iff i - j < window, under the causal
    mask) the live tiles are a BAND: a tile whose last key the tile's first
    query no longer sees is wholly behind it."""
    return [(qi, ki) for qi in range(seq_len // block_q)
            for ki in range(seq_len // block_k)
            if (not causal or ki * block_k <= qi * block_q + block_q - 1)
            and (window is None or ki * block_k + block_k - 1 > qi * block_q - window)]


def _masked(s, q0, k0, *, causal: bool, kv_len: int, q_axis: int,
            window: int | None = None):
    """Scores with dead entries at NEG_INF: keys past the real length, under
    a causal mask keys after their query and, with a `window`, keys that many
    positions or more before it. `q_axis` is the axis of `s` that runs over
    queries (0, or 1 in the transposed dK/dV tile)."""
    if window is not None:
        # ONE predicate, 0 <= qpos - kpos < window, as an unsigned compare (a
        # key after its query wraps past any window): under a band EVERY tile
        # is masked, and three selects and their compares cost the vector
        # unit more than the tile's softmax (PERF.md section 6, PR 48). A
        # padded key lies after every real query, so the causal edge hides it
        ahead = (q0 - k0) + (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
                             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
        return jnp.where(jax.lax.bitcast_convert_type(ahead, jnp.uint32) < window, s, NEG_INF)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    s = jnp.where(kpos < kv_len, s, NEG_INF)
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    return s


def _for_tile(body, qi, ki, *, block_q, block_k, causal, kv_len, seq_len, window=None):
    """Run `body(mask)`: with the masks in a tile that crosses the diagonal,
    the band's far edge or the padded end, without them everywhere else."""
    crosses = []
    if kv_len < seq_len:
        crosses.append((ki + 1) * block_k > kv_len)
    if causal:
        crosses.append(ki * block_k + block_k - 1 > qi * block_q)
    if window is not None:   # the tile's last query no longer sees its first key
        crosses.append(qi * block_q + block_q - 1 - ki * block_k >= window)
    if not crosses:
        return body(None)
    crosses = functools.reduce(jnp.logical_or, crosses)
    mask = functools.partial(_masked, q0=qi * block_q, k0=ki * block_k,
                             causal=causal, kv_len=kv_len, window=window)
    pl.when(crosses)(lambda: body(mask))
    pl.when(jnp.logical_not(crosses))(lambda: body(None))


def _first_key_tile(qi, ki, *, block_q, block_k, window=None, **_):
    """The first key tile a query tile visits: tile 0, or with a `window` the
    one whose predecessor is wholly behind the band."""
    first = ki == 0
    if window is not None:
        first |= ki * block_k - 1 <= qi * block_q - window
    return first


def _last_key_tile(qi, ki, *, block_q, block_k, causal, seq_len, **_):
    last = ki == seq_len // block_k - 1
    if causal:
        last |= (ki + 1) * block_k > qi * block_q + block_q - 1
    return last


# ---------------------------------------------------------------- forward

def _fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, **geom):
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]

    @pl.when(_first_key_tile(qi, ki, **geom))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(mask):
        v = v_ref[0]                                          # [BK, D]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        if mask is not None:
            s = mask(s, q_axis=0)
        m_prev = m_scr[...]                                   # [BQ, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    _for_tile(tile, qi, ki, **geom)

    @pl.when(_last_key_tile(qi, ki, **geom))
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        # stored [BQ, 1]: a block's last two dims are (8k, 128m) or the array's
        lse_ref[0] = m_scr[...] + jnp.log(l)


# ---------------------------------------------------------------- backward

def _bwd_dq_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_scr, **geom):
    """Grid (heads, live tiles by query tile): accumulate dQ over key tiles."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(mask):
        k = k_ref[0]                                          # [BK, D]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        if mask is not None:
            s = mask(s, q_axis=0)
        p = jnp.exp(s - lse_ref[0])                           # lse [BQ, 1]
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_scr[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _for_tile(tile, qi, ki, **geom)

    @pl.when(_last_key_tile(qi, ki, **geom))
    def _finalize():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(ki_tab, gi_tab, qi_tab, k_ref, v_ref, q_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    group: int, **geom):
    """Grid (KV heads, live tiles by key tile, then by the group's query head):
    accumulate dK/dV over the group's query heads and their query tiles. The
    tile is transposed, [BK, BQ]; lse and delta are [1, BQ] rows."""
    t = pl.program_id(1)
    ki, gi, qi = ki_tab[t], gi_tab[t], qi_tab[t]
    block_q, block_k = geom["block_q"], geom["block_k"]

    first_q = qi == 0
    if geom["causal"]:   # the query tile before this one is dead for this key tile
        first_q |= ki * block_k > qi * block_q - 1

    @pl.when(jnp.logical_and(gi == 0, first_q))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(mask):
        q, do = q_ref[0], do_ref[0]                           # [BQ, D]
        st = jax.lax.dot_general(k_ref[0], q, _NT,
                                 preferred_element_type=jnp.float32)  # [BK, BQ]
        if mask is not None:
            st = mask(st, q_axis=1)
        pt = jnp.exp(st - lse_ref[0])                         # lse [1, BQ]
        dv_scr[...] += jax.lax.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])
        dk_scr[...] += jax.lax.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _for_tile(tile, qi, ki, **geom)

    @pl.when(jnp.logical_and(gi == group - 1,
                             qi == geom["seq_len"] // block_q - 1))
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------- plumbing

def _call(kernel, name, tables, in_specs, out_specs, out_shape, scratch, heads,
          interpret):
    """One kernel over the grid (heads, live tiles); `tables` are its int32
    scalar-prefetch columns, one entry a live tile."""
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))}
    tables = [jnp.asarray(np.asarray(col, np.int32)) for col in tables]
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=(heads, len(tables[0])),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        out_shape=out_shape, interpret=interpret, name=name, **params)
    return functools.partial(call, *tables)


def _by_query_tile(S, D, g, bq, bk, causal, window=None):
    """What the kernels that walk live tiles by query tile (forward, dQ)
    share: their tables, and the specs of a [bq, D] query-side block, a
    [bk, D] block of the head's group's K or V, and a [bq, 1] column."""
    tables = tuple(zip(*_live_tiles(S, bq, bk, causal, window)))
    q_spec = pl.BlockSpec((1, bq, D), lambda b, t, qt, kt: (b, qt[t], 0))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, t, qt, kt: (b // g, kt[t], 0))
    col_spec = pl.BlockSpec((1, bq, 1), lambda b, t, qt, kt: (b, qt[t], 0))
    return tables, q_spec, kv_spec, col_spec


def _fwd_call(qbh, kbh, vbh, causal, blocks, interpret, kv_len, window=None):
    """The one kernel that takes two widths: q and k [., S, D], v and o
    [., S, Dv]. The kernel's body reads its shapes from its refs. With a
    `window` it walks the band's tiles and is `flash_attention_window` to a
    profile: its time is never counted with the triangle's."""
    BH, S, D = qbh.shape
    Dv = vbh.shape[2]
    bq, bk = blocks
    tables, q_spec, k_spec, col_spec = _by_query_tile(
        S, D, BH // kbh.shape[0], bq, bk, causal, window)
    o_spec = pl.BlockSpec((1, bq, Dv), q_spec.index_map)   # q's tile, v's width
    v_spec = pl.BlockSpec((1, bk, Dv), k_spec.index_map)
    kernel = functools.partial(_fwd_kernel, block_q=bq, block_k=bk, causal=causal,
                               kv_len=kv_len, seq_len=S, window=window)
    return _call(
        kernel, "flash_attention_fwd" if window is None else "flash_attention_window", tables,
        [q_spec, k_spec, v_spec], [o_spec, col_spec],
        [jax.ShapeDtypeStruct((BH, S, Dv), qbh.dtype),
         jax.ShapeDtypeStruct((BH, S, 1), jnp.float32)],
        [(bq, 1), (bq, 1), (bq, Dv)], BH, interpret)(qbh, kbh, vbh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_bh(qbh, kbh, vbh, causal, blocks, interpret, kv_len):
    """qbh [B*Hq, S, D] (already scaled), kbh [B*Hkv, S, D], vbh [B*Hkv, S,
    Dv] -> [B*Hq, S, Dv]. `blocks`: (block_q, block_k) of fwd, dQ and dK/dV;
    `kv_len` masks padded key rows."""
    o, _ = _fwd_call(qbh, kbh, vbh, causal, blocks[0], interpret, kv_len)
    return o


def _flash_bh_fwd(qbh, kbh, vbh, causal, blocks, interpret, kv_len):
    if vbh.shape[2] != qbh.shape[2]:
        raise NotImplementedError(
            f"flash_attention differentiates at one head width only: q and k are "
            f"{qbh.shape[2]} wide, v {vbh.shape[2]} (the backward kernels keep one width)")
    o, lse = _fwd_call(qbh, kbh, vbh, causal, blocks[0], interpret, kv_len)
    return o, (qbh, kbh, vbh, o, lse)


def _flash_bh_bwd(causal, blocks, interpret, kv_len, res, do):
    qbh, kbh, vbh, o, lse = res
    BH, S, D = qbh.shape
    BHkv = kbh.shape[0]
    g = BH // BHkv
    # delta_i = rowsum(dO * O): an O(S * D) reduction, fine as plain XLA.
    # [BH, S, 1] columns for dQ (as lse is), [BH, 1, S] rows for dK/dV.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    bq, bk = blocks[1]
    tables, q_spec, kv_spec, col_spec = _by_query_tile(S, D, g, bq, bk, causal)
    dq_kernel = functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk,
                                  causal=causal, kv_len=kv_len, seq_len=S)
    dq = _call(
        dq_kernel, "flash_attention_dq", tables,
        [q_spec, kv_spec, kv_spec, q_spec, col_spec, col_spec], q_spec,
        jax.ShapeDtypeStruct((BH, S, D), qbh.dtype), [(bq, D)], BH, interpret,
    )(qbh, kbh, vbh, do, lse, delta)

    bq, bk = blocks[2]
    by_key = sorted((ki, gi, qi) for qi, ki in _live_tiles(S, bq, bk, causal)
                    for gi in range(g))
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, t, kt, gt, qt: (b, kt[t], 0))
    q_spec = pl.BlockSpec((1, bq, D),
                          lambda b, t, kt, gt, qt: (b * g + gt[t], qt[t], 0))
    row_spec = pl.BlockSpec((1, 1, bq),
                            lambda b, t, kt, gt, qt: (b * g + gt[t], 0, qt[t]))
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, group=g, block_q=bq, block_k=bk, causal=causal,
        kv_len=kv_len, seq_len=S)
    dk, dv = _call(
        dkv_kernel, "flash_attention_dkv", tuple(zip(*by_key)),
        [kv_spec, kv_spec, q_spec, q_spec, row_spec, row_spec],
        [kv_spec, kv_spec],
        [jax.ShapeDtypeStruct((BHkv, S, D), kbh.dtype),
         jax.ShapeDtypeStruct((BHkv, S, D), vbh.dtype)],
        [(bk, D), (bk, D)], BHkv, interpret,
    )(kbh, vbh, qbh, do, lse.reshape(BH, 1, S), delta.reshape(BH, 1, S))
    return dq, dk, dv


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_window_bh(qbh, kbh, vbh, window, blocks, interpret, kv_len):
    """`_flash_bh` under a causal mask with a `window`: the forward alone."""
    return _fwd_call(qbh, kbh, vbh, True, blocks, interpret, kv_len, window)[0]


def _no_window_backward(*_):
    raise NotImplementedError(
        "flash_attention(window=) has no backward: the dQ and dK/dV kernels walk the "
        "causal triangle's tiles, and nothing trains a sliding-window layer yet "
        "(ROADMAP R2: a windowed backward)")


_flash_window_bh.defvjp(_no_window_backward, _no_window_backward)


def window_tiles(seq_len: int, window: int, head_dim: int, itemsize: int,
                 v_dim: int | None = None) -> tuple[int, int]:
    """(block_q, block_k) of the banded forward: `choose_tiles`'s, with both
    edges held to the window (in whole lane tiles). A band `window` keys wide
    under tiles of edge t costs `window + t` keys a query and more, so a tile
    past the window only adds masked work: 512 x 512 at a window of 512 visits
    1,024 keys a query where 1,024 x 1,024 visits 2,048."""
    S = padded_len(seq_len)
    if S <= LANES:
        return S, S
    cap = -(-window // LANES) * LANES
    held = lambda t: max(e for e in range(LANES, min(t, cap) + 1, LANES) if S % e == 0)
    bq, bk = choose_tiles(seq_len, head_dim, itemsize, "fwd", v_dim)
    return held(bq), held(bk)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool | None = None,
                    scale: float | None = None, window: int | None = None):
    """Drop-in attn_fn for models.llama: q [B,S,Hq,D], k [B,S,Hkv,D], v
    [B,S,Hkv,Dv] (GQA) -> [B,S,Hq,Dv].

    Differentiable where Dv == D (custom VJP with flash backward kernels);
    the FORWARD also takes a v of another width than q and k (latent
    attention's 192-wide q/k beside 128-wide v, `models/kimi_k2.py`), and
    differentiating that raises. `scale` multiplies the scores (None:
    `1/sqrt(D)`). At equal widths and `scale=None` the function lowers to
    the text it lowered to before it took either (tests/test_ops.py holds
    the hashes). Tiles come from the shapes (`choose_tiles`) unless
    `block_q`/`block_k` name them.
    `interpret=None` compiles the kernel when q/k/v are placed on a TPU and
    interprets it anywhere else (ops/platform.py); callers that know their
    mesh pass it.

    `window` (causal only): query i sees key j iff j <= i and i - j < window,
    the query's own key among the `window`. The forward then visits the
    BAND's tiles alone (`_live_tiles`) under tiles no wider than the window
    (`window_tiles`), masks the tiles the band's far edge crosses, and is
    another kernel to a profile (`flash_attention_window`); it has no
    backward and differentiating it raises. `window=None` is the text it was.
    """
    if interpret is None:
        interpret = target_platform(q, k, v) != "tpu"
    B, S, Hq, D = q.shape
    Dv = v.shape[3]
    if window is not None and not causal:
        raise ValueError("flash_attention(window=) is a causal mask's: query i sees "
                         "the `window` keys that end at its own")
    if block_q is None and block_k is None:
        S_pad = padded_len(S)
        if window is not None:
            blocks = (window_tiles(S, window, D, q.dtype.itemsize, Dv),)
        else:
            blocks = tuple(choose_tiles(S, D, q.dtype.itemsize, kernel, Dv)
                           for kernel in ("fwd", "dq", "dkv"))
    else:
        bq, bk = min(block_q or block_k, S), min(block_k or block_q, S)
        S_pad = -(-S // math.lcm(bq, bk)) * math.lcm(bq, bk)
        blocks = ((bq, bk),) * 3
    # pad to whole tiles; padded KEY rows are masked inside the kernels
    # (position >= S), padded query rows are sliced off
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)

    def heads_first(x):   # [B, S, H, d] -> [B*H, S, d]
        return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2], S_pad, x.shape[3])

    scale = jnp.asarray(1.0 / math.sqrt(D) if scale is None else scale, q.dtype)
    if window is not None:
        obh = _flash_window_bh(heads_first(q * scale), heads_first(k), heads_first(v),
                               window, blocks[0], interpret, S)
    else:
        obh = _flash_bh(heads_first(q * scale), heads_first(k), heads_first(v),
                        causal, blocks, interpret, S)
    return obh.reshape(B, Hq, S_pad, Dv).transpose(0, 2, 1, 3)[:, :S]
