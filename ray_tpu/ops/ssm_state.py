"""One decode step of a selective state-space layer's running state, IN
PLACE in the paged pool (Pallas TPU).

A Mamba-2 layer keeps, a sequence, a float32 state h [heads, P, N] in ITS
page of the pool's leaf `ssm` [L, NS, heads, P, N]
(`models/nemotron_h.py::init_kv_pool`). A decode step is, a head,

    h <- decay * h + dx B^T            decay = exp(dt A) a head, dx = dt x [P]
    y  = h C                           B, C [N]

over every live sequence: elementwise on 2 MiB a sequence and layer, which
the step must read once and write once. Written in `jax.numpy` that is a
gather of the live pages into `f32[B, heads, P, N]`, a pass over it and a
scatter back: the state crosses the HBM's pins six times where two are needed
(PERF.md section 6, PR 45). Here the pool is the kernel's input AND its output
(`input_output_aliases`), a grid step's block is `heads_per_step` heads of
one sequence's page, found through the page ids as a scalar-prefetch operand
(as `ops/paged_attention.py` finds a sequence's pages), and nothing of the
state is written anywhere else. A dead row's page is 0, the garbage page:
several rows may write it, one after another, and nobody reads it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pages, layer, h_ref, decay_ref, dx_ref, b_ref, c_ref, out_ref, y_ref):
    """`heads_per_step` heads of one sequence's page at once: [hb, P, N], P on
    the sublanes and N on the lanes. A head's decay [1, 1] and its B and C [1,
    N] come shaped for their broadcast; dx and y are [hb, P], compact, and
    cross between lanes and sublanes HERE, a tile at a time."""
    h = h_ref[...].astype(jnp.float32)                    # [hb, P, N]
    decay = decay_ref[0]                                  # [hb, 1, 1]
    # a decay of 0 (a sequence at its first position, or a head that forgets
    # everything) takes NOTHING of the page, whatever it holds
    h = jnp.where(decay > 0, decay * h, 0.0) + dx_ref[0][:, :, None] * b_ref[0]
    out_ref[...] = h.astype(out_ref.dtype)
    y_ref[0] = (h * c_ref[0]).sum(axis=-1)                # [hb, P]


def ssm_state_step(ssm, layer, pages, decay, dx, b, c, *, heads_per_step: int = 32,
                   interpret: bool = False):
    """ssm [L, NS, H, P, N] (float32; donated by the caller's step), `layer` an
    int32 scalar (traced or not), `pages` int32 [B]; decay float32 [B, H], dx
    float32 [B, H, P], b and c float32 [B, H, N] (a group's B and C repeated
    under its heads) -> (ssm with `ssm[layer, pages[i]]` advanced one step,
    y float32 [B, H, P]).

    dx goes in COMPACT, `[B, H, P]`: shaped `[B, H, P, 1]` for a ready-made
    broadcast it is padded to 128 lanes by the TPU's tiling, a 100 MB array a
    layer whose making cost as much as the state's own pass (PERF.md section
    6, PR 45). decay `[B, H, 1, 1]` and B, C `[B, H, 1, N]` are padded to 8
    sublanes a head, 13 MB each beside the state's 201."""
    L, NS, H, P, N = ssm.shape
    B = pages.shape[0]
    hb = min(heads_per_step, H)
    if H % hb:
        raise ValueError(f"{H} heads are no whole number of steps of {hb}")
    page = lambda i, j, pages, layer: (layer[0], pages[i], j, 0, 0)
    row4 = lambda i, j, pages, layer: (i, j, 0, 0)
    row3 = lambda i, j, pages, layer: (i, j, 0)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))}
    f32 = jnp.float32
    out, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // hb),
            in_specs=[pl.BlockSpec((None, None, hb, P, N), page),
                      pl.BlockSpec((1, hb, 1, 1), row4), pl.BlockSpec((1, hb, P), row3),
                      pl.BlockSpec((1, hb, 1, N), row4), pl.BlockSpec((1, hb, 1, N), row4)],
            out_specs=[pl.BlockSpec((None, None, hb, P, N), page),
                       pl.BlockSpec((1, hb, P), row3)]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((B, H, P), f32)],
        # operand 2 (after the two scalar-prefetch operands) is the pool
        input_output_aliases={2: 0},
        interpret=interpret, name="ssm_state_step", **params,
    )(pages.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32), ssm,
      decay.astype(f32)[:, :, None, None], dx.astype(f32),
      b.astype(f32)[:, :, None, :], c.astype(f32)[:, :, None, :])
    return out, y
