"""Paged decode attention as a Pallas TPU kernel.

The serving-side hot op (PAPERS.md ragged/paged attention): one query token
per sequence attends over a KV cache stored in block_size-token PAGES scattered
through a pool. The block table rides in scalar-prefetch memory so the
BlockSpec index_map can route each grid step's page straight from HBM into
VMEM — pages are read IN PLACE, with none of the [B, max_blocks*block_size]
gathered-view materialization the XLA fallback pays
(models/llama.py forward_paged).

Reference: vLLM's paged_attention CUDA kernel is the analog (the reference
delegates serving to vLLM); this is the TPU-native equivalent built on the
pallas playbook (/opt/skills/guides/pallas_guide.md).

Layout contract: pages are [Hkv, num_blocks, block_size, D] per layer (head
major) so a (head, block) pair maps to one VMEM tile of (block_size, D) —
Mosaic's block-shape rule needs the last two dims tile-aligned.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.platform import target_platform

NEG_INF = -1e30


def _decode_kernel(tables_ref, lens_ref,       # scalar-prefetch
                   q_ref, k_ref, v_ref,        # blocks
                   o_ref,                      # output
                   m_scr, l_scr, acc_scr, *,
                   block_size: int, num_blocks: int):
    """Grid (B, Hkv, seq_blocks); the page for (b, i) was DMA'd via the
    table-driven index_map. Streaming softmax over the sequence's pages."""
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]
    live = i * block_size < seq_len  # pages past the ragged end are skipped

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)   # [Gp, D]
        k = k_ref[0, 0].astype(jnp.float32)   # [BS, D]
        v = v_ref[0, 0].astype(jnp.float32)   # [BS, D]
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [Gp, BS]
        kpos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alive = (m_new > NEG_INF / 2).astype(jnp.float32)
        m_safe = m_new * alive
        p = jnp.exp(s - m_safe[:, None]) * alive[:, None]
        corr = jnp.exp(m_prev - m_safe) * alive
        l_scr[:] = l_scr[:] * corr + p.sum(axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot(p, v)
        m_scr[:] = m_new

    @pl.when(i == num_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] /
                       jnp.maximum(l_scr[:], 1e-30)[:, None]).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *,
                           interpret: bool | None = None):
    """q [B, Hq, D]; k/v_pages [Hkv, NB, BS, D]; tables [B, max_blocks]
    (pool block id per sequence block; unused entries must be a valid id —
    their reads are masked); lengths [B] = valid KV tokens (incl. the token
    being decoded). Returns [B, Hq, D]. `interpret=None` compiles the kernel
    when the inputs are placed on a TPU and interprets it anywhere else.
    """
    if interpret is None:
        interpret = target_platform(q, k_pages, v_pages) != "tpu"
    B, Hq, D = q.shape
    Hkv, NB, BS, _ = k_pages.shape
    max_blocks = tables.shape[1]
    g = Hq // Hkv
    gp = -(-g // 8) * 8  # pad the per-kv-head query group to a sublane multiple
    # [B, Hkv, Gp, D] query groups
    q4 = q.reshape(B, Hkv, g, D)
    if gp != g:
        q4 = jnp.pad(q4, [(0, 0), (0, 0), (0, gp - g), (0, 0)])

    kernel = functools.partial(_decode_kernel, block_size=BS,
                               num_blocks=max_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, gp, D), lambda b, h, i, tab, lens: (b, h, 0, 0)),
            # the table routes sequence-block i of sequence b to its pool page
            pl.BlockSpec((1, 1, BS, D), lambda b, h, i, tab, lens: (h, tab[b, i], 0, 0)),
            pl.BlockSpec((1, 1, BS, D), lambda b, h, i, tab, lens: (h, tab[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, D), lambda b, h, i, tab, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp,), jnp.float32),
            pltpu.VMEM((gp,), jnp.float32),
            pltpu.VMEM((gp, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, gp, D), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}),
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q4, k_pages, v_pages)
    return out[:, :, :g].reshape(B, Hq, D)
