"""Ring attention: blockwise causal attention with sequence parallelism over ICI.

The reference has NO sequence/context parallelism (SURVEY §2.5 marks SP/CP ABSENT —
delegated to training frameworks); this is the TPU-native implementation the rebuild
supplies. Design (blockwise ring attention, per the blockwise-attention literature):

- q/k/v are sharded over the `seq` mesh axis via shard_map.
- Each of the `n` ring steps computes one (q-block × kv-block) tile with streaming
  flash-softmax accumulation (running max m, denominator l, numerator o) in fp32,
  then rotates k/v (and their global positions) to the next ICI neighbor with
  lax.ppermute — compute overlaps the permute under XLA's async collectives.
- Causal masking uses the carried *global* positions, so correctness is independent
  of block layout; fully-masked tiles contribute zero work to the softmax streams.

This scales max sequence length linearly in ring size at constant per-chip memory —
the long-context primitive for train (context parallel) and serve (long prompts).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn_accum(q, k, v, qpos, kpos, o, m, l):
    """One flash-attention tile: accumulate (o, m, l) with q:[B,Sq,Hq,D] k/v:[B,Sk,Hkv,D]."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) / math.sqrt(D)
    mask = qpos[:, None, None, :, None] >= kpos[:, None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard fully-masked rows (m_new == NEG_INF): keep them at zero contribution
    alive = m_new > NEG_INF / 2
    m_safe = jnp.where(alive, m_new, 0.0)
    correction = jnp.where(alive, jnp.exp(m - m_safe), 0.0)
    p = jnp.exp(jnp.where(mask, scores - m_safe[..., None], NEG_INF))
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v).astype(jnp.float32)
    o_new = o * correction[..., None] + pv
    return o_new, m_new, l_new


def _ring_attention_sharded(q, k, v, qpos, kpos, axis_name: str):
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    n = jax.lax.psum(1, axis_name)
    o = jnp.zeros((B, Hkv, g, Sq, D), dtype=jnp.float32)
    m = jnp.full((B, Hkv, g, Sq), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((B, Hkv, g, Sq), dtype=jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        o, m, l, k, v, kpos = carry
        o, m, l = _block_attn_accum(q, k, v, qpos, kpos, o, m, l)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        kpos = jax.lax.ppermute(kpos, axis_name, perm)
        return o, m, l, k, v, kpos

    o, m, l, *_ = jax.lax.fori_loop(0, n, step, (o, m, l, k, v, kpos))
    out = o / jnp.maximum(l[..., None], 1e-30)
    # [B,Hkv,g,Sq,D] -> [B,Sq,Hq,D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, seq_axis: str = "seq", positions=None):
    """Causal ring attention over the mesh's sequence axis.

    q/k/v: [B, S, H, D] global shapes, logically sharded [B, S/n, H, D] per device.
    """
    B, S, Hq, D = q.shape
    n = mesh.shape[seq_axis]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    pspec = P(None, seq_axis, None, None)
    pos_spec = P(None, seq_axis)

    fn = jax.shard_map(
        partial(_ring_attention_sharded, axis_name=seq_axis),
        mesh=mesh,
        in_specs=(pspec, pspec, pspec, pos_spec, pos_spec),
        out_specs=pspec,
        check_vma=False,
    )
    return fn(q, k, v, positions, positions)


def make_ring_attn_fn(mesh: Mesh, seq_axis: str = "seq"):
    """Adapter with the models.llama attn_fn signature (q, k, v) -> o."""

    def attn_fn(q, k, v):
        return ring_attention(q, k, v, mesh, seq_axis=seq_axis)

    return attn_fn
