"""Flash attention (forward + backward) as Pallas TPU kernels.

The hot-op playbook from /opt/skills/guides/pallas_guide.md applied to the
attention bottleneck: blockwise streaming softmax in VMEM scratch so the [S,S]
score matrix never materializes in HBM. Grid = (batch*heads, q_blocks, k_blocks)
with the k dimension 'arbitrary' (sequential) so (m, l, acc) scratch persists
across k iterations; causally-dead (q_block, k_block) tiles are skipped.

Training support: the op carries a `jax.custom_vjp`. The forward kernel emits
the per-row logsumexp as a residual; the backward pass runs two kernels — one
accumulating dQ over k-blocks, one accumulating dK/dV over q-blocks — using the
standard flash-attention recurrences (P = exp(S - lse), Δ = rowsum(dO∘O),
dS = P∘(dOVᵀ - Δ)). Memory stays O(S·D) per head in both directions.

This replaces the XLA dense attention in models.llama for long sequences —
HBM traffic drops from O(S^2) to O(S*D) per head. The reference has no such
kernel (vLLM/torch own it there); this is the TPU-native equivalent.
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


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                sm_scale: float, block_q: int, block_k: int, causal: bool,
                num_k_blocks: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip tiles strictly above the diagonal band
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [BQ, D]
        k = k_ref[0].astype(jnp.float32)  # [BK, D]
        v = v_ref[0].astype(jnp.float32)  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # [BQ, BK]
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < kv_len, s, NEG_INF)  # mask padded key rows
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        # Masks as f32 arithmetic: Mosaic can't reshape i1 vectors to [BQ, 1],
        # and exp(NEG_INF - x) underflows to exactly 0 anyway (NEG_INF is a
        # finite -1e30, so no inf-inf NaNs).
        alive = (m_new > NEG_INF / 2).astype(jnp.float32)
        m_safe = m_new * alive
        p = jnp.exp(s - m_safe[:, None]) * alive[:, None]
        corr = jnp.exp(m_prev - m_safe) * alive
        l_scr[:] = l_scr[:] * corr + p.sum(axis=1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot(p, v)
        m_scr[:] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        # lse = m + log(l); dead rows (fully masked) keep NEG_INF so the bwd
        # kernels zero their P contributions. Stored [BQ, 1]: Mosaic requires
        # the last two block dims be (8k, 128m) or match the array dims.
        lse_ref[0] = jnp.where(l > 0.0, m_scr[:] + jnp.log(jnp.maximum(l, 1e-30)),
                               NEG_INF)[:, None]


# ---------------------------------------------------------------- backward

def _recompute_p(q, k, lse, qi, ki, *, sm_scale, block_q, block_k, causal,
                 kv_len):
    """Shared bwd-side reconstruction of the probability tile:
    P = exp(S - lse) with kv_len + causal masking, dead rows zeroed.
    One definition so dQ and dK/dV can never disagree on masking."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < kv_len, s, NEG_INF)
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(rows >= cols, s, NEG_INF)
    alive = (lse > NEG_INF / 2).astype(jnp.float32)
    return jnp.exp(s - (lse * alive)[:, None]) * alive[:, None]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, sm_scale: float, block_q: int, block_k: int,
                   causal: bool, num_k_blocks: int, kv_len: int):
    """Grid (BH, nq, nk), k sequential: accumulate dQ for one q block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)        # [BQ, D]
        k = k_ref[0].astype(jnp.float32)        # [BK, D]
        v = v_ref[0].astype(jnp.float32)        # [BK, D]
        do = do_ref[0].astype(jnp.float32)      # [BQ, D]
        lse = lse_ref[0][:, 0].astype(jnp.float32)    # [BQ]
        delta = delta_ref[0][:, 0].astype(jnp.float32)  # [BQ]
        p = _recompute_p(q, k, lse, qi, ki, sm_scale=sm_scale, block_q=block_q,
                         block_k=block_k, causal=causal, kv_len=kv_len)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))  # [BQ, BK]
        ds = p * (dp - delta[:, None]) * sm_scale
        acc_scr[:] = acc_scr[:] + jax.lax.dot(ds, k)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale: float,
                    block_q: int, block_k: int, causal: bool,
                    num_q_blocks: int, kv_len: int):
    """Grid (BH, nk, nq), q sequential: accumulate dK/dV for one k block."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)

    @pl.when(live)
    def _compute():
        k = k_ref[0].astype(jnp.float32)        # [BK, D]
        v = v_ref[0].astype(jnp.float32)        # [BK, D]
        q = q_ref[0].astype(jnp.float32)        # [BQ, D]
        do = do_ref[0].astype(jnp.float32)      # [BQ, D]
        lse = lse_ref[0][:, 0].astype(jnp.float32)    # [BQ]
        delta = delta_ref[0][:, 0].astype(jnp.float32)  # [BQ]
        p = _recompute_p(q, k, lse, qi, ki, sm_scale=sm_scale, block_q=block_q,
                         block_k=block_k, causal=causal, kv_len=kv_len)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------- plumbing

def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params(interpret: bool):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _fwd_call(qbh, kbh, vbh, causal, block_q, block_k, interpret, kv_len):
    BH, Sq, D = qbh.shape
    Sk = kbh.shape[1]
    nq = Sq // block_q
    nk = Sk // block_k
    sm_scale = 1.0 / math.sqrt(D)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, num_k_blocks=nk, kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, q, k: (b, q, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, q, k: (b, k, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, q, k: (b, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, q, k: (b, q, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, q, k: (b, q, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), qbh.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[_vmem((block_q,)), _vmem((block_q,)),
                        _vmem((block_q, D))],
        interpret=interpret,
        name="flash_attention_fwd",
        **_compiler_params(interpret),
    )(qbh, kbh, vbh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bh(qbh, kbh, vbh, causal, block_q, block_k, interpret, kv_len):
    """qbh/kbh/vbh: [BH, S, D] -> [BH, S, D]. kv_len masks padded key rows."""
    o, _ = _fwd_call(qbh, kbh, vbh, causal, block_q, block_k, interpret, kv_len)
    return o


def _flash_bh_fwd(qbh, kbh, vbh, causal, block_q, block_k, interpret, kv_len):
    o, lse = _fwd_call(qbh, kbh, vbh, causal, block_q, block_k, interpret, kv_len)
    return o, (qbh, kbh, vbh, o, lse)


def _flash_bh_bwd(causal, block_q, block_k, interpret, kv_len, res, do):
    qbh, kbh, vbh, o, lse = res
    BH, Sq, D = qbh.shape
    Sk = kbh.shape[1]
    nq = Sq // block_q
    nk = Sk // block_k
    sm_scale = 1.0 / math.sqrt(D)
    # Δ_i = rowsum(dO ∘ O): tiny O(S·D) reduction, fine as plain XLA.
    # Kept [BH, S, 1] like lse (Mosaic block-shape rule).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, num_k_blocks=nk, kv_len=kv_len)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, q, k: (b, q, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, q, k: (b, k, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, q, k: (b, k, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, q, k: (b, q, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, q, k: (b, q, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, q, k: (b, q, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, q, k: (b, q, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), qbh.dtype),
        scratch_shapes=[_vmem((block_q, D))],
        interpret=interpret,
        name="flash_attention_dq",
        **_compiler_params(interpret),
    )(qbh, kbh, vbh, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        causal=causal, num_q_blocks=nq, kv_len=kv_len)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, k, q: (b, k, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, k, q: (b, k, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, k, q: (b, q, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, k, q: (b, q, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, k, q: (b, q, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, k, q: (b, q, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, k, q: (b, k, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, k, q: (b, k, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), kbh.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), vbh.dtype),
        ],
        scratch_shapes=[_vmem((block_k, D)), _vmem((block_k, D))],
        interpret=interpret,
        name="flash_attention_dkv",
        **_compiler_params(interpret),
    )(kbh, vbh, qbh, do, lse, delta)

    return dq, dk, dv


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool | None = None):
    """Drop-in attn_fn for models.llama: q [B,S,Hq,D], k/v [B,S,Hkv,D] (GQA).

    Differentiable (custom VJP with flash backward kernels). `interpret=None`
    compiles the kernel when q/k/v are placed on a TPU and interprets it
    anywhere else (ops/platform.py); callers that know their mesh pass it.
    """
    if interpret is None:
        interpret = target_platform(q, k, v) != "tpu"
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    # GQA: repeat kv heads to match q heads, fold heads into batch. The repeat
    # is outside the custom_vjp, so its adjoint (sum over the group) is
    # handled by normal AD.
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    # pad sequence to block multiples; padded KEY rows are masked inside the
    # kernel (global col >= real length => NEG_INF), padded query rows sliced off
    S_pad = -(-S // block_q) * block_q
    S_pad = -(-S_pad // block_k) * block_k
    if S_pad != S:
        pad = [(0, 0), (0, S_pad - S), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    qbh = q.transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    kbh = k.transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    vbh = v.transpose(0, 2, 1, 3).reshape(B * Hq, S_pad, D)
    obh = _flash_bh(qbh, kbh, vbh, causal, block_q, block_k, interpret, S)
    return obh.reshape(B, Hq, S_pad, D).transpose(0, 2, 1, 3)[:, :S]
