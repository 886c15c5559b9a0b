#!/usr/bin/env python
"""The paged decode kernel alone, by step (PERF.md section 6, PR 28 and PR 30).

One layer's call of `paged_attention_decode` at the serving cells' widths
(32 query / 8 KV heads of 128, block 16, a 128-block table, a 4,097-block
pool, bfloat16) and three fills of the 32 slots:

    chat   ~20 live slots holding ~9.7k tokens (a `serve-chat-steady` step)
    docs   32 live slots holding ~47k tokens (a `serve-docs-batch` step)
    empty  every slot at length 1

and these arms, each timed as 32 dependent calls inside one jitted loop
(a call's output is the next call's query), the median of 7 repeats:

    parent        the kernel PR 28 replaced: grid (B, Hkv, max_blocks), one
                  (16, 128) page a step through a BlockSpec, float32 operands
    pages=N       the shipped kernel at N pages a group (shipped = what
                  `pages_per_group` works out), on the token-major pool
                  [L, NB, BS, Hkv * D] that the model keeps since PR 30: a
                  page is one contiguous copy, the layer an index (the last
                  of the pool's two layers here)
    head-major    PR 28's kernel on PR 28's pool [Hkv, NB, BS, D], a page Hkv
                  strided pieces: the old layout against the new
    per-head      (head-major) one copy a (head, page) in place of one over
                  all heads
    f32-operands  q, k, v cast to float32 before both products
    walk-dead     every group of the table copied and multiplied, live or not
    copies-only   the copies without the products: what the DMAs alone cost
    upstream      jax.experimental.pallas.ops.tpu.paged_attention at 8 and 32
                  pages a compute block

The head-major arms run a copy of PR 28's kernel body with one thing changed
(`_ablation_kernel`; all but `head-major` itself are PR 28's ablations, on its
layout). The same keys and values fill both layouts. Every arm is checked against dense attention in float32
before it is timed. Needs the chip:

    chiprun --chips 1 -- python3 scripts/bench_paged_attention_ab.py

Prints one JSON line an arm and fill, and writes them to
chiprun_out/bench_paged_attention_ab.jsonl.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import paged_attention as shipped

HBM_BYTES_PER_S = 819e9   # TPU v5e, Google Cloud documentation
B, HQ, HKV, D, BS, MAX_BLOCKS, NB = 32, 32, 8, 128, 16, 128, 4097
NEG_INF = shipped.NEG_INF


# ------------------------------------------------------------------ the fills
def fill(name: str, rng) -> np.ndarray:
    """lengths [B] (valid KV tokens, the decoded one included)."""
    if name == "chat":     # 20 live slots, prompts of the chat mix's scale
        live = np.clip(rng.lognormal(math.log(400), 0.6, 20), 80, 1700)
        live = live * (9700 / live.sum())
        return np.concatenate([live, np.ones(B - 20)]).astype(np.int32)
    if name == "docs":     # 32 live: 1052-1892 prompt tokens + 1-16 decoded
        return (rng.integers(1052, 1893, B) + rng.integers(1, 17, B)).astype(np.int32)
    return np.ones(B, np.int32)


def tables_for(lengths: np.ndarray, rng) -> np.ndarray:
    """Distinct pool pages, out of order, for every live page; 0 elsewhere
    (an empty slot's table is all zeros, as the engine leaves it)."""
    need = -(-lengths // BS)
    need[lengths <= 1] = 0
    ids = rng.permutation(np.arange(1, NB))[: need.sum()]
    tables, at = np.zeros((B, MAX_BLOCKS), np.int32), 0
    for b, n in enumerate(need):
        tables[b, :n] = ids[at:at + n]
        at += n
    return tables


def dense_reference(q, k_pages, v_pages, tables, lengths):
    """float32 attention over the gathered view, [B, Hq, D]."""
    k = k_pages[:, tables].astype(jnp.float32)   # [Hkv, B, MB, BS, D]
    v = v_pages[:, tables].astype(jnp.float32)
    k = k.transpose(1, 0, 2, 3, 4).reshape(B, HKV, MAX_BLOCKS * BS, D)
    v = v.transpose(1, 0, 2, 3, 4).reshape(B, HKV, MAX_BLOCKS * BS, D)
    qg = q.astype(jnp.float32).reshape(B, HKV, HQ // HKV, D)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, k, precision="highest") / math.sqrt(D)
    live = jnp.arange(MAX_BLOCKS * BS)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, None], s, NEG_INF), axis=-1)
    return jnp.einsum("bhgt,bhtd->bhgd", p, v, precision="highest").reshape(B, HQ, D)


def query_groups(q):
    """[B, Hq, D] -> [B, Hkv, 8, D]: a KV head's query heads, padded to 8 rows."""
    g = HQ // HKV
    return jnp.pad(q.reshape(B, HKV, g, D), [(0, 0), (0, 0), (0, 8 - g), (0, 0)])


def query_heads(out):
    """[B, Hkv, 8, D] -> [B, Hq, D]."""
    return out[:, :, :HQ // HKV].reshape(B, HQ, D)


# ------------------------------------------------------------ the parent's arm
def _parent_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, block_size, num_blocks):
    """`ray_tpu/ops/paged_attention.py` as of commit 70e52d8, unchanged."""
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    seq_len = lens_ref[b]

    @pl.when(i * block_size < seq_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) / math.sqrt(q.shape[-1])
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


def parent(q, k_pages, v_pages, tables, lengths):
    q4, gp = query_groups(q), 8
    page = pl.BlockSpec((1, 1, BS, D), lambda b, h, i, tab, lens: (h, tab[b, i], 0, 0))
    rows = pl.BlockSpec((1, 1, gp, D), lambda b, h, i, tab, lens: (b, h, 0, 0))
    out = pl.pallas_call(
        functools.partial(_parent_kernel, block_size=BS, num_blocks=MAX_BLOCKS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, HKV, MAX_BLOCKS),
            in_specs=[rows, page, page], out_specs=rows,
            scratch_shapes=[pltpu.VMEM((gp,), jnp.float32),
                            pltpu.VMEM((gp,), jnp.float32),
                            pltpu.VMEM((gp, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, HKV, gp, D), q.dtype),
        name="paged_attention_decode_parent",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(tables, lengths, q4, k_pages, v_pages)
    return query_heads(out)


# ---------------------------------------------------------- the ablation arms
def _ablation_kernel(tables_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                     k_buf, v_buf, sems, *, pages, per_head, f32_operands,
                     walk_dead, copies_only):
    """PR 28's `_decode_kernel` (head-major pages) with one thing changed a flag."""
    b = pl.program_id(0)
    seq_len = lens_ref[b]
    if walk_dead:
        n_pages = MAX_BLOCKS
    else:
        n_pages = pl.cdiv(seq_len, BS)
    n_groups = pl.cdiv(n_pages, pages)
    group = pages * BS

    def page_copies(gi, buf, j):
        page = tables_ref[b * MAX_BLOCKS + gi * pages + j]
        rows = pl.ds(j * BS, BS)
        heads = [(h,) for h in range(HKV)] if per_head else [(slice(None),)]
        return [pltpu.make_async_copy(hbm.at[h + (page,)], vmem.at[(buf,) + h + (rows,)],
                                      sems.at[i, buf])
                for i, (hbm, vmem) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf)))
                for h in heads]

    def for_pages(gi, buf, live, zero_dead):
        for j in range(pages):
            is_live = gi * pages + j < n_pages

            @pl.when(is_live)
            def _live():
                for copy in page_copies(gi, buf, j):
                    live(copy)

            if zero_dead:
                @pl.when(jnp.logical_not(is_live))
                def _dead():
                    v_buf[buf, :, pl.ds(j * BS, BS)] = jnp.zeros((HKV, BS, D), v_buf.dtype)

    def start(gi, buf):
        for_pages(gi, buf, lambda c: c.start(), True)

    @pl.when(n_groups > 0)
    def _first():
        start(0, 0)

    q = q_ref[0]
    scale = 1.0 / math.sqrt(D)

    def step(gi, carry):
        m_prev, l_prev, acc = carry
        buf = gi % 2

        @pl.when(gi + 1 < n_groups)
        def _next():
            start(gi + 1, 1 - buf)

        for_pages(gi, buf, lambda c: c.wait(), False)
        if copies_only:
            return carry
        k, v, qq = k_buf[buf], v_buf[buf], q
        if f32_operands:
            k, v, qq = (x.astype(jnp.float32) for x in (k, v, q))
        s = jnp.einsum("hgd,htd->hgt", qq, k, preferred_element_type=jnp.float32) * scale
        kpos = gi * group + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < seq_len, s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=2, keepdims=True)
        acc = acc * corr + jnp.einsum("hgt,htd->hgd", p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    stat = q.shape[:2] + (1,)
    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros(q.shape, jnp.float32)))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def ablation(q, k_pages, v_pages, tables, lengths, *, pages, per_head=False,
             f32_operands=False, walk_dead=False, copies_only=False):
    q4 = query_groups(q)
    spec = pl.BlockSpec((1, HKV, 8, D), lambda b, tab, lens: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_ablation_kernel, pages=pages, per_head=per_head,
                          f32_operands=f32_operands, walk_dead=walk_dead,
                          copies_only=copies_only),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((2, HKV, pages * BS, D), k_pages.dtype),
                            pltpu.VMEM((2, HKV, pages * BS, D), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        name="paged_attention_decode_ablation",
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
    )(tables.reshape(-1), lengths, q4, k_pages, v_pages)
    return query_heads(out)


LAYERS = 2   # of the token-major pool; the shipped arms read the last


def token_major(pages):
    """Head-major pages [Hkv, NB, BS, D] -> the model's pool [L, NB, BS, Hkv*D]
    with these pages as its last layer and other bytes before."""
    layer = pages.transpose(1, 2, 0, 3).reshape(NB, BS, HKV * D)
    return jnp.stack([jnp.flip(layer, 0)] * (LAYERS - 1) + [layer])


def shipped_at(pages):
    def f(q, k_pool, v_pool, tables, lengths):
        return query_heads(shipped._decode_call(
            query_groups(q), k_pool, v_pool, tables, lengths, jnp.int32(LAYERS - 1),
            pages=pages, scale=1.0 / math.sqrt(D), interpret=False))
    f.token_major = True
    return f


def upstream(pages):
    from jax.experimental.pallas.ops.tpu.paged_attention import paged_attention

    def f(q, k_pages, v_pages, tables, lengths):
        return paged_attention(q * (1.0 / math.sqrt(D)), k_pages, v_pages, lengths,
                               tables, pages_per_compute_block=pages)
    return f


# ------------------------------------------------------------------- the clock
def seconds_a_call(fn, q, *args, calls: int, repeats: int) -> tuple[float, float]:
    """(median, least) seconds of one call, from `calls` dependent calls in
    one jitted loop; compile and a warm run come first."""
    @jax.jit
    def many(q, *args):
        return jax.lax.fori_loop(0, calls, lambda _, x: fn(x, *args).astype(x.dtype), q)

    t0 = time.perf_counter()
    many(q, *args).block_until_ready()
    compile_s = time.perf_counter() - t0
    many(q, *args).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        many(q, *args).block_until_ready()
        times.append((time.perf_counter() - t0) / calls)
    return statistics.median(times), min(times), compile_s


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arms", default="", help="comma-separated prefixes; all if empty")
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("bench_paged_attention_ab: needs a TPU", file=sys.stderr)
        return 1
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind, "count": jax.device_count()}

    rng = np.random.default_rng(args.seed)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(kq, (B, HQ, D), jnp.bfloat16)
    k_pages = jax.random.normal(kk, (HKV, NB, BS, D), jnp.bfloat16)
    v_pages = jax.random.normal(kv, (HKV, NB, BS, D), jnp.bfloat16)
    head_major, pools = (k_pages, v_pages), (token_major(k_pages), token_major(v_pages))

    own = shipped.pages_per_group(BS, D, HKV, 2, MAX_BLOCKS)
    arms = [("parent", parent)]
    arms += [(f"pages={p}" + (" (shipped)" if p == own else ""), shipped_at(p))
             for p in sorted({1, 2, 4, 8, 16, 32, 64, own})]
    for p in (8, own):
        arms += [(f"head-major pages={p}", functools.partial(ablation, pages=p)),
                 (f"per-head pages={p}", functools.partial(ablation, pages=p, per_head=True)),
                 (f"f32-operands pages={p}", functools.partial(ablation, pages=p, f32_operands=True)),
                 (f"walk-dead pages={p}", functools.partial(ablation, pages=p, walk_dead=True)),
                 (f"copies-only pages={p}", functools.partial(ablation, pages=p, copies_only=True)),
                 (f"copies-only per-head pages={p}",
                  functools.partial(ablation, pages=p, copies_only=True, per_head=True))]
    arms += [(f"upstream pages={p}", upstream(p)) for p in (8, 32)]
    want = [a for a in args.arms.split(",") if a]
    if want:
        arms = [(n, f) for n, f in arms if any(n.startswith(w) for w in want)]

    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/bench_paged_attention_ab.jsonl", "a")
    for fill_name in ("chat", "docs", "empty"):
        lengths_np = fill(fill_name, rng)
        tables_np = tables_for(lengths_np, rng)
        lengths, tables = jnp.asarray(lengths_np), jnp.asarray(tables_np)
        ctx = int(lengths_np[lengths_np > 1].sum())
        live = int((lengths_np > 1).sum())
        need = (2 * ctx * HKV * D * 2 + 2 * B * HQ * D * 2) / HBM_BYTES_PER_S
        ref = dense_reference(q, k_pages, v_pages, tables, lengths)
        for name, fn in arms:
            line = {"fill": fill_name, "live": live, "ctx": ctx, "arm": name,
                    "device": device}
            kv = pools if getattr(fn, "token_major", False) else head_major
            try:
                got = jax.jit(fn)(q, *kv, tables, lengths)
                if "copies-only" not in name:
                    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
                    line["max_err"] = err
                    # walk-dead reads table entries of 0 (the garbage page):
                    # masked, so the answer is the same
                    assert err < 0.05, (name, err)
                med, least, compile_s = seconds_a_call(
                    fn, q, *kv, tables, lengths,
                    calls=args.calls, repeats=args.repeats)
                line.update(us_a_call=med * 1e6, least_us=least * 1e6,
                            compile_s=compile_s, hbm_need_us=need * 1e6,
                            roofline_pct=100 * need / med)
            except Exception as e:   # an arm the compiler refuses is a finding
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
