#!/usr/bin/env python
"""A share's expert layer alone, by how its held rows are moved (PERF.md
section 6, PR 47; PR 38 measured the first of these at Kimi's share).

`moe.moe_mlp` with `experts_held` at the serving cells' widths, the experts
read in place from every layer's stack (`stacked=`), bfloat16, routed by a
random router and bias a layer (sigmoid scores, the chosen renormalised and
scaled, as the three families route):

    nemotron-4096  T 4,096 x H 2,688, 16 of 128 un-gated relu2 experts of 1,856
                   at 6 a token, 23 layers: an even share is 3,072 of 24,576 pairs
    nemotron-2048  the same at T 2,048 (5 of the cell's 16 strata)
    kimi-2048      T 2,048 x H 7,168, 12 of 384 gated experts of 2,048 at 8 a
                   token, 7 layers: an even share is 512 of 16,384 pairs
    xing-512       T 512 x H 3,584, 8 of 64 gated experts of 1,024 at 4 a token,
                   38 layers: an even share is 256 of 2,048 pairs

and these arms, each timed as ONE pass over the share's layers inside one
jitted loop (a layer's output perturbs the next layer's input, so nothing is
hoisted and every layer routes by its own router), the median of 5 repeats:

    every-pair     the uncompacted text: all T x k sorted rows gathered,
                   multiplied, masked and summed back through the inverse
    over-even=N    `moe._held_rows` at a trip of N even shares in whole row
                   tiles: 4 is the parent's text (ISSUE 38's bound), 5/4 what
                   `moe.held_rows_trip` ships, 1 ISSUE 47's (1) as written, 2
                   and 1/2 their neighbours
    live-tiles     ISSUE 47's (2): the parent's chunk of four even shares, the
                   combine's one-hot product taken over the chunk's LIVE
                   1,024-column tiles only (gather and masks stay at the bound)
    gather         ISSUE 47's (3): the shipped trip whose rows are summed back
                   as a gather of [T, k, H] through the inverse
    f32-carry      the shipped trip with the carried sum held in float32

Every arm is checked against `every-pair` on the first layer before it is
timed (bfloat16 in another order: rel_rms under 0.02), and its `rows` must be
that arm's. Needs the chip:

    chiprun --chips 1 -- python3 scripts/bench_moe_share_ab.py

Prints one JSON line an arm and share, and writes them to
chiprun_out/bench_moe_share_ab.jsonl.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import statistics
import sys
import time
from fractions import Fraction
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, moe
from ray_tpu.ops.grouped_matmul import ROW_TILE

SHARES = {
    "nemotron-4096": dict(T=4096, H=2688, m=1856, E=128, held=16, k=6, gated=False,
                          activation="relu2", scaling=2.5, layers=23),
    "nemotron-2048": dict(T=2048, H=2688, m=1856, E=128, held=16, k=6, gated=False,
                          activation="relu2", scaling=2.5, layers=23),
    "kimi-2048": dict(T=2048, H=7168, m=2048, E=384, held=12, k=8, gated=True,
                      activation="silu", scaling=2.827, layers=7),
    "xing-512": dict(T=512, H=3584, m=1024, E=64, held=8, k=4, gated=True,
                     activation="silu", scaling=2.0, layers=38),
}
TINY = dict(T=256, H=128, m=128, E=32, held=4, k=6, gated=False, activation="relu2",
            scaling=2.5, layers=3)   # --tiny: the control flow, on any backend
LIVE_TILE = 1024


def tiles(rows: int) -> int:
    return -(-rows // ROW_TILE) * ROW_TILE


def trip_over_even(over: Fraction):
    """`moe.held_rows_trip` at `over` even shares (4: the parent's bound)."""
    def trip(pairs, count, num_experts):
        even = -(-pairs * count // num_experts)
        return min(pairs, tiles(math.ceil(over * even)))
    return trip


# ------------------------------------------------------- the candidates' texts
def held_rows_variant(combine: str):
    """`moe._held_rows` with its combine replaced: the loop, the dispatch and
    the products are the shipped function's, statement for statement."""

    def _held_rows(yt, order, top_p, group_sizes, rows, trip, products):
        T, k = top_p.shape
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        pairs_all = order.shape[0]
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(pairs_all, dtype=order.dtype), unique_indices=True)
        order = jnp.pad(order, (0, -pairs_all % trip))
        weights = top_p.reshape(T * k)
        trips = (rows + trip - 1) // trip
        carried = jnp.float32 if combine == "f32-carry" else yt.dtype

        def one(i, out):
            lo = i * trip
            with jax.named_scope("moe/dispatch"):
                pairs = jax.lax.dynamic_slice(order, (lo,), (trip,))
                tokens = pairs // k
                xs = yt[tokens]
            with jax.named_scope("moe/experts"):
                sizes = jnp.clip(ends - lo, 0, trip) - jnp.clip(starts - lo, 0, trip)
                live = lo + jnp.arange(trip) < rows
                ys = jnp.where(live[:, None], products(xs, sizes), 0)
            with jax.named_scope("moe/combine"):
                if combine == "f32-carry":
                    w = jnp.where(tokens == jnp.arange(T)[:, None], weights[pairs], 0)
                    return out + jnp.dot(w.astype(yt.dtype), ys,
                                         preferred_element_type=jnp.float32)
                if combine == "gather":
                    # row of each (token, choice) in this trip, or past it
                    at = inverse.reshape(T, k) - lo
                    mine = (at >= 0) & (at < trip) & (inverse.reshape(T, k) < rows)
                    per = ys[jnp.clip(at, 0, trip - 1)]                  # [T, k, H]
                    w = jnp.where(mine, top_p, 0).astype(yt.dtype)
                    return out + (per * w[..., None]).sum(axis=1)
                assert combine == "live-tiles", combine
                tile = min(LIVE_TILE, trip)
                assert trip % tile == 0, (trip, tile)
                wp = weights[pairs]

                def column(j, acc):
                    tk = jax.lax.dynamic_slice(tokens, (j * tile,), (tile,))
                    wj = jax.lax.dynamic_slice(wp, (j * tile,), (tile,))
                    yj = jax.lax.dynamic_slice(ys, (j * tile, 0), (tile, ys.shape[1]))
                    w = jnp.where(tk == jnp.arange(T)[:, None], wj, 0)
                    return acc + jnp.dot(w.astype(yt.dtype), yj,
                                         preferred_element_type=jnp.float32)

                n_live = jnp.clip((rows - lo + tile - 1) // tile, 0, trip // tile)
                acc = jax.lax.fori_loop(0, n_live, column, jnp.zeros(yt.shape, jnp.float32))
                return out + acc.astype(out.dtype)

        out = jax.lax.fori_loop(0, trips, one, jnp.zeros(yt.shape, carried))
        return out.astype(yt.dtype), trips * trip

    return _held_rows


ARMS = [   # (name, the trip in even shares or None for every pair, a combine or None)
    ("every-pair", None, None),
    ("over-even=4 (parent)", Fraction(4), None),
    ("over-even=2", Fraction(2), None),
    ("over-even=5/4 (shipped)", Fraction(*moe.TRIP_OVER_EVEN), None),
    ("over-even=1", Fraction(1), None),
    ("over-even=1/2", Fraction(1, 2), None),
    ("live-tiles over-even=4", Fraction(4), "live-tiles"),
    ("gather over-even=5/4", Fraction(*moe.TRIP_OVER_EVEN), "gather"),
    ("f32-carry over-even=5/4", Fraction(*moe.TRIP_OVER_EVEN), "f32-carry"),
]


def patched(over, combine):
    trip = (lambda pairs, count, num_experts: pairs) if over is None else trip_over_even(over)
    patches = [mock.patch.object(moe, "held_rows_trip", trip)]
    if combine:
        patches.append(mock.patch.object(moe, "_held_rows", held_rows_variant(combine)))
    return patches


# --------------------------------------------------------------- a share's layer
def build(share: dict, seed: int):
    """(cfg, routers {router [L, H, E], router_bias [L, E]}, stacked experts
    [L, held, ...], y [1, T, H])."""
    T, H, m, E, held, L = (share[k] for k in ("T", "H", "m", "E", "held", "layers"))
    cfg = moe.MoEConfig(base=llama.LlamaConfig.tiny(), num_experts=E, top_k=share["k"],
                        norm_topk_prob=True, score_func="sigmoid",
                        routed_scaling=share["scaling"], activation=share["activation"],
                        experts_held=(0, held))
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16

    def stack(key, fan_in, *shape):   # one layer's values under every layer's index
        one = (jax.random.normal(key, shape, jnp.float32) / fan_in ** 0.5).astype(bf)
        return jnp.tile(one[None], (L,) + (1,) * len(shape))

    routers = {"router": (jax.random.normal(ks[0], (L, H, E), jnp.float32) / H ** 0.5).astype(bf),
               "router_bias": 0.1 * jax.random.normal(ks[1], (L, E), jnp.float32)}
    if share["gated"]:
        stacked = {"e_gate": stack(ks[2], H, held, H, m), "e_up": stack(ks[3], H, held, H, m),
                   "e_down": stack(ks[4], m, held, m, H)}
    else:
        stacked = {"e_up_t": stack(ks[2], H, held, m, H), "e_down": stack(ks[4], m, held, m, H)}
    y = jax.random.normal(ks[5], (1, T, H), jnp.float32).astype(bf)
    return cfg, routers, stacked, y


def layer_fn(cfg, platform):
    def layer(y, routers, stacked, i):
        this = {"router": routers["router"][i], "router_bias": routers["router_bias"][i],
                "stack_index": i}
        out, stats = moe.moe_mlp(y, this, cfg, platform=platform, stacked=stacked)
        return out, stats["rows"], stats["moved"]
    return layer


def seconds_a_pass(layer, y, routers, stacked, layers: int, repeats: int):
    """(median, least seconds of a pass over the layers, compile seconds, the
    rows held and the rows moved over the pass)."""
    @jax.jit
    def many(y, routers, stacked):
        def body(i, carry):
            x, rows, moved = carry
            out, r, mv = layer(x, routers, stacked, i)
            # the next layer's input: this one's, nudged by what came out
            return y + (out * 1e-3).astype(y.dtype), rows + r, moved + mv
        return jax.lax.fori_loop(0, layers, body, (y, jnp.int32(0), jnp.int32(0)))

    t0 = time.perf_counter()
    jax.block_until_ready(many(y, routers, stacked))
    compile_s = time.perf_counter() - t0
    _, rows, moved = jax.block_until_ready(many(y, routers, stacked))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(many(y, routers, stacked))
        times.append(time.perf_counter() - t0)
    return statistics.median(times), min(times), compile_s, int(rows), int(moved)


def rel_rms(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.sqrt(jnp.mean((got - want) ** 2) / jnp.mean(want ** 2)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shares", default="", help="comma-separated prefixes; all if empty")
    ap.add_argument("--arms", default="", help="comma-separated prefixes; all if empty")
    ap.add_argument("--tiny", action="store_true",
                    help="one small share on whatever backend there is: the control "
                         "flow only, its times mean nothing")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        print("bench_moe_share_ab: needs a TPU", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": jax.devices()[0].device_kind,
              "count": jax.device_count()}
    shares = {"tiny": TINY} if args.tiny else SHARES
    pick = lambda names, want: [n for n in names
                                if not want or any(n.startswith(w) for w in want.split(","))]
    arms = [a for a in ARMS if a[0] in pick([a[0] for a in ARMS], args.arms)]

    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/bench_moe_share_ab.jsonl", "a")
    for share_name in pick(list(shares), args.shares):
        share = shares[share_name]
        cfg, routers, stacked, y = build(share, args.seed)
        layer = layer_fn(cfg, platform)
        pairs = share["T"] * share["k"]
        # a NEW function a call: `jit` keeps a traced program by the function
        # it was given, and the patches below are no part of that key
        first = lambda: jax.jit(lambda *a: layer(*a))(y, routers, stacked, jnp.int32(0))
        with mock.patch.object(moe, "held_rows_trip", lambda pairs, count, num_experts: pairs):
            want, want_rows, _ = first()
        for name, over, combine in arms:
            line = {"share": share_name, "arm": name, "device": device,
                    "trip": pairs if over is None else
                    trip_over_even(over)(pairs, share["held"], share["E"]),
                    **{k: share[k] for k in ("T", "H", "E", "held", "k", "layers")}}
            try:
                with contextlib.ExitStack() as stack:
                    for p in patched(over, combine):
                        stack.enter_context(p)
                    got, rows, _ = first()
                    line["rel_rms"] = rel_rms(got, want)
                    assert int(rows) == int(want_rows), (int(rows), int(want_rows))
                    assert line["rel_rms"] < 0.02, line["rel_rms"]
                    med, least, compile_s, rows, moved = seconds_a_pass(
                        layer, y, routers, stacked, share["layers"], args.repeats)
                n = share["layers"]
                line.update(ms_a_call=med * 1e3 / n, least_ms=least * 1e3 / n,
                            compile_s=compile_s, rows_a_call=rows / n, moved_a_call=moved / n)
            except Exception as e:   # an arm the compiler refuses is a finding
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
        del routers, stacked, y, want
    return 0


if __name__ == "__main__":
    sys.exit(main())
