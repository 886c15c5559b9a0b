"""Traffic as a fixed multiset that the seed permutes and never resamples.

No JAX and no numpy here: the load generator's process imports this file.

A length distribution is cut into equal-probability strata and each stratum
is stood for by its mid quantile, so a mix is a small fixed set of lengths.
Which (prompt, output) pairs a run sends is decided by `pair_design` from
the request's index alone; `--seed` decides the ORDER they are sent in, the
jitter of each arrival inside its slot, and the token content. Two seeds
therefore send the same number of requests and the same multiset of lengths.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def strata(spec: dict) -> list[int]:
    """The mid quantile of each of `spec["strata"]` equal-probability strata
    of the distribution, clipped to [min, max] and rounded to whole tokens."""
    n = int(spec.get("strata", 1))
    lo, hi = spec["min"], spec["max"]
    out = []
    for k in range(n):
        p = (k + 0.5) / n
        if spec["dist"] == "lognormal":
            x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(p))
        elif spec["dist"] == "uniform":
            x = lo + (hi - lo) * p
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(int(round(min(max(x, lo), hi))))
    return out


def _bit_reverse(i: int, n: int) -> int:
    """Position i of a van der Corput order over range(n): a cut-off cycle
    then holds lengths spread over the whole range, not the shortest ones."""
    bits = max(1, (n - 1).bit_length())
    r = int(format(i, f"0{bits}b")[::-1], 2)
    return r if r < n else _bit_reverse(r % n, n)  # n not a power of two


def pair_design(count: int, n_prompt: int, n_out: int) -> list[tuple[int, int]]:
    """(prompt stratum, output stratum) of requests 0..count-1. Each cycle of
    n_prompt requests holds every prompt stratum once; the output stratum it
    meets shifts by 5 a cycle (coprime to 16), so n_out cycles meet every pair
    once. Depends on nothing but the index."""
    pairs = []
    order = sorted(range(n_prompt), key=lambda i: _bit_reverse(i, n_prompt))
    for m in range(count):
        cycle, k = divmod(m, n_prompt)
        i = order[k]
        pairs.append((i, (7 * i + 5 * cycle) % n_out))
    return pairs


def open_loop_plan(traffic: dict, seed: int, seconds: float, slots: int) -> dict:
    """Evenly spaced arrivals at `rate_rps`, each moved by a seeded jitter of
    +-`jitter` of the interval, over a fixed multiset in a seeded order; and
    the requests that fill the system to its steady occupancy beforehand."""
    rate = float(traffic["rate_rps"])
    prompts, outputs = strata(traffic["prompt"]), strata(traffic["output"])
    rng = random.Random(seed)
    n = int(rate * seconds)
    pairs = pair_design(n, len(prompts), len(outputs))
    # The seed orders the requests INSIDE each cycle of len(prompts) arrivals,
    # so every stretch of the window holds every prompt stratum once: shuffled
    # over the whole window, the tokens in flight (and with them the decode
    # step) followed the order, and seeds differed by four times what two runs
    # of one seed do (PERF.md, PR 23).
    cyc = len(prompts)
    for c in range(0, n, cyc):
        block = pairs[c:c + cyc]
        rng.shuffle(block)
        pairs[c:c + cyc] = block
    jit = float(traffic.get("jitter", 0.0))
    window = [{"due_s": (k + 0.5 + rng.uniform(-jit, jit)) / rate,
               "prompt_len": prompts[i], "max_tokens": outputs[j]}
              for k, (i, j) in enumerate(pairs)]
    # Steady occupancy is rate x mean lifetime. Of the requests in flight at a
    # random instant, one of output length o is met in proportion to o and is
    # a uniform share u of the way through: so output strata are taken at even
    # steps of their cumulative length, and u at even steps of (0, 1). The
    # already generated part rides in the prompt, so the KV length is right.
    n_fill = min(slots, int(round(rate * float(traffic["fill"]["lifetime_s"]))))
    total = float(sum(outputs))
    fixed = random.Random(0)           # the same assignment for every seed
    shares = [(k + 0.5) / n_fill for k in range(n_fill)]
    fixed.shuffle(shares)
    fill, acc, j = [], 0.0, 0
    order = sorted(range(len(prompts)), key=lambda i: _bit_reverse(i, len(prompts)))
    for k in range(n_fill):
        target = (k + 0.5) / n_fill * total
        while acc + outputs[j] < target:
            acc += outputs[j]
            j += 1
        left = max(2, int(round(outputs[j] * shares[k])))
        fill.append({"prompt_len": prompts[order[k % len(prompts)]] + outputs[j] - left,
                     "max_tokens": left})
    rng.shuffle(fill)
    return {"mode": "open", "fill": fill, "window": window}


def closed_loop_plan(traffic: dict, seed: int, seconds: float, slots: int) -> dict:
    """`clients` requests always outstanding: more than the engine has slots, as
    an offline queue keeps them, so that a freed slot never waits for a client's
    round trip (with exactly as many, whether the next request met the
    admission pass or waited out a decode step was a race, and throughput had
    two modes 3.4% apart: PERF.md, PR 23). The work is an endless cycle over
    the strata, each cycle in a seeded order; the first request of each client
    has its output cut to a staggered length so that completions, and so the
    prefills that follow them, are spread over the decode steps from the start."""
    clients = int(traffic["clients"])
    prompts, outputs = strata(traffic["prompt"]), strata(traffic["output"])
    rng = random.Random(seed)
    # enough cycles for any window: a request takes over 10 ms
    n = int(traffic.get("max_requests_per_s", 20) * seconds) + clients
    sequence = []
    while len(sequence) < n:
        cyc = pair_design(len(prompts), len(prompts), len(outputs))
        rng.shuffle(cyc)
        sequence += [{"prompt_len": prompts[i], "max_tokens": outputs[j]}
                     for i, j in cyc]
    fill = []
    for k in range(clients):
        item = dict(sequence[k])
        item["max_tokens"] = 1 + (k * item["max_tokens"]) // clients
        fill.append(item)
    return {"mode": "closed", "fill": fill, "sequence": sequence[clients:],
            "clients": clients}
