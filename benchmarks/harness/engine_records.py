"""The serving engine's own records, as the serving cells read them.

Since PR 24 the engine clocks every admission and every decode step itself
and leaves one record for each in the program's timeline ring (`ray_tpu/
util/timeline.py`, category `engine`; PERF.md section 3 lists the fields):
`admit` records carry `outcome`, `queue_wait_s` and `prompt`, `decode`
records `live` and `ctx`, both `dur_s`. The replica and its engine live in
the benchmark's process, so the ring is read in place and nothing of the
engine is wrapped for a span: the loop's methods can be renamed, split or
merged, and as long as the records say what they said the metrics read on.

The ring holds `timeline.MAX_EVENTS` entries of every category together and
drops the oldest. `mark()` at the window's opening and `since(mark)` at its
close fail the run by name when an entry written in between is gone.

Clocks: the ring stamps wall seconds (`time.monotonic()` plus an anchor the
program read once); the generator's records and the window's bounds are
`time.monotonic()`. `mark()` reads an anchor of its own, which differs from
the program's by the microseconds between two reads of the same two clocks.
"""

from __future__ import annotations

import time


def mark() -> dict:
    """Taken when the window opens: the ring's newest sequence number and
    this process's wall-minus-monotonic anchor."""
    from ray_tpu.util import timeline

    events = timeline.local_events()
    return {"seq": events[-1][1] if events else 0,
            "anchor": time.time() - time.monotonic()}


def since(mark: dict) -> list:
    """[(name, t0, dur_s, args)] of every `engine` record written after
    `mark`, oldest first, `t0` on `time.monotonic()`."""
    from ray_tpu.util import timeline

    events = timeline.local_events()
    if len(events) >= timeline.MAX_EVENTS and events[0][1] > mark["seq"] + 1:
        raise SystemExit(
            f"benchmark: the program's timeline ring (ray_tpu/util/timeline.py, "
            f"MAX_EVENTS={timeline.MAX_EVENTS}) no longer holds the window's first "
            f"records: its oldest entry is number {events[0][1]}, the window opened "
            f"after number {mark['seq']} (benchmarks/harness/engine_records.py)")
    return [(e[3], e[5] - mark["anchor"], e[6], e[7]) for e in events
            if e[0] == "span" and e[2] == "engine" and e[1] > mark["seq"]
            and isinstance(e[7], dict)]


def host_path(admitted: list, client: list) -> list:
    """Seconds from a client's send stamp to the engine's enqueue stamp, for
    each admitted request. A record carries no request id yet (ROADMAP D11,
    step 3), so a record finds its request by what it does carry: of the
    client's requests with the record's prompt length, the one sent last
    before the engine's enqueue stamp, `t0 - queue_wait_s`. `admitted` is
    [(t0, args)], `client` the generator's records."""
    by_len: dict = {}
    for r in client:
        if r["sent"]:
            by_len.setdefault(r["prompt_len"], []).append(r["sent"])
    out = []
    for t0, args in admitted:
        t_enq = t0 - args["queue_wait_s"]
        earlier = [s for s in by_len.get(args.get("prompt"), ()) if s <= t_enq]
        if earlier:
            sent = max(earlier)
            by_len[args["prompt"]].remove(sent)
            out.append(t_enq - sent)
    return out


def reduce(records: list, lo: float, hi: float, client: list,
           traced: tuple | None = None) -> tuple[dict, dict]:
    """(series, counters) of the records that begin inside the window
    [lo, hi]: `prefill_s` and `queue_wait_s` over admissions that ended
    `admitted`, `decode_step_s` over decode steps, `host_path_s`. With
    `traced`, the interval the device trace covers, also what a kernel's
    roofline needs of its steps: how many decode steps lie in it
    (`traced_decode_steps`, a step that an edge cuts counting for the part of
    its duration inside, since the trace holds that part of its kernels) and
    their mean context tokens and live slots, weighted the same way."""
    prefill, decode, wait, admitted = [], [], [], []
    steps = ctx = live = 0.0
    for name, t0, dur, args in records:
        if not lo <= t0 <= hi:
            continue
        if name == "admit" and args.get("outcome") == "admitted":
            prefill.append(dur)
            wait.append(args["queue_wait_s"])
            admitted.append((t0, args))
        elif name == "decode":
            decode.append(dur)
            if traced and dur > 0:
                inside = (min(t0 + dur, traced[1]) - max(t0, traced[0])) / dur
                if inside > 0:
                    steps += inside
                    ctx += inside * args["ctx"]
                    live += inside * args["live"]
    series = {"prefill_s": prefill, "decode_step_s": decode, "queue_wait_s": wait,
              "host_path_s": host_path(admitted, client)}
    counters = {}
    if steps:
        counters = {"traced_decode_steps": steps,
                    "traced_context_tokens": ctx / steps,
                    "traced_live_slots": live / steps}
    return series, counters
