"""The engine thread's time off the CPU, from its own profiled records.

Since ISSUE 35 a `PhaseClock` under a profiler session reads
`time.thread_time()` beside the wall, so a profiled record holds
`<phase>_cpu` beside `<phase>_s`, and one that follows another while the loop
stays busy holds `turn` and `turn_cpu`: the time between the two
(`ray_tpu/util/timeline.py::PhaseLoop`). Wall less CPU of a
phase that makes no blocking call is time the thread was runnable and not
running: the wait for the interpreter lock, or a call that blocked after all.

`read` sums, record by record over the records named in `names`, the wall
less CPU of those of `phases` (bare names: `dispatch`) that the record clocks
and, with `turn`, the whole turn before the record. The metric is their total
(x `scale`) over the span from the first profiled engine record's opening to
the last one's close (`over="span"`: x 100 a share), or over the number of
such records (`over="records"`: x 1000 the mean in ms). The first profiled
record's turn ended where the span begins and
is left out. A program whose records carry no `_cpu` leaves the metric out.

Totals only, no quantile: the benchmark machine's per-thread CPU clock ticks
at 10 ms (a phase reads 0 or 0.01 s of CPU, and wall less CPU of ONE record can
be negative), so only sums over many records mean anything; the wall fields
(`turn`, `<phase>_s`) are exact record by record. And these records are the
traced seconds' own: the profiler's Python hooks slow the host while they are
on, so what is read here is the program under its tracer (`record_window`
reads the fields kept with tracing off from the rest of the window).

`diag.engine_offcpu` (for people, to hold against `diag.engine_phases` and
`breakdown.idle_gaps` of the same run): per record name the count, the sums
of `turn` and `turn_cpu`, and `<phase>_off`, the sum of wall less CPU, for
every phase; on `decode` rows also the sums of the streams' `st_*` counts
(`st_backlog`: its mean over the records); `closure.turn` is every record's
turn together, which is what `diag.engine_phases.closure.loop_overhead_s`
gets by subtraction when the loop never rests, and `closure.cpu_tick_s` the
grain of `time.thread_time()` on this machine (a CPU sum over it is the number
of ticks the sum rests on).
"""

import time

from benchmarks.readers import engine_phase


def cpu_tick(spin_s: float = 0.03) -> float:
    """The smallest step `time.thread_time()` makes while this thread spins."""
    end, last, tick = time.monotonic() + spin_s, time.thread_time(), spin_s
    while time.monotonic() < end:
        c = time.thread_time()
        if c != last:
            tick, last = min(tick, c - last), c
    return tick


def _off(args: dict, phase: str):
    """Wall less CPU of one phase of a record, None where it has no such clock."""
    wall, cpu = args.get(phase + "_s"), args.get(phase + "_cpu")
    return None if wall is None or cpu is None else wall - cpu


def summary(records: list) -> dict:
    out, turns = {}, 0.0
    for k, (name, _, _, args) in enumerate(records):
        row = out.setdefault(name, {"n": 0, "turn": 0.0, "turn_cpu": 0.0})
        row["n"] += 1
        if k:   # the first record's turn lies before the span
            row["turn"] += args.get("turn", 0.0)
            row["turn_cpu"] += args.get("turn_cpu", 0.0)
            turns += args.get("turn", 0.0)
        for key, value in args.items():
            if key.startswith("st_"):
                row[key] = row.get(key, 0) + value
            elif key.endswith("_cpu") and key != "turn_cpu":
                off = _off(args, key[:-len("_cpu")])
                if off is not None:
                    row[key[:-3] + "off"] = row.get(key[:-3] + "off", 0.0) + off
    for row in out.values():
        if "st_backlog" in row:
            row["st_backlog"] /= row["n"]
    if records:
        out["closure"] = {"turn": turns, "cpu_tick_s": cpu_tick()}
    return out


def read(ctx, names: list, phases: list, turn: bool = False,
         scale: float = 1.0, over: str = "span"):
    from ray_tpu.util import timeline

    records = engine_phase.profiled_records(timeline.local_events())
    if not any("cpu" in args for _, _, _, args in records):
        return None
    ctx.notes.setdefault("engine_offcpu", summary(records))
    sums = []
    for k, (name, _, _, args) in enumerate(records):
        if name not in names:
            continue
        parts = [off for off in (_off(args, p) for p in phases) if off is not None]
        if not parts:
            continue
        sums.append(sum(parts) + (args.get("turn", 0.0) if turn and k else 0.0))
    if not sums:
        return None
    if over == "records":
        return sum(sums) / len(sums) * scale
    span = max(t0 + dur for _, t0, dur, _ in records) - min(
        t0 for _, t0, _, _ in records)
    return sum(sums) / span * scale if span > 0 else None
