"""A quantile, over the engine's own records, of the sum of named phases.

The serving engine clocks each decode step, admission and PD op itself and
leaves one record for it in the program's timeline ring (`ray_tpu/util/
timeline.py::PhaseClock`, category `engine`): `<phase>_s` for each phase,
and `profiled`, true when a profiler session was on from the record's first
instant to its last. The replica and its engine live in the benchmark's
process, so the ring is read in place. Only the profiled records count: they
are exactly those of the interval the device trace covers, so this metric,
`breakdown` and the idle share describe one window, and no bound of that
window has to reach this reader.

A program from before the engine clocked itself has no `PhaseClock`: the
metric is then left out of the line (its file says `"optional": true`).
A program that has the clock and left no such record in the traced window
is a yardstick that went missing: the command fails and says which.

`diag.engine_phases` (for people, to hold against `breakdown.idle_gaps` of
the same run): per record name the count and the sum of every phase over
the profiled records, the largest share of a record that its phases leave
uncovered, and how much of the engine thread's time between the first and
the last profiled record lies in none of them (the loop's own overhead).
"""

from benchmarks.harness.stats import quantile

NOT_PHASES = ("queue_wait_s", "compile_s")   # seconds, but not parts of `dur_s`


def profiled_records(events) -> list:
    """[(name, t0, dur_s, args)] of the `engine` records a profiler session
    covered whole, in the order they were written."""
    return [(e[3], e[5], e[6], e[7]) for e in events
            if e[0] == "span" and e[2] == "engine"
            and isinstance(e[7], dict) and e[7].get("profiled")]


def _phases(args: dict) -> dict:
    return {k: v for k, v in args.items()
            if k.endswith("_s") and k not in NOT_PHASES}


def summary(records: list) -> dict:
    out, worst, covered = {}, 0.0, 0.0
    for name, _, dur, args in records:
        row = out.setdefault(name, {"n": 0, "dur_s": 0.0})
        row["n"] += 1
        row["dur_s"] += dur
        phases = _phases(args)
        for k, v in phases.items():
            row[k] = row.get(k, 0.0) + v
        row["compile_s"] = row.get("compile_s", 0.0) + args.get("compile_s", 0.0)
        if phases and dur > 0:
            worst = max(worst, abs(dur - sum(phases.values())) / dur)
        covered += dur
    if records:
        span = max(t0 + dur for _, t0, dur, _ in records) - min(
            t0 for _, t0, _, _ in records)
        out["closure"] = {"worst_uncovered_share_of_a_record": worst,
                          "span_s": span, "in_records_s": covered,
                          "loop_overhead_s": span - covered}
    return out


def read(ctx, name: str, phases: list, q: float = 50, scale: float = 1000,
         outcome: str | None = None):
    from ray_tpu.util import timeline

    if not hasattr(timeline, "PhaseClock"):
        return None
    records = profiled_records(timeline.local_events())
    ctx.notes["engine_phases"] = summary(records)
    sums = [sum(args.get(p, 0.0) for p in phases)
            for rec, _, _, args in records
            if rec == name and outcome in (None, args.get("outcome"))]
    if not sums:
        raise SystemExit(
            f"benchmark: the traced window holds no profiled engine/{name} record"
            f"{f' with outcome {outcome!r}' if outcome else ''}: the engine's own "
            f"clock (ray_tpu/util/timeline.py::PhaseClock) went missing, or the "
            f"window held no such step (benchmarks/readers/engine_phase.py)")
    return quantile(sums, q) * scale
