"""The sum of some fields over the sum of others, or a pass, from the engine's
records of the WHOLE measured window that no profiler session touched.

`jax.profiler.start_trace`'s Python hooks slow the host while they are on
(PERF.md section 6, PR 35: in the 64-slot cell the decode passes run at 0.7 of
their rate inside the traced seconds), so a field the program keeps with
tracing off (`turn`, `<phase>_s`, `st_taken`, `st_wake`, `st_backlog`) is read
here from the window's records OUTSIDE the session: they say what the program
does, the profiled ones what it does under its tracer.

The window's bounds do not reach a reader, but its `decode` records do: the
harness keeps their `dur_s`, in order, as the series `decode_step_s`
(`engine_records.reduce`), and the ring holds the same numbers. The window's
records are the ring's from the first of that run of `decode` records to the
last. Where the run is not found (the ring turned over since; a run that made
no decode step), or no record in it carries a field of `num`, the metric is
left out.

`read`: the sum of the fields `num` over every such record that carries them
(an `admit` record notes a `turn` too), x `scale`, over the sum of the fields
`den` over the `name` records, or with no `den` over their NUMBER (a mean a
pass). `diag.engine_window` (for people, to hold against `diag.engine_phases`,
which is the traced seconds' own): per record name the count and the sums of
`dur_s`, of every phase, of `turn` and of the streams' `st_*` over the same
records (`st_backlog` too: divide by `n`).
"""

from benchmarks.readers import engine_phase


def window_records(ctx, events) -> list:
    """[(name, t0, dur_s, args)] of the `engine` records from the window's
    first `decode` record to its last, [] where they are not found."""
    durs = ctx.series.get("decode_step_s") or []
    records = [(e[3], e[5], e[6], e[7]) for e in events
               if e[0] == "span" and e[2] == "engine" and isinstance(e[7], dict)]
    at = [k for k, rec in enumerate(records) if rec[0] == "decode"]
    for i in range(len(at) - len(durs) + 1 if durs else 0):
        if all(records[at[i + j]][2] == dur for j, dur in enumerate(durs)):
            return records[at[i]:at[i + len(durs) - 1] + 1]
    return []


def summary(records: list) -> dict:
    out = {}
    for name, _, dur, args in records:
        row = out.setdefault(name, {"n": 0, "dur_s": 0.0})
        row["n"] += 1
        row["dur_s"] += dur
        for key, value in args.items():
            if key == "turn" or key.startswith("st_") or (
                    key.endswith("_s") and key not in engine_phase.NOT_PHASES):
                row[key] = row.get(key, 0) + value
    return out


def read(ctx, num: list, den: list | None = None, name: str = "decode",
         scale: float = 1.0):
    from ray_tpu.util import timeline

    records = [rec for rec in window_records(ctx, timeline.local_events())
               if not rec[3].get("profiled")]
    top = [args[f] for _, _, _, args in records for f in num if f in args]
    if not top:
        return None
    ctx.notes.setdefault("engine_window", summary(records))
    rows = [args for rec, _, _, args in records if rec == name]
    bottom = sum(args.get(f, 0) for args in rows for f in den) if den else len(rows)
    return sum(top) / bottom * scale if bottom else None
