"""The sum of some fields over the sum of others, over the traced window: from
the engine's profiled records of one name (`engine_phase.profiled_records`),
scaled (`st_detok_cpu` over the four stages' CPU: the detokeniser's share). A
program whose records lack one of the fields, or a window in which the
denominator stays 0 (nothing was streamed), leaves the metric out."""

from benchmarks.readers import engine_phase


def read(ctx, name: str, num: list, den: list, scale: float = 1.0):
    from ray_tpu.util import timeline

    pairs = [(sum(args[f] for f in num), sum(args[f] for f in den))
             for rec, _, _, args in
             engine_phase.profiled_records(timeline.local_events())
             if rec == name and all(f in args for f in (*num, *den))]
    total = sum(d for _, d in pairs)
    return sum(n for n, _ in pairs) / total * scale if total else None
