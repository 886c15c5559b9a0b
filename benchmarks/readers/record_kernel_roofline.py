"""A kernel's share of its roofline, from the device trace and the engine's
own records, in percent.

`kernel_roofline` counts a kernel's work a decode STEP from the steps' mean
context and live slots. Some work follows what those do not carry: a prefill
kernel's follows the PROMPT, and a window layer's decode kernel reads
`min(length, window)` rows of EACH live sequence, which the sum of the lengths
does not give. The engine's records do (an `admit` record's `prompt`; a
`decode` record's `win_rows` and `win_rings`, the pool's own counters of that
step): the least time the chip could take is a shapes function of the family
module, asked once for each record named `record` that a profiler session
covered whole (`engine_phase.profiled_records`; of admissions, those that ended
`admitted`), with that record's `fields`, summed, over the device time of the
trace's operations whose label matches `ops`. A call that the trace's edge
cuts has no profiled record and leaves part of its kernels in the trace: the
time then holds work the count lacks, and the share reads low, never high. A
program without the kernel or the fields, or a window with no whole record,
leaves the metric out. The counter `<work_fn>_bound` says which of compute and
memory bounded it.
"""

from benchmarks.harness import shapes
from benchmarks.readers import engine_phase


def read(ctx, ops: str, work_fn: str, record: str, fields: list):
    from ray_tpu.util import timeline

    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(ops)
    sizes = [[args[f] for f in fields] for rec, _, _, args in
             engine_phase.profiled_records(timeline.local_events())
             if rec == record and args.get("outcome") in (None, "admitted")
             and all(f in args for f in fields)]
    if not seconds or not sizes:
        return None
    work_of = getattr(ctx.family, work_fn)
    works = [work_of(ctx.config["model"], *size) for size in sizes]
    total = {key: sum(w[key] for w in works) for key in ("flops", "bytes")}
    least, bound = shapes.least_seconds(total, ctx.peaks)
    ctx.notes[f"{work_fn}_bound"] = bound
    return 100.0 * least / seconds
