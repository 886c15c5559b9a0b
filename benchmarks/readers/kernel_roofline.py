"""A kernel's share of its roofline, from the device trace, in percent.

The least time the chip could take for the traced calls (a shapes function
that the configuration's family module holds gives the operations and bytes
the algorithm needs for ONE step) over the device time of the trace's operations whose label matches
`ops`: the kernel's own name, so that another kernel in the same step is not
counted with it. How many steps the trace covers is a counter the cell's
driver kept (`steps`): whole train steps, or the engine's decode records
inside the traced interval, where a step the trace's edge cuts counts for the
part of it inside (`harness/engine_records.py`).
The counter `<name>_bound` says which of compute and memory bounded it.
"""

from benchmarks.harness import shapes


def read(ctx, ops: str, work_fn: str, work_args: list, steps: str):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(ops)
    n_steps = ctx.counters.get(steps)
    args = [ctx.counters.get(a) for a in work_args]
    if not seconds or not n_steps or any(a is None for a in args):
        return None
    work = getattr(ctx.family, work_fn)(ctx.config["model"], *args)
    least, bound = shapes.least_seconds(work, ctx.peaks)
    ctx.notes[f"{work_fn}_bound"] = bound
    return 100.0 * least * n_steps / seconds
