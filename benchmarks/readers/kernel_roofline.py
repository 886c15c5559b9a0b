"""A kernel's share of its roofline, from the device trace, in percent.

The least time the chip could take for the traced calls (a shapes function
that the configuration's family module holds gives the operations and bytes
the algorithm needs for ONE step) over the device time of the trace's operations whose label matches
`ops`. How many steps the trace covers is either a counter the harness kept
(`steps`) or the number of matching calls over a model size that says how
many calls one step makes (`calls_per_step`, e.g. one per layer), which also
counts the steps that the trace's edges cut.
The counter `<name>_bound` says which of compute and memory bounded it.
"""

from benchmarks.harness import shapes


def read(ctx, ops: str, work_fn: str, work_args: list, steps: str | None = None,
         calls_per_step: str | None = None):
    if ctx.trace is None:
        return None
    seconds = ctx.trace.op_seconds(ops)
    if calls_per_step is not None:
        n_steps = ctx.trace.op_calls(ops) / ctx.config["model"][calls_per_step]
    else:
        n_steps = ctx.counters.get(steps)
    args = [ctx.counters.get(a) for a in work_args]
    if not seconds or not n_steps or any(a is None for a in args):
        return None
    work = getattr(ctx.family, work_fn)(ctx.config["model"], *args)
    least, bound = shapes.least_seconds(work, ctx.peaks)
    ctx.notes[f"{work_fn}_bound"] = bound
    return 100.0 * least * n_steps / seconds
