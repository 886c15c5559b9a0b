"""A compiled program's share of its roofline, from the device trace, in
percent: `kernel_roofline` for a whole jitted step instead of one kernel.

The least time the chip could take for the traced steps (a shapes function
that the configuration's family module holds gives the operations and bytes
ONE step must do, from the steps' mean context and live slots) over the
device's SELF time in the program's operations. An operation's `op_name`
(`harness/xplane.py`) starts with the jitted function's name
(`jit(decode)/...`), and `scope` is a pattern searched in it. The denominator
is every operation of the window but those traced under ANOTHER name (a
prefill): an operation the compiler made carries no `op_name` at all (the
re-layout of a whole stacked weight that XLA hoists out of the layer loop,
2.6 ms of Ouro's 52 ms step), belongs to some program of the window, and
is counted with this one, so the time never leaves out part of the step's
work and the share reads low, not high, by the other programs' unnamed part
(`diag.trace_unscoped_s` is all of it). The host's gaps between steps are in
no operation. How many steps the trace covers is the counter the cell's
driver kept (`steps`, `harness/engine_records.py`). `<work_fn>_bound` says
which of compute and memory bounded it. A trace without `op_name`s, or with
no operation under `scope`, leaves the metric out."""

from benchmarks.harness import shapes


def read(ctx, scope: str, work_fn: str, work_args: list, steps: str):
    trace = ctx.trace
    if trace is None or not trace.scope_self_s:
        return None
    named = sum(trace.scope_self_s.values())
    total = sum(trace.op_self_s.values())
    seconds = total - (named - trace.scope_seconds(scope))
    n_steps = ctx.counters.get(steps)
    args = [ctx.counters.get(a) for a in work_args]
    if not trace.scope_seconds(scope) or not n_steps or any(a is None for a in args):
        return None
    work = getattr(ctx.family, work_fn)(ctx.config["model"], *args)
    least, bound = shapes.least_seconds(work, ctx.peaks)
    ctx.notes[f"{work_fn}_bound"] = bound
    ctx.notes["trace_unscoped_s"] = total - named
    return 100.0 * least * n_steps / seconds
