"""The share of the traced window a chip spends in collective operations
with no compute running on it, in percent. A TPU core runs the operations
of its `XLA Ops` line one after another, so the time that line spends in an
all-gather, reduce-scatter, all-reduce, all-to-all or collective-permute
(for an asynchronous one: in its `-start` and in the wait of its `-done`) is
time in which that core computes nothing. Averaged over the chips."""

COLLECTIVES = r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"


def read(ctx):
    if ctx.trace is None or not ctx.trace.window_s:
        return None
    return 100.0 * ctx.trace.op_seconds(COLLECTIVES) / ctx.trace.window_s
