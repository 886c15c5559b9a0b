"""The share of the traced window's busy time that the device spends in
operations traced under a named scope, in percent. `scope` is a pattern
searched in an operation's `op_name`, the path of `jax.named_scope`s the
program put around the code it came from (`harness/xplane.py`), so an
operation counts under every scope it lies in. Self times: a loop is not
counted on top of its body. A fusion carries one `op_name`, its root's.
A trace whose operations carry no `op_name` leaves the metric out."""


def read(ctx, scope: str):
    trace = ctx.trace
    if trace is None or not trace.busy_s or not trace.scope_self_s:
        return None
    return 100.0 * trace.scope_seconds(scope) / trace.busy_s
