"""One small module per way of reading a metric: `read(ctx, **args)` returns
a number, or None when there is nothing to read (the metric is then left out
of the line). `ctx` is `harness.measure.Measurement`: `series` (named samples
in seconds), `counters` (named numbers), `trace` (the reduced device trace
of a `--trace 1` run, else None), `config`, `traffic`, `peaks`."""
