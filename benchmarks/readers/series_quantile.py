"""A quantile of a named sample, scaled (1000 turns seconds into ms)."""

from benchmarks.harness.stats import quantile


def read(ctx, series: str, q: float, scale: float = 1.0):
    value = quantile(ctx.series.get(series, ()), q)
    return None if value is None else value * scale
