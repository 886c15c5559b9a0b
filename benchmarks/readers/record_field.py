"""A quantile of one field of the engine's own records, over the profiled
records of one name (`engine_phase.profiled_records`), scaled; with `per_fn`,
as a share of what a function of the family module makes of the configuration
file (`blocks` over the pool's usable blocks). A program whose records lack
the field, or a window with no such record, leaves the metric out. Also
writes `diag.engine_phases` (the phase sums of the same records, for people),
where no reader has yet."""

from benchmarks.harness.stats import quantile
from benchmarks.readers import engine_phase


def read(ctx, name: str, field: str, q: float = 50, scale: float = 1.0,
         per_fn: str | None = None):
    from ray_tpu.util import timeline

    records = engine_phase.profiled_records(timeline.local_events())
    ctx.notes.setdefault("engine_phases", engine_phase.summary(records))
    values = [args[field] for rec, _, _, args in records
              if rec == name and field in args]
    if not values:
        return None
    per = getattr(ctx.family, per_fn)(ctx.config) if per_fn else 1.0
    return quantile(values, q) * scale / per
