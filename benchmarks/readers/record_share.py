"""The share, in percent, of the engine's profiled records of one name whose
`field` equals `equals` (admissions that ended `requeued`). A window with no
record of that name leaves the metric out."""

from benchmarks.readers import engine_phase


def read(ctx, name: str, field: str, equals):
    from ray_tpu.util import timeline

    values = [args.get(field) for rec, _, _, args in
              engine_phase.profiled_records(timeline.local_events()) if rec == name]
    if not values:
        return None
    return 100.0 * sum(v == equals for v in values) / len(values)
