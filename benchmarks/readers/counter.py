"""A named number the harness counted or clocked itself, scaled."""


def read(ctx, name: str, scale: float = 1.0):
    value = ctx.counters.get(name)
    return None if value is None else value * scale
