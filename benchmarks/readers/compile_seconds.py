"""Seconds the process spent compiling programs or loading them from the
persistent cache, by the program's own counter (`ray_tpu/util/compile_cache.py
::compile_totals`, a `jax.monitoring` listener that `ensure_compile_cache`
installs before anything compiles), read once when the run is over. All of
it should lie in set-up: `diag.compile` also gives the counts, how many were
cache loads, and the seconds that fell inside the engine's profiled records
(`diag.engine_phases.*.compile_s`, 0 when nothing compiled in the window).
A program without the counter leaves the metric out of the line."""


def read(ctx):
    from ray_tpu.util import compile_cache

    totals = getattr(compile_cache, "compile_totals", None)
    if totals is None:
        return None
    compiles, compile_s, cache_loads, cache_load_s = totals()
    ctx.notes["compile"] = {"compiles": compiles, "compile_s": compile_s,
                            "cache_loads": cache_loads,
                            "cache_load_s": cache_load_s}
    return compile_s
