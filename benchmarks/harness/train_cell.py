"""Drives one training cell through `JaxTrainer.fit`.

The loop below is what a user's `train_loop_per_worker` is: it builds the
mesh (`make_mesh`), takes the sharded state and the compiled step from the
configuration's family module (for the Llama family `spmd.init_state`, jitted
once with `out_shardings`, and `spmd.make_train_step`) and steps on a fresh
seeded batch of packed sequences each step, so `device_put` is in the loop.
Whatever scalars the step returns in its metrics dict (`loss`, `grad_norm`,
an architecture's router load or auxiliary loss) are kept for every step of
the window as the series `step.<name>`, which `series_quantile` reads.

`correct`: a step on a batch that repeats ONE sequence, before the window,
gives that sequence's loss; the float32 reference computes the same loss
from the same seeded weights. Tolerance in the configuration file.
"""

from __future__ import annotations

import os
import shutil
import time

from benchmarks.harness import device, xplane
from benchmarks.harness.measure import Measurement, log
from benchmarks.harness.spec import ROOT


def plan(traffic: dict, seed: int, seconds: float, slots: int) -> dict:
    return {}


def _loop(config: dict) -> None:
    import importlib

    import jax
    import numpy as np

    from benchmarks.harness.families import seeded_key
    from ray_tpu import train
    from ray_tpu.parallel import sharding as shd
    from ray_tpu.parallel.mesh import make_mesh

    cell_cfg, traffic = config["cell_config"], config["traffic"]
    m, tr = cell_cfg["model"], cell_cfg["trainer"]
    seed, seconds, trace = config["seed"], config["seconds"], config["trace"]
    n_dev = cell_cfg["chips"]
    seq, batch = traffic["seq_len"], tr["sequences_per_chip"] * n_dev
    # found by name, as spec.Cell does (the loop's config carries only data)
    family = importlib.import_module(
        f"benchmarks.harness.families.{cell_cfg['family']}")
    reference = importlib.import_module(
        f"benchmarks.reference.{cell_cfg['reference']}")
    mesh = make_mesh(n_dev, **cell_cfg["mesh"])
    state, step = family.train_state_and_step(m, tr, mesh, seeded_key(seed))
    log("state made on its shards from the seed")
    batch_sh = shd.batch_sharding(mesh)

    def batch_of(i: int, repeat_one: bool = False):
        rng = np.random.default_rng([seed, i])
        rows = 1 if repeat_one else batch
        tokens = rng.integers(0, m["vocab_size"], (rows, seq)).astype(np.int32)
        if repeat_one:
            tokens = np.repeat(tokens, batch, axis=0)
        return tokens, np.roll(tokens, -1, axis=1)

    def run_step(state, tokens, targets):
        t0 = time.monotonic()
        tok, tgt = jax.device_put((tokens, targets), batch_sh)
        t1 = time.monotonic()
        state, metrics = step(state, tok, tgt)
        loss = float(jax.block_until_ready(metrics["loss"]))
        return state, loss, t1 - t0, time.monotonic() - t1, metrics

    # -- correct: one sequence, repeated, against the reference
    tokens, targets = batch_of(0, repeat_one=True)
    want = reference.loss(state.params, tokens[0], targets[0], m)
    log(f"reference loss {want}")
    state, got, *_ = run_step(state, tokens, targets)
    log(f"first step (compiled or loaded): loss {got}")
    check = {"loss": got, "reference_loss": want, "abs_err": abs(got - want),
             "ok": abs(got - want) <= cell_cfg["check"]["loss_abs"]}
    for i in range(1, 1 + tr["warmup_steps_before_window"]):
        state, *_ = run_step(state, *batch_of(i))

    # -- the window: whole steps, from now until the first step that ends at
    # or after `seconds`; the rate is all their tokens over all that time. In
    # a traced run the seconds spent starting and stopping the profiler (some 6 s
    # on four chips) are taken out, or `train_mfu` would read a tenth low;
    # without a trace there are none, and the end-to-end rate is untouched.
    steps, waits, losses, step_metrics, profiler_s = [], [], [], [], 0.0
    trace_dir, traced_steps = config["trace_dir"], 0
    tspec = traffic.get("trace", {})
    first_traced, n_traced = int(tspec.get("start_step", 3)), int(tspec.get("steps", 3))
    t_open = time.monotonic()
    log("window opens")
    i = 0
    while time.monotonic() - t_open < seconds:
        tracing = trace and first_traced <= i < first_traced + n_traced
        if tracing and i == first_traced:
            t = time.monotonic()
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            profiler_s += time.monotonic() - t
        tokens, targets = batch_of(100 + i)
        if tracing:
            with jax.profiler.StepTraceAnnotation("bench:train_step", step_num=i):
                state, loss, wait, dur, extra = run_step(state, tokens, targets)
            traced_steps += 1
        else:
            state, loss, wait, dur, extra = run_step(state, tokens, targets)
        if tracing and i == first_traced + n_traced - 1:
            t = time.monotonic()
            jax.profiler.stop_trace()
            profiler_s += time.monotonic() - t
        steps.append(dur)
        waits.append(wait)
        losses.append(loss)
        step_metrics.append(extra)
        i += 1
    window_s = time.monotonic() - t_open - profiler_s
    if trace and 0 < traced_steps < n_traced:   # window ended inside the trace
        jax.profiler.stop_trace()
    # every scalar the steps returned beside their loss, copied to the host
    # once the window is over (a copy a step cost 0.7 ms of each 588 ms step:
    # my chip run, PR 26): they reach a reader as the series `step.<name>`
    scalars: dict = {}
    for metrics in jax.device_get(step_metrics):
        for name, value in metrics.items():
            if np.ndim(value) == 0:
                scalars.setdefault(name, []).append(float(value))
    train.report({
        "t_open": t_open, "window_s": window_s, "check": check,
        "step_s": steps, "data_wait_s": waits, "losses": losses,
        "step_scalars": scalars,
        "traced_steps": traced_steps, "profiler_s": profiler_s,
        "chip_batch": tr["sequences_per_chip"],
        "tokens": len(steps) * batch * seq,
        "memory_peak_bytes": device.memory_peak_bytes(),
        "platform": list(mesh.devices.flat)[0].platform})


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        dev: dict, peaks: dict) -> Measurement:
    import math

    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    ms = Measurement(config=cell.config, traffic=cell.traffic, peaks=peaks,
                     family=cell.family)
    tmp = os.path.join(ROOT, ".bench_tmp")
    trace_dir = os.path.join(tmp, f"trace-{cell.name}")
    cell.family_entry("train_state_and_step")   # the loop finds it by name
    try:
        ray_tpu.init()
        result = JaxTrainer(
            _loop,
            train_loop_config={"cell_config": cell.config, "traffic": cell.traffic,
                               "seed": seed, "seconds": seconds, "trace": trace,
                               "trace_dir": trace_dir},
            scaling_config=ScalingConfig(num_workers=1,
                                         use_tpu=dev["platform"] == "tpu"),
            run_config=RunConfig(name=cell.name,
                                 storage_path=os.path.join(tmp, "train")),
        ).fit()
        if result.error is not None:
            raise result.error
        r = result.metrics
        if r["platform"] != dev["platform"]:
            raise RuntimeError(f"the step ran on {r['platform']!r}, "
                               f"jax's backend is {dev['platform']!r}")
        ms.correct = bool(r["check"]["ok"])
        ms.attempted = len(r["step_s"])
        ms.failed = sum(1 for x in r["losses"] if not math.isfinite(x))
        ms.series.update(step_s=r["step_s"], data_wait_s=r["data_wait_s"])
        ms.series.update({f"step.{k}": v for k, v in r["step_scalars"].items()})
        ms.counters.update(
            setup_s=r["t_open"] - t_start, window_s=r["window_s"],
            train_tok_s_chip=r["tokens"] / r["window_s"] / cell.chips,
            memory_peak_bytes=r["memory_peak_bytes"],
            traced_train_steps=r["traced_steps"], chip_batch=r["chip_batch"],
            seq_len=cell.traffic["seq_len"])
        ms.notes.update(check=r["check"], steps=len(r["step_s"]),
                        profiler_s=r["profiler_s"],
                        first_loss=r["losses"][0], last_loss=r["losses"][-1])
        if trace:
            ms.trace = xplane.reduce_trace_dir(trace_dir)
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
        shutil.rmtree(os.path.join(tmp, "train"), ignore_errors=True)
    return ms
