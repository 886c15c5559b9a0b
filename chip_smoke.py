"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # from the root of a checkout, on a TPU host

One process drives the two things this runtime puts on a TPU, through the
entry points a user calls, at the full width of `LlamaConfig.llama_1b()`
(random weights from a seed, full depth except where a phase says it cut it):

  device   the TPU backend is the default, the runtime counts its chips,
           the native store was built
  kernels  flash attention (forward and gradients) and the paged decode
           kernel, COMPILED, agree with the dense reference
  train    JaxTrainer(...).fit() -> make_mesh / init_state / make_train_step,
           a few steps: finite, first loss near ln(vocab), falling, Mosaic
           call present in the compiled step
  serve    serve.run(build_openai_app(PagedLLMConfig(...))) + HTTP proxy:
           every prefill bucket, four requests at once, a prefix-cache hit,
           one SSE stream; the engine says it runs on the TPU
  four     (>= 4 devices) the train phase on fsdp=2 x tensor=2 and on fsdp=4:
           shards, collectives, first loss equal to the one-device loss

Any failed phase fails the run: no exception is downgraded. It exits 0 only
after serve, the runtime and every engine thread are down, and then prints
as its LAST line one JSON object with exactly these keys,
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}};
what else the run learned (four_chip, compile cache, wall time) is on the
`[smoke] summary:` line before it and in chiprun_out/chip_smoke.jsonl.
Without a TPU backend it exits non-zero before any phase and prints no
result; `main()` has no CPU mode. (tests/test_tpu_chip_smoke.py imports this file
and runs each phase function at `LlamaConfig.tiny()` sizes on the CPU, so the
control flow is debugged before chip time is spent.)

Every time printed here is a SMOKE TIMING of one cold run, compilation and
first-touch costs mixed in. None of it is a benchmark number.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
MOSAIC = "tpu_custom_call"  # custom-call target of a compiled Pallas kernel
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# bf16 keeps 8 bits of mantissa: one rounding is a relative error of 2^-8.
BF16_EPS = 2.0 ** -8


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU test."""

    model: object                      # LlamaConfig trained and served
    flash: tuple = (1, 2048, 32, 8, 64)        # B, S, Hq, Hkv, D
    paged_layers: int = 2              # depth of the paged-kernel comparison
    paged_batch: int = 8
    train_batch: int = 2
    train_seq: int = 2048
    train_steps: int = 5
    four_batch: int = 8
    serve_batch: int = 8
    serve_seq: int = 2048
    max_tokens: int = 8


def full_sizes() -> Sizes:
    from ray_tpu.models import llama

    # llama_1b at every published width and full depth; only the context the
    # step is compiled for is cut (8192 -> 2048), and remat keeps matmul
    # outputs ("dots") so that 2 x 2048 tokens fit beside the 8.4 GiB of
    # parameters and optimizer state on one 16 GB chip.
    return Sizes(model=dataclasses.replace(
        llama.LlamaConfig.llama_1b(), max_seq_len=2048, remat_policy="dots"))


# --------------------------------------------------------------- compile cache
class CacheCounter:
    """Counts jax's persistent-cache events for this process."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.saved_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs


def _entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


# ------------------------------------------------------------------ the device
def require_tpu() -> dict:
    """With JAX_PLATFORMS unset jax only WARNS when the TPU fails to
    initialise and carries on on the CPU, so ask what it ended up with."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: jax's default backend is {backend!r}, not 'tpu' "
            f"(devices: {jax.devices()}); there is no CPU mode")
    return device_info()


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def phase_device(sizes: Sizes) -> dict:
    """Starts the runtime session the later phases use."""
    import importlib.metadata as md

    import jax
    import jaxlib

    import ray_tpu
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.native.build import build_library

    dev = device_info()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    ray_tpu.init()
    tpus = ray_tpu.cluster_resources().get("TPU", 0.0)
    expected = dev["count"] if dev["platform"] == "tpu" else 0
    check(tpus == expected,
          f"ray_tpu.init() counted {tpus} TPU chips, jax has {expected}")
    rt = rt_mod.get_runtime()
    check(rt.shm_store is not None,
          "native shm store was not built: the session fell back to the "
          "in-memory store")
    so = build_library("shm_store")  # cached: returns the path it loaded
    say(f"device: runtime TPU resource={tpus} native_store={so}")
    return {**dev, "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "runtime_tpus": tpus, "native_store": so}


# ----------------------------------------------------------------- the kernels
def _max_err(a, b) -> tuple[float, float]:
    """(max |a-b|, max |b|) in float32."""
    import jax.numpy as jnp

    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a32 - b32))), float(jnp.max(jnp.abs(b32)))


def phase_kernels(sizes: Sizes) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.platform import target_platform

    out: dict = {}
    B, S, Hq, Hkv, D = sizes.flash
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, S, Hq, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, Hkv, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, Hkv, D), jnp.bfloat16)
    w = jax.random.normal(kw, (B, S, Hq, D), jnp.float32)  # cotangent
    platform = target_platform(q)
    on_tpu = platform == "tpu"

    # -- flash forward + gradients vs llama.attention taken in float32 (the
    # truth both bf16 paths approximate)
    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, interpret=not on_tpu)
        return (o.astype(jnp.float32) * w).sum(), o

    def dense_loss(q, k, v):
        o = llama.attention(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
        return (o * w).sum(), o

    t0 = time.perf_counter()
    flash_vg = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)).lower(q, k, v).compile()
    if on_tpu:
        check(flash_vg.as_text().count(MOSAIC) >= 3,
              "flash fwd+bwd compiled without its three Mosaic calls")
    (_, o_f), g_f = jax.block_until_ready(flash_vg(q, k, v))
    t_flash = time.perf_counter() - t0
    # "highest": on a TPU a float32 matmul otherwise runs as one bf16 pass
    with jax.default_matmul_precision("highest"):
        (_, o_d), g_d = jax.jit(jax.value_and_grad(
            dense_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    # Tolerance, forward: the output is a convex combination of v rows, so
    # |o| <= max|v|; the kernel rounds q.k products' inputs, p and the output
    # to bf16-level precision: a few roundings of that scale -> 4 eps * max|v|.
    err, scale = _max_err(o_f, o_d)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    tol_fwd = 4 * BF16_EPS * vmax
    say(f"kernels: flash fwd S={S} Hq={Hq} Hkv={Hkv} D={D}: max|err|={err:.2e} "
        f"(tolerance {tol_fwd:.2e} = 4 bf16 eps x max|v|, max|ref|={scale:.2f}); "
        f"smoke timing fwd+bwd incl. compile {t_flash:.2f}s")
    check(err <= tol_fwd, f"flash forward off by {err} > {tol_fwd}")
    out["flash_fwd_err"], out["flash_fwd_tol"] = err, tol_fwd
    # Tolerance, gradients: dq/dk/dv are sums over up to S products each
    # rounded like the forward, and are returned in bf16; errors add like a
    # random walk, far below the largest entry. 8 eps of the largest
    # reference entry separates that from a wrong mask or scale (O(1) of it).
    for name, gf, gd in zip(("dq", "dk", "dv"), g_f, g_d):
        err, scale = _max_err(gf, gd)
        tol = 8 * BF16_EPS * scale
        say(f"kernels: flash {name}: max|err|={err:.2e} "
            f"(tolerance {tol:.2e} = 8 bf16 eps x max|ref|={scale:.2f})")
        check(bool(jnp.isfinite(gf.astype(jnp.float32)).all()),
              f"flash {name} not finite")
        check(err <= tol, f"flash {name} off by {err} > {tol}")
        out[f"flash_{name}_err"], out[f"flash_{name}_tol"] = err, tol

    # -- paged decode kernel vs the same forward_paged step on the gathered
    # dense view. Depth is cut (the comparison is per layer; weights are
    # random); every width is the model's.
    cfg = dataclasses.replace(sizes.model, num_layers=sizes.paged_layers)
    bs = 16
    Bp = sizes.paged_batch
    max_blocks = sizes.serve_seq // bs
    n_blocks = Bp * max_blocks + 1
    params = llama.init(cfg, jax.random.PRNGKey(1))
    # the engine's pool (token major, a head in whole 128-lane tiles), its
    # heads' own lanes random and the padding lanes zero as the model leaves them
    pshape = jax.eval_shape(lambda: llama.init_kv_pool(cfg, n_blocks, bs))["k"].shape
    own_lanes = (jnp.arange(pshape[-1]) % llama.pool_head_dim(cfg.hd)) < cfg.hd
    pool = {name: jnp.where(own_lanes, jax.random.normal(
                jax.random.PRNGKey(seed), pshape, cfg.dtype), 0)
            for name, seed in (("k", 2), ("v", 3))}
    full = max_blocks * bs
    # KV tokens already cached per row; the kernel sees +1 (the new token):
    # 1 token, 2, one full block, a block and one, not a block multiple,
    # a long ragged one, and the full table.
    lengths = np.resize(np.array(
        [0, 1, bs - 1, bs, 2 * bs + 5, full // 2 + 3, full - 2, full - 1],
        np.int32), Bp)
    rng = np.random.default_rng(0)
    tables = (rng.permutation(n_blocks - 1)[: Bp * max_blocks] + 1).reshape(
        Bp, max_blocks).astype(np.int32)
    tokens = rng.integers(0, cfg.vocab_size, (Bp, 1)).astype(np.int32)

    args = (params, pool, jnp.asarray(tokens), jnp.asarray(tables),
            jnp.asarray(lengths))

    def decode(use_kernel):
        def f(params, pool, tokens, tables, lengths):
            return llama.forward_paged(params, tokens, cfg, pool, tables,
                                       lengths, bs, use_kernel=use_kernel,
                                       platform=platform)
        return jax.jit(f).lower(*args).compile()

    kernel_step = decode(True)
    if on_tpu:
        check(MOSAIC in kernel_step.as_text(),
              "the decode step compiled without the Mosaic paged kernel")
    logits_k, pool_k = kernel_step(*args)
    logits_d, pool_d = decode(False)(*args)
    # layer 0's new K/V do not depend on which arm computed attention
    check(bool(jnp.array_equal(pool_k["k"][0], pool_d["k"][0]))
          and bool(jnp.array_equal(pool_k["v"][0], pool_d["v"][0])),
          "kernel and dense decode steps wrote different layer-0 KV pages")
    # a step writes one row a slot and layer and nothing else
    changed = int((pool_k["k"] != pool["k"]).any(axis=-1).sum())
    check(0 < changed <= cfg.num_layers * Bp,
          f"a decode step changed {changed} rows of the K pool, "
          f"more than {cfg.num_layers} layers x {Bp} slots")
    # Tolerance: neither arm is the truth. Both round to bf16 at every
    # matmul and differ in where (the dense arm rounds scores to bf16 before
    # the softmax, the kernel keeps them in float32), over sizes.paged_layers
    # layers and the output head: 8 eps of the largest logit.
    err, scale = _max_err(logits_k, logits_d)
    tol = 8 * BF16_EPS * max(scale, 1.0)
    say(f"kernels: paged decode step B={Bp} lengths+1={(lengths + 1).tolist()} "
        f"block={bs} table={max_blocks}: max|logit err|={err:.2e} "
        f"(tolerance {tol:.2e} = 8 bf16 eps x max|logit|={scale:.2f})")
    check(bool(jnp.isfinite(logits_k).all()), "paged kernel logits not finite")
    check(err <= tol, f"paged decode logits off by {err} > {tol}")
    out["paged_logit_err"], out["paged_logit_tol"] = err, tol
    return out


# ------------------------------------------------------------------- the train
def _train_loop(config: dict) -> None:
    """The per-worker loop JaxTrainer runs: build the mesh, the state and the
    SPMD step the way a user does, take a few steps on one repeated batch and
    report each through train.report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import sharding as shd
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.train import spmd

    cfg, n_dev, layout = config["cfg"], config["n_devices"], config["layout"]
    batch, seq, steps = config["batch"], config["seq"], config["steps"]
    mesh = make_mesh(n_dev, **layout)
    optimizer = spmd.make_optimizer(warmup=1)
    state = spmd.init_state(cfg, jax.random.PRNGKey(0), optimizer=optimizer)
    n_params = llama.param_count(state.params)
    rng = np.random.default_rng(0)
    tokens_np = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    targets_np = np.roll(tokens_np, -1, axis=1)

    ref_loss = None
    if config.get("reference_loss"):
        # the loss ONE device computes for the same seeded batch and weights,
        # forward only, a chunk of rows at a time (the whole batch does not
        # fit one chip); rows have equal length, so the mean of chunk means
        # is the batch mean
        fwd = jax.jit(lambda p, t, y: llama.loss_fn(p, t, y, cfg))
        chunk = config["reference_chunk"]
        ref_loss = float(np.mean([
            float(fwd(state.params, jnp.asarray(tokens_np[i:i + chunk]),
                      jnp.asarray(targets_np[i:i + chunk])))
            for i in range(0, batch, chunk)]))

    step = spmd.make_train_step(cfg, mesh, optimizer=optimizer)(state)
    shardings = spmd.state_shardings(cfg, mesh, state)
    # init_state left the whole state on device 0; move it to its shards and
    # drop the original before the step runs, or device 0 holds both
    state = jax.device_put(state, shardings)
    tokens = jax.device_put(tokens_np, shd.batch_sharding(mesh))
    targets = jax.device_put(targets_np, shd.batch_sharding(mesh))

    t0 = time.perf_counter()
    compiled = step.lower(state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()

    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, tokens, targets)
        jax.block_until_ready((state, metrics))
        train.report({"step": i, "loss": float(metrics["loss"]),
                      "grad_norm": float(metrics["grad_norm"]),
                      "step_s": time.perf_counter() - t0})

    devices = list(mesh.devices.flat)
    stats = [d.memory_stats() for d in devices]
    sharded = []  # (name, n_shards, n_distinct_devices, shard_bytes, leaf_bytes)
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        if all(ax is None for ax in leaf.sharding.spec):
            continue
        shards = leaf.addressable_shards
        sharded.append((jax.tree_util.keystr(path), len(shards),
                        len({s.device for s in shards}),
                        shards[0].data.nbytes, leaf.nbytes))
    train.report({
        "summary": True, "compile_s": compile_s, "param_count": n_params,
        "mosaic_calls": text.count(MOSAIC),
        "collectives": {c: text.count(c) for c in COLLECTIVES},
        "reference_loss": ref_loss, "sharded_leaves": sharded,
        "peak_bytes": [s["peak_bytes_in_use"] if s else None for s in stats],
        # XLA's scratch for the running program is reserved, not "in use"
        "peak_reserved": [s.get("peak_bytes_reserved") if s else None
                          for s in stats],
        "bytes_in_use": [s["bytes_in_use"] if s else None for s in stats],
        "platform": devices[0].platform,
    })


def _run_trainer(sizes: Sizes, label: str, **config) -> tuple[list, dict]:
    """JaxTrainer(...).fit() over _train_loop; returns (step reports, summary)
    after the checks every layout shares."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg = sizes.model
    result = JaxTrainer(
        _train_loop,
        train_loop_config={"cfg": cfg, "seq": sizes.train_seq,
                           "steps": sizes.train_steps, **config},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=config["use_tpu"]),
        run_config=RunConfig(name=label, storage_path=os.path.join(
            OUT_DIR, "chip_smoke_train")),
    ).fit()
    if result.error is not None:
        raise result.error
    steps = [m for m in result.metrics_history if "step" in m]
    summary = result.metrics
    check(summary.get("summary") and len(steps) == sizes.train_steps,
          f"{label}: expected {sizes.train_steps} step reports and a summary, "
          f"got {len(steps)}")
    losses = [m["loss"] for m in steps]
    say(f"{label}: params={summary['param_count']:,} (embeddings untied) "
        f"batch={config['batch']}x{sizes.train_seq} remat={cfg.remat_policy} "
        f"layout={config['layout'] or 'one device'}")
    say(f"{label}: losses={[round(l, 4) for l in losses]} "
        f"grad_norms={[round(m['grad_norm'], 3) for m in steps]}")
    check(all(math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])
              for m in steps), f"{label}: loss or grad norm not finite")
    # logits of a random-weight model are ~N(0,1), so the loss starts about
    # half a nat above ln(vocab); a broken head or loss lands far outside
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) < 1.0,
          f"{label}: first loss {losses[0]:.3f} not near ln(vocab)={ln_v:.2f}")
    check(losses[-1] < losses[0],
          f"{label}: loss on a repeated batch did not fall: {losses}")
    warm = [m["step_s"] for m in steps[1:]]
    gib = lambda key: [round(b / 2**30, 2) for b in summary[key]
                       if b is not None] or "not reported"
    say(f"{label}: smoke timings: compile {summary['compile_s']:.1f}s, first "
        f"step {steps[0]['step_s']:.2f}s, later steps "
        f"{min(warm):.3f}-{max(warm):.3f}s each (block_until_ready); "
        f"peak_bytes_in_use(GiB)={gib('peak_bytes')} "
        f"peak_bytes_reserved(GiB)={gib('peak_reserved')}; "
        f"mosaic_calls={summary['mosaic_calls']} "
        f"collectives={summary['collectives']}")
    if summary["platform"] == "tpu":
        # a quiet fall to dense attention would still train; fail it here
        check(summary["mosaic_calls"] > 0,
              f"{label}: no Mosaic call in the compiled train step")
    return steps, summary


def phase_train(sizes: Sizes) -> dict:
    import jax

    steps, summary = _run_trainer(
        sizes, "train", n_devices=1, layout={}, batch=sizes.train_batch,
        use_tpu=jax.default_backend() == "tpu")
    return {"losses": [m["loss"] for m in steps],
            "compile_s": summary["compile_s"],
            "step_s": [m["step_s"] for m in steps],
            "peak_bytes": summary["peak_bytes"],
            "peak_reserved": summary["peak_reserved"],
            "mosaic_calls": summary["mosaic_calls"]}


def phase_four_chip(sizes: Sizes) -> dict:
    import jax

    out: dict = {}
    for layout in ({"fsdp": 2, "tensor": 2}, {"fsdp": 4}):
        label = "four_chip[" + ",".join(f"{k}={v}" for k, v in layout.items()) + "]"
        steps, summary = _run_trainer(
            sizes, label, n_devices=4, layout=layout, batch=sizes.four_batch,
            use_tpu=jax.default_backend() == "tpu",
            reference_loss=True, reference_chunk=sizes.train_batch)
        leaves = summary["sharded_leaves"]
        check(len(leaves) > 0, f"{label}: no parameter leaf is sharded")
        for name, n_shards, n_devs, shard_bytes, leaf_bytes in leaves:
            check(n_shards == 4 and n_devs == 4 and shard_bytes * 4 == leaf_bytes,
                  f"{label}: {name} has {n_shards} shards on {n_devs} devices "
                  f"of {shard_bytes} bytes (leaf {leaf_bytes})")
        in_use = [b for b in summary["bytes_in_use"] if b is not None]
        if in_use:
            check(max(in_use) < 2 * min(in_use),
                  f"{label}: device memory uneven across the mesh: {in_use}")
        check(sum(summary["collectives"].values()) > 0,
              f"{label}: no collective in the compiled sharded step")
        # Tolerance: the same bf16 forward pass, reduced in another order
        # (per-shard partial sums, then all-reduce): a few bf16 roundings of
        # a value near 12 -> 2e-2 absolute. A wrong shard spec is off by >0.1.
        ref = summary["reference_loss"]
        say(f"{label}: {len(leaves)} sharded leaves x 4 shards of 1/4 on 4 "
            f"devices; bytes_in_use(GiB)="
            f"{[round(b / 2**30, 2) for b in in_use] or 'not reported'}; "
            f"first loss {steps[0]['loss']:.4f} vs one-device {ref:.4f} "
            f"(tolerance 2e-2)")
        check(abs(steps[0]["loss"] - ref) <= 2e-2,
              f"{label}: first loss {steps[0]['loss']} != one-device {ref}")
        out[label] = {"first_loss": steps[0]["loss"], "reference_loss": ref,
                      "compile_s": summary["compile_s"],
                      "collectives": summary["collectives"]}
    return out


# ------------------------------------------------------------------- the serve
def _post(url: str, body: dict, timeout: float = 600.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:  # raises on 4xx/5xx
        check(resp.status == 200, f"POST {url} -> {resp.status}")
        return json.loads(resp.read())


def _stream_chat(url: str, body: dict, timeout: float = 600.0) -> tuple[float, int]:
    """Reads an SSE chat stream to `data: [DONE]`; returns (seconds to the
    first content frame, content frames)."""
    req = urllib.request.Request(
        url, data=json.dumps({**body, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    ttft, frames, done = None, 0, False
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        check(resp.status == 200, f"POST {url} (stream) -> {resp.status}")
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[len("data:"):].strip()
            if data == "[DONE]":
                done = True
                break
            frame = json.loads(data)
            check("error" not in frame, f"stream error frame: {frame}")
            if frame["choices"][0]["delta"].get("content"):
                frames += 1
                if ttft is None:
                    ttft = time.perf_counter() - t0
    check(done, "stream ended without data: [DONE]")
    check(ttft is not None, "stream carried no content frame")
    return ttft, frames


def phase_serve(sizes: Sizes) -> dict:
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm_paged import PagedLLMConfig
    from ray_tpu.serve.openai_api import build_openai_app

    mem = jax.devices()[0].memory_stats()
    in_use0 = mem["bytes_in_use"] if mem else None
    handle = serve.run(build_openai_app(PagedLLMConfig(
        model_config=sizes.model, max_batch_size=sizes.serve_batch,
        max_seq_len=sizes.serve_seq)), route_prefix="/v1")
    try:
        proxy = serve.start_http_proxy(port=0)
        out = _serve_requests(sizes, handle,
                              f"http://127.0.0.1:{proxy.port}/v1")
    finally:
        # the replica goes with the app, and its engine with the replica:
        # loop thread joined, weights and KV pool back to the device
        serve.shutdown()
        _join_engine_threads()
    if in_use0 is not None:
        in_use = jax.devices()[0].memory_stats()["bytes_in_use"]
        say(f"serve: device bytes_in_use {in_use0 / 2**30:.2f} GiB before the "
            f"app, {in_use / 2**30:.2f} GiB after its shutdown")
        check(in_use <= in_use0 + 2**26,
              f"the engine's device memory did not come back: {in_use0} -> {in_use}")
    return out


def _serve_requests(sizes: Sizes, handle, base: str) -> dict:
    import jax

    import ray_tpu

    n = sizes.max_tokens
    stats0 = ray_tpu.get(handle.stats.remote())
    # item 2 of the issue: an engine inside a CPU-pinned worker would serve
    # from the CPU without a word. This one lives in the driver process.
    check(stats0["platform"] == jax.default_backend(),
          f"the engine initialised on {stats0['platform']!r}, "
          f"jax's backend is {jax.default_backend()!r}")

    def complete(prompt: str) -> dict:
        r = _post(f"{base}/completions", {"prompt": prompt, "max_tokens": n})
        check(r["usage"]["completion_tokens"] == n,
              f"completion_tokens {r['usage']['completion_tokens']} != {n}")
        return r

    chat = {"messages": [{"role": "user", "content": "hi"}], "max_tokens": n}
    ttft_cold, frames = _stream_chat(f"{base}/chat/completions", chat)
    # one prompt per prefill bucket: <=32, <=128, and longer (pads to
    # max_seq_len). A letter each: a shared prefix would be served from the
    # prefix cache and leave only a short suffix to prefill.
    buckets = {}
    for name, letter, length in (("<=32", "a", 20), ("<=128", "b", 100),
                                 (f"->{sizes.serve_seq}", "c", 150)):
        t0 = time.perf_counter()
        r = complete(letter * length)
        check(r["usage"]["prompt_tokens"] == length, "byte tokenizer length")
        buckets[name] = round(time.perf_counter() - t0, 2)
    # four at once: decode runs with several live rows
    results: list = [None] * 4

    def one(i: int) -> None:
        try:
            results[i] = complete(f"request number {i}")
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in results:
        if isinstance(r, BaseException):
            raise r
    # the 100-token prompt again: its full blocks are in the prefix cache
    before = ray_tpu.get(handle.stats.remote())
    complete("b" * 100)
    after = ray_tpu.get(handle.stats.remote())
    check(after["prefix_hits"] > before["prefix_hits"],
          f"repeated prompt did not hit the prefix cache: {before} -> {after}")
    ttft_warm, _ = _stream_chat(f"{base}/chat/completions", chat)
    check(after["active_slots"] == 0, f"slots still active: {after}")
    say(f"serve: engine platform={stats0['platform']} B={sizes.serve_batch} "
        f"max_seq_len={sizes.serve_seq}; all requests 200 with "
        f"completion_tokens={n}; prefix_hits {before['prefix_hits']}->"
        f"{after['prefix_hits']}; stream frames={frames}")
    say(f"serve: smoke timings: time to first token cold {ttft_cold:.2f}s "
        f"(compiles its prefill bucket and decode), warm {ttft_warm:.3f}s; "
        f"first request per bucket {buckets}")
    return {"platform": stats0["platform"], "ttft_cold_s": ttft_cold,
            "ttft_warm_s": ttft_warm, "bucket_first_request_s": buckets,
            "prefix_hits": after["prefix_hits"]}


# -------------------------------------------------------------------- teardown
def teardown() -> None:
    """serve, then the runtime; then every engine loop thread must be gone —
    one left inside a jitted call aborts the interpreter at exit."""
    import ray_tpu
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()
    _join_engine_threads()


def _join_engine_threads() -> None:
    deadline = time.monotonic() + 60
    for t in threading.enumerate():
        if t.name.endswith("LLMEngine"):
            t.join(max(0.0, deadline - time.monotonic()))
            check(not t.is_alive(), f"engine thread {t.name} outlived shutdown")


def main() -> int:
    device = require_tpu()  # exits non-zero here when there is no TPU
    from ray_tpu.util.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache(device["platform"])
    cache = CacheCounter()
    entries0 = _entries(cache_dir)
    say(f"compile cache: dir={cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}) "
        f"entries at start={entries0}")
    sizes = full_sizes()
    phases: dict = {}
    t_start = time.perf_counter()
    try:
        for name, fn in (("device", phase_device), ("kernels", phase_kernels),
                         ("train", phase_train), ("serve", phase_serve)):
            t0 = time.perf_counter()
            phases[name] = fn(sizes)
            say(f"phase {name}: PASSED in {time.perf_counter() - t0:.1f}s")
        if device["count"] >= 4:
            t0 = time.perf_counter()
            phases["four_chip"] = phase_four_chip(sizes)
            say(f"phase four_chip: PASSED in {time.perf_counter() - t0:.1f}s")
            four = "passed"
        else:
            four = f"skipped ({device['count']} devices)"
            say(f"four_chip: {four}")
    finally:
        teardown()
    say(f"compile cache: entries at end={_entries(cache_dir)} hits={cache.hits} "
        f"misses={cache.misses} compile seconds saved by hits={cache.saved_s:.1f}")
    say(f"smoke wall time {time.perf_counter() - t_start:.1f}s")
    summary = {
        "ok": True, "device": device, "four_chip": four,
        "compile_cache": {"dir": cache_dir, "entries_start": entries0,
                          "entries_end": _entries(cache_dir),
                          "hits": cache.hits, "misses": cache.misses,
                          "saved_s": round(cache.saved_s, 1)},
        "wall_s": round(time.perf_counter() - t_start, 1), "claim": None,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.jsonl"), "a") as f:
        f.write(json.dumps({**summary, "phases": phases}, default=str) + "\n")
    say(f"summary: {json.dumps(summary)}")
    print(result_line(device), flush=True)
    return 0


def result_line(device: dict) -> str:
    """The LAST line of stdout, which the driver parses: exactly the keys
    "ok" and "device", and in "device" exactly "platform", "kind", "count".
    Everything else the run learned goes on the `summary:` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


if __name__ == "__main__":
    sys.exit(main())
