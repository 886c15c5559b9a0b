"""One in-process timing of one training shape on the attached TPU.

Not the benchmark (ROADMAP S1 builds that: cells, a trace reduction, bounds).
This only says how fast the SPMD train step runs for one shape that fits one
v5e chip — `LlamaConfig.llama_1b()` at full depth, 2 x 2048 tokens, dots
remat, the shape `chip_smoke.py` trains — and says it truthfully: it needs a
TPU and exits non-zero without one, it opens the chip once, from this
process, and it prints the device beside the number.

Prints ONE JSON line: tokens/s/chip, the 6N model-FLOP/s utilization against
the chip's published peak, platform, device_kind, device count, the shape.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

# bf16 peak FLOP/s per chip by jax `device_kind`. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16). A kind that is not listed is
# an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}

BATCH, SEQ, WARMUP_STEPS, TIMED_STEPS = 2, 2048, 2, 10


def main() -> int:
    import jax

    if jax.default_backend() != "tpu":
        print(f"bench.py needs a TPU: jax's backend here is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.train import spmd
    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache("tpu")
    device = jax.devices()[0]
    peak = PEAK_BF16_FLOPS.get(device.device_kind)
    if peak is None:
        print(f"bench.py has no published peak for device_kind "
              f"{device.device_kind!r}; add it with its source", file=sys.stderr)
        return 1

    cfg = dataclasses.replace(llama.LlamaConfig.llama_1b(), max_seq_len=SEQ,
                              remat_policy="dots")
    mesh = make_mesh(1)
    optimizer = spmd.make_optimizer(warmup=1)
    key = jax.random.PRNGKey(0)
    state = spmd.init_state(cfg, key, optimizer=optimizer)
    step = spmd.make_train_step(cfg, mesh, optimizer=optimizer)(state)
    tokens = jax.random.randint(key, (BATCH, SEQ), 0, cfg.vocab_size)
    for _ in range(WARMUP_STEPS):  # compile, then one executed step
        state, metrics = step(state, tokens, tokens)
    jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(TIMED_STEPS):
        state, metrics = step(state, tokens, tokens)
    jax.block_until_ready(metrics["loss"])
    tokens_per_sec = BATCH * SEQ * TIMED_STEPS / (time.perf_counter() - t0)

    n_params = llama.param_count_analytic(cfg)
    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        # 6 * parameters * tokens/s over peak: leaves attention's own
        # operations out and counts the embedding table, so it is a rough
        # utilization, not S1's count of required operations
        "mfu_6n": round(6 * n_params * tokens_per_sec / peak, 4),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "shape": {"model": "llama_1b", "params": n_params, "batch": BATCH,
                  "seq": SEQ, "remat": cfg.remat_policy},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
