"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the chips the cell
asks for. Everything a cell is made of is found by name from BENCHMARK.json
(`harness/spec.py`). It needs a TPU: with any other backend, or fewer chips
than the cell asks for, it exits 1 and prints no result. The last line of
stdout is one JSON object: `correct`, `attempted`, `failed`, `metrics` (the
cell's end-to-end metrics with `--trace 0`, its per-layer metrics with
`--trace 1`), `device`, and with `--trace 1` `breakdown`. The line before it,
`{"diag": ...}`, is for people. `--set key=value` overrides a number of the traffic file for a
sweep (the driver never passes it).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is clocked from here to the window's opening

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv: list[str] | None = None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    args = ap.parse_args(argv)

    from benchmarks.harness import device, spec
    from benchmarks.harness.peaks import peaks_for

    overrides = {k: json.loads(v) for k, v in (s.split("=", 1) for s in args.set)}
    cell = spec.Cell(args.workload, overrides, root)
    dev = device.require_tpu(cell.chips)        # exits 1 here without the chips
    peaks = peaks_for(dev["kind"])              # exits on an unknown device kind
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache: the program's
    # own rule (ray_tpu/util/compile_cache.py), taken here before anything compiles
    from ray_tpu.util.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache(dev["platform"])
    from benchmarks.harness.measure import log

    log(f"{cell.name}: {dev} compile cache {cache_dir}")

    # the runtime's session directory defaults to the fixed /tmp/ray_tpu; its
    # own environment knob moves it under TMPDIR, which the driver gives each
    # side for itself
    os.environ.setdefault("RAY_TPU_SESSION_DIR_PREFIX",
                          os.path.join(tempfile.gettempdir(), "ray_tpu"))
    ms = cell.kind.run(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, dev, peaks)
    metrics, missing = spec.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ms)
    if missing:
        print(f"[bench] {cell.name} declares {missing} and their readers found "
              f"nothing to read: no result", file=sys.stderr)
        return 1
    dev["memory_peak_bytes"] = int(ms.counters["memory_peak_bytes"])
    line = {"correct": bool(ms.correct), "attempted": int(ms.attempted),
            "failed": int(ms.failed), "metrics": metrics, "device": dev}
    if args.trace:
        if ms.trace is None or ms.trace.busy_s <= 0:
            print("[bench] the traced window holds no device operation",
                  file=sys.stderr)
            return 1
        dev["busy_s"], dev["window_s"] = ms.trace.busy_s, ms.trace.window_s
        line["breakdown"] = ms.trace.breakdown()
    # for people, on the line BEFORE the result: the result line has exactly
    # the contract's keys
    print(json.dumps({"diag": {**ms.notes, "seed": args.seed, "seconds": args.seconds,
                               "setup_s": ms.counters.get("setup_s")}}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
