"""Tests of the benchmark's own code. They run on the CPU:
`python -m pytest benchmarks/tests -q -p no:cacheprovider`."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "tiny")


def tiny_config(family: str, does: str) -> dict:
    """The CPU stand-in of a family's serving or training configurations:
    `fixtures/tiny/<family>-<serve|train>.json`. A family brings its own, and
    a traffic mix its short stand-in, `fixtures/tiny/traffic-<mix>.json`."""
    with open(os.path.join(TINY, f"{family}-{does}.json")) as f:
        return json.load(f)


# the toy model the llama stand-ins share: their published-shape keys
TINY_MODEL = {k: v for k, v in tiny_config("llama", "serve").items()
              if k not in ("family", "reference", "chips", "mesh", "engine", "check")}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-shaped directory whose BENCHMARK.json names the real cells
    and metrics (the real metric files) over toy configurations (each real
    configuration's stand-in, `tiny_config`) and short traffic, so the whole
    command runs on the CPU in seconds."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks"
    for d in ("configs", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"), bench / "metrics")

    def dump(path, obj):
        path.write_text(json.dumps(obj))

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for w in real["workloads"]:
        # the stand-in is found as the cell's parts are: by the real
        # configuration's family, and by whether it serves or trains
        with open(os.path.join(ROOT, "benchmarks", "configs", w["config"] + ".json")) as f:
            cfg = json.load(f)
        does = "serve" if "engine" in cfg else "train"
        w["config"] = f"{cfg['family']}-{does}"
        w["chips"] = 1
        dump(bench / "configs" / (w["config"] + ".json"),
             tiny_config(cfg["family"], does))
        shutil.copy(os.path.join(TINY, f"traffic-{w['traffic']}.json"),
                    bench / "traffic" / (w["traffic"] + ".json"))
    dump(root / "BENCHMARK.json", real)
    return str(root)


@pytest.fixture
def cpu_as_device(monkeypatch):
    """The test-only device stub: the CPU stands where the TPU is required,
    with a made-up row in the peak table."""
    from benchmarks.harness import device, peaks

    monkeypatch.setattr(device, "require_tpu", lambda chips: device.describe())
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
        "source": "test stub"})
