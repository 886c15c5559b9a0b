"""The command end to end: what it does without a TPU, and what its last
line holds."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_metric():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "serve-chat-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr.lower()


def test_an_unknown_device_kind_is_an_error():
    from benchmarks.harness.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks_for("TPU v9 imaginary")


def _cells() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", _cells())
def test_last_line_has_exactly_the_contract_keys(tiny_root, cpu_as_device,
                                                 capsys, workload):
    """Every cell of BENCHMARK.json, a later PR's too, through the whole
    command on its family's CPU stand-in."""
    from benchmarks import run

    rc = run.main(["--workload", workload, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], root=tiny_root)
    assert rc == 0
    last = [l for l in capsys.readouterr().out.splitlines() if l.strip()][-1]
    line = json.loads(last)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_a_declared_metric_that_cannot_be_read_fails_the_command_by_name(
        tiny_root, cpu_as_device, capsys):
    """A metric a cell declares whose reader finds nothing is a yardstick
    that went missing: exit 1, the name on stderr, no result line."""
    from benchmarks import run

    path = os.path.join(tiny_root, "benchmarks", "metrics", "train_tok_s_chip.json")
    with open(path) as f:
        m = json.load(f)
    m["reader"]["args"]["name"] = "a_counter_nobody_keeps"
    with open(path, "w") as f:
        json.dump(m, f)
    argv = ["--workload", "train-4k-1chip", "--seed", "3", "--seconds", "1",
            "--trace", "0"]
    assert run.main(argv, root=tiny_root) == 1
    cap = capsys.readouterr()
    assert "train_tok_s_chip" in cap.err
    assert not any(l.startswith('{"correct"') for l in cap.out.splitlines())
    m["optional"] = True   # said to be optional, it is left out of the line
    with open(path, "w") as f:
        json.dump(m, f)
    assert run.main(argv, root=tiny_root) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert set(last["metrics"]) == {"setup_s"}


def test_a_renamed_engine_callable_fails_by_name():
    """The two callables the check wraps are the seams the yardstick still
    stands on; the loop's methods are none (test_engine_records.py)."""
    from benchmarks.harness.engine_tap import EngineTap

    class Engine:
        def _prefill(self, *a):
            return None

    tap = EngineTap(lambda cfg: None)
    tap.engine = Engine()
    with pytest.raises(SystemExit, match="_decode"):
        tap.capture_logits()
    tap.unwrap()
    assert not hasattr(tap, "record_spans")


def test_a_family_that_does_not_train_says_so_by_name(tiny_root, monkeypatch):
    """A family module may serve only or train only: a cell that asks for the
    missing entry point ends with the family's and the cell's names."""
    from benchmarks.harness import spec

    cell = spec.Cell("train-4k-1chip", root=tiny_root)
    assert callable(cell.family_entry("train_state_and_step"))
    monkeypatch.delattr(cell.family, "train_state_and_step")
    with pytest.raises(SystemExit, match=r"'train-4k-1chip'.*'llama'.*does not train"):
        cell.kind.run(cell, 1, 1.0, False, 0.0, {"platform": "cpu"}, {})
    monkeypatch.delattr(cell.family, "serve_app")
    with pytest.raises(SystemExit, match="does not serve"):
        spec.Cell("serve-docs-batch", root=tiny_root).family_entry("serve_app")
