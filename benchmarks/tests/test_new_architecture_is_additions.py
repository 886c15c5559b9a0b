"""The rehearsal of a PR that adds an architecture: on a copy of the tree a
toy family, its configuration, published shape, CPU stand-in, reference, one
cell and two per-layer metrics are ADDED (`fixtures/toy_family/`), the
cell's name is appended where BENCHMARK.json lists cells, and the copy's own
tests pass with no file that was there modified. Such a PR may add files and
entries and may edit no file under `benchmarks/`: what forces an edit here
would refuse it."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "toy_family")
PLACES = {   # file of fixtures/toy_family -> where the PR would add it
    "toy.py": "benchmarks/harness/families/toy.py",
    "toy_reference.py": "benchmarks/reference/toy_reference.py",
    "config-toy-2l.json": "benchmarks/configs/toy-2l.json",
    "published-Toy-2L.json": "benchmarks/configs/published/Toy-2L.json",
    "tiny-toy-train.json": "benchmarks/tests/fixtures/tiny/toy-train.json",
    "metric-toy_aux_p50.json": "benchmarks/metrics/toy_aux_p50.json",
    "metric-toy_mlp_scope_share.json": "benchmarks/metrics/toy_mlp_scope_share.json",
    "check_toy_cell.py": "benchmarks/tests/test_toy_cell.py",
}


def _hashes(root: str) -> dict:
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "benchmarks")):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_new_family_cell_and_metrics_are_additions(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", ".pytest_cache"))
    for name in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(os.path.join(ROOT, name), root)
    before = _hashes(root)

    # -- the additions: new files ...
    for name, place in PLACES.items():
        assert place not in before, place
        shutil.copy(os.path.join(TOY, name), os.path.join(root, place))
    # ... and new entries in BENCHMARK.json, nothing of what is there changed
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))
    with open(os.path.join(TOY, "config-toy-2l.json")) as f:
        source = json.load(f)["source"]
    bench["configs"].append({
        "name": "toy-2l", "source": source, "file": "benchmarks/configs/toy-2l.json",
        "reduced": ["num_hidden_layers"], "why": "the rehearsal's toy family: trains only"})
    bench["workloads"].append({
        "name": "train-toy", "config": "toy-2l", "traffic": "pretrain-4k", "chips": 1,
        "why": "the rehearsal's cell: a new family under a traffic mix that is there"})
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for metric in ("train_tok_s_chip", "step_ms"):
        by_name[metric]["workloads"].append("train-toy")
    for name in ("toy_aux_p50", "toy_mlp_scope_share"):
        with open(os.path.join(TOY, f"metric-{name}.json")) as f:
            m = json.load(f)
        bench["per_layer"].append({**{k: m[k] for k in (
            "name", "unit", "better", "source", "layer", "moves")},
            "workloads": ["train-toy"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)

    # -- the copy's own tests: the data files against the contract, the new
    # cell through the whole command on its family's stand-in, and the test
    # the family brought
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([ROOT, os.environ.get("PYTHONPATH", "")])}
    tests = os.path.join(root, "benchmarks", "tests")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-x",
         os.path.join(tests, "test_data_files.py"),
         os.path.join(tests, "test_engine_phase.py")
         + "::test_the_seven_metric_files_load_and_name_a_reader_that_takes_their_arguments",
         os.path.join(tests, "test_command.py")
         + "::test_last_line_has_exactly_the_contract_keys[train-toy]",
         os.path.join(tests, "test_toy_cell.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert " passed" in p.stdout and "failed" not in p.stdout

    # -- nothing that was there changed
    after = _hashes(root)
    changed = sorted(k for k, v in before.items() if after.get(k) != v)
    assert changed == []
    assert sorted(set(after) - set(before)) == sorted(PLACES.values())
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[section], bench[section]):
            grew = {**now, "workloads": now["workloads"][:len(was["workloads"])]} \
                if "workloads" in was else now
            assert was == grew, was["name"]
    assert {k: bench[k] for k in ("command", "paths", "run_seconds")} == \
        {k: old[k] for k in ("command", "paths", "run_seconds")}
