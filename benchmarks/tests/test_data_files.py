"""BENCHMARK.json against the contract's limits, and against the files that
the harness finds by the names in it."""

import importlib
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _file(*parts):
    with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
        return json.load(f)


def test_keys_names_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    assert bench["paths"] == ["benchmarks"] and 1 <= bench["run_seconds"] <= 51
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[section]:
            assert NAME.match(e["name"]), e["name"]
            names.append((section in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"] and "\t" not in e["why"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_every_cell_is_made_of_files_found_by_name(bench):
    from benchmarks.harness import spec

    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        assert configs[w["config"]]["file"] == f"benchmarks/configs/{w['config']}.json"
        assert cell.config["chips"] == w["chips"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert callable(cell.kind.run) and callable(cell.kind.plan)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
        for m in cell.end_to_end + cell.per_layer:
            reader = importlib.import_module(
                f"benchmarks.readers.{m['reader']['module']}")
            assert callable(reader.read)
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}


def test_metric_files_say_what_benchmark_json_says(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        f = _file("metrics", m["name"] + ".json")
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert f.get(key) == m.get(key), (m["name"], key)
        # which cells report a metric is said once, in BENCHMARK.json: a new
        # cell appends its name there and edits no metric file
        assert "workloads" not in f, m["name"]
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} |" in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_configurations_keep_every_published_width(bench):
    """Against the published shape each configuration names
    (`benchmarks/configs/published/<published_config>.json`): the keys that
    differ are exactly `reduced`, in BENCHMARK.json and in the file, none of
    them is one of the source's `widths`, and the sources agree."""
    for c in bench["configs"]:
        cfg = _file("configs", c["name"] + ".json")
        pub = _file("configs", "published", cfg["published_config"] + ".json")
        missing = object()
        changed = {k for k, v in pub["config"].items() if cfg.get(k, missing) != v}
        assert changed == set(c["reduced"]) == set(cfg["reduced"]), c["name"]
        assert cfg["source"] == c["source"] == pub["source"]
        assert set(pub["widths"]) <= set(pub["config"])
        assert not changed & set(pub["widths"]), c["name"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in changed)


def test_no_topology_call_and_no_jax_at_import_time():
    """Importing the harness touches no device, and the load generator's
    process never imports jax."""
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r);"
            "import benchmarks.run, benchmarks.harness.spec, benchmarks.harness.schedule,"
            "benchmarks.harness.loadgen, benchmarks.harness.xplane, benchmarks.harness.shapes,"
            "benchmarks.harness.serve_cell, benchmarks.harness.train_cell;"
            "assert 'jax' not in sys.modules, 'jax imported'") % ROOT
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    for dirpath, _, files in os.walk(os.path.join(ROOT, "benchmarks")):
        for name in files:
            if name.endswith(".py") and "tests" not in dirpath:
                with open(os.path.join(dirpath, name)) as f:
                    assert "get_topology_desc" not in f.read(), name
