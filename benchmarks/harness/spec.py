"""Finds everything a cell needs by the names in BENCHMARK.json.

A cell is `{name, config, traffic, chips, why}`. Its configuration is
`benchmarks/configs/<config>.json`, whose `family` names a module under
`benchmarks/harness/families/` (what the drivers take from the program for
that architecture), whose `reference` a module under `benchmarks/reference/`
and whose `published_config` the published shape it was cut from
(`benchmarks/configs/published/`); its traffic is `benchmarks/traffic/
<traffic>.json`, the traffic's kind a module `benchmarks/harness/
traffic_kinds/<kind>.py`, and each metric `benchmarks/metrics/<name>.json`
naming a reader module under `benchmarks/readers/`. Which cells report a
metric is said in BENCHMARK.json alone (`workloads`). Adding a cell, a mix,
a metric or an architecture adds files and appends entries and edits no file
(`benchmarks/tests/test_new_architecture_is_additions.py` holds that).
"""

from __future__ import annotations

import functools
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    def __init__(self, name: str, overrides: dict | None = None, root: str = ROOT):
        self.root = root
        _load = self._load
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = _load("configs", self.entry["config"] + ".json")
        # imports no jax: a family module reaches the program inside its functions
        self.family = importlib.import_module(
            f"benchmarks.harness.families.{self.config['family']}")
        self.config["model"] = {k: self.config[k] for k in self.family.MODEL_KEYS}
        self.traffic = _load("traffic", self.entry["traffic"] + ".json")
        for key, value in (overrides or {}).items():
            self.traffic[key] = value
        self.kind = importlib.import_module(
            f"benchmarks.harness.traffic_kinds.{self.traffic['kind']}")
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    @functools.cached_property
    def reference(self):
        """The plain reference of this configuration (imports jax)."""
        return importlib.import_module(
            f"benchmarks.reference.{self.config['reference']}")

    def family_entry(self, name: str):
        """An entry point of the family module that this cell's driver needs.
        A family may serve only or train only; a cell that asks it for the
        other says so here, by name, before anything is built."""
        fn = getattr(self.family, name, None)
        if not callable(fn):
            raise SystemExit(
                f"benchmark: cell {self.name!r} needs `{name}` of the family "
                f"{self.config['family']!r} and benchmarks/harness/families/"
                f"{self.config['family']}.py has none: that family does not "
                f"{'train' if name.startswith('train') else 'serve'}")
        return fn

    def _load(self, *parts: str) -> dict:
        with open(os.path.join(self.root, "benchmarks", *parts)) as f:
            return json.load(f)

    def _metrics(self, entries: list) -> list[dict]:
        """The metric files of the entries that this cell reports."""
        out = []
        for e in entries:
            if "workloads" in e and self.name not in e["workloads"]:
                continue
            out.append({**e, **self._load("metrics", e["name"] + ".json")})
        return out


def read_metrics(metrics: list[dict], ctx) -> tuple[dict, list[str]]:
    """{name: {"value", "unit"}} for every metric whose reader found something
    to read, and the names of those whose reader found nothing. A reader that
    returns None leaves its metric out of the line; the command then fails
    and names it, unless the metric's file says `"optional": true`: a metric
    that a cell declares and cannot read is a yardstick that went missing
    (a renamed span, an operation the trace no longer holds), not a result."""
    out, missing = {}, []
    for m in metrics:
        reader = importlib.import_module(f"benchmarks.readers.{m['reader']['module']}")
        value = reader.read(ctx, **m["reader"].get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        elif not m.get("optional"):
            missing.append(m["name"])
    return out, missing
