"""The toy family's own test, added with it: its cell through the whole
command at its real (toy) size, and its two per-layer metrics through their
readers: the step's own scalar from the run, the scope's share from a trace
recorded on a TPU (a CPU run has no device trace)."""

import json
import os

from conftest import ROOT


def test_the_toy_cell_runs_and_its_metrics_read(cpu_as_device, capsys, monkeypatch):
    from benchmarks import run
    from benchmarks.harness import spec, xplane

    seen = {}
    read_metrics = spec.read_metrics

    def keep(metrics, ms):
        seen["ms"] = ms
        return read_metrics(metrics, ms)

    monkeypatch.setattr(spec, "read_metrics", keep)
    rc = run.main(["--workload", "train-toy", "--seed", str(2 ** 31 + 5),
                   "--seconds", "1", "--trace", "0"], root=ROOT)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    ms = seen["ms"]
    assert len(ms.series["step.aux"]) == len(ms.series["step.loss"]) == line["attempted"]
    trace = os.path.join(ROOT, "benchmarks", "tests", "fixtures",
                         "decode_scoped_v5e.xplane.pb.gz")
    ms.trace = xplane.reduce_file(trace)
    cell = spec.Cell("train-toy", root=ROOT)
    values, missing = read_metrics(cell.per_layer, ms)
    assert missing == []
    assert set(values) == {"step_ms", "toy_aux_p50", "toy_mlp_scope_share"}
    assert values["toy_aux_p50"]["value"] > 0
    assert 0 < values["toy_mlp_scope_share"]["value"] < 100
