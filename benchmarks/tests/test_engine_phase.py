"""The readers of the engine's own records and of the compile counter, on
hand-made ring entries, on a program that lacks them, and on a toy engine
under a profiler session; and the seven metric files that name them."""

import importlib
import inspect
import json
import os

import pytest

from conftest import ROOT

from benchmarks.harness.measure import Measurement
from benchmarks.readers import compile_seconds, engine_phase

NEW = {"decode_host_ms.chat": "serve-chat-steady",
       "decode_dispatch_ms.chat": "serve-chat-steady",
       "decode_copy_ms.chat": "serve-chat-steady",
       "decode_emit_ms.chat": "serve-chat-steady",
       "decode_host_ms.batch": "serve-docs-batch",
       "admit_copy_ms.batch": "serve-docs-batch",
       "compile_s": None}


def _ctx():
    return Measurement(config={}, traffic={}, peaks={})


def _span(name, t0, dur, **args):
    return ["span", 0, "engine", name, 1, t0, dur, args]


@pytest.fixture
def ring(monkeypatch):
    """Hand-made entries in place of the program's ring."""
    from ray_tpu.util import timeline

    decode = lambda t0, d, c, s, f, p: _span(
        "decode", t0, d + 0.1 + c + s + f, dispatch_s=d, wait_s=0.1, copy_s=c,
        sample_s=s, finish_s=f, compile_s=0.0, live=2, ctx=50, profiled=p)
    events = [
        decode(0.0, 0.9, 0.9, 0.9, 0.9, False),            # before the session
        decode(10.0, 0.001, 0.010, 0.003, 0.001, True),
        decode(10.2, 0.002, 0.020, 0.004, 0.002, True),
        decode(10.4, 0.003, 0.030, 0.005, 0.003, True),
        _span("admit", 10.6, 0.2, alloc_s=0.01, prefill_s=0.01, wait_s=0.1,
              copy_s=0.07, sample_s=0.01, queue_wait_s=5.0, compile_s=0.0,
              outcome="admitted", profiled=True),
        _span("admit", 10.8, 0.001, alloc_s=0.001, prefill_s=0.0, wait_s=0.0,
              copy_s=0.0, sample_s=0.0, queue_wait_s=5.0, compile_s=0.0,
              outcome="requeued", profiled=True),
        _span("ops", 10.9, 0.05, kind="attach", profiled=True),
        ["span", 0, "plane", "pull", 1, 10.0, 1.0, {"profiled": True}],
        ["phase", 0, None, 1, 0.0, 0.0, 0.0, 0.0, 0.0, "ok"],
    ]
    monkeypatch.setattr(timeline, "local_events", lambda: list(events))
    return events


def test_quantile_of_the_summed_phases_over_profiled_records(ring):
    ctx = _ctx()
    host = ["dispatch_s", "copy_s", "sample_s", "finish_s"]
    assert engine_phase.read(ctx, "decode", host) == pytest.approx(28.0)
    assert engine_phase.read(ctx, "decode", host, q=100) == pytest.approx(41.0)
    assert engine_phase.read(ctx, "decode", ["copy_s"], scale=1) == pytest.approx(0.020)


def test_outcome_filter(ring):
    ctx = _ctx()
    assert engine_phase.read(ctx, "admit", ["copy_s"], outcome="admitted") == \
        pytest.approx(70.0)
    assert engine_phase.read(ctx, "admit", ["copy_s"], outcome="requeued") == 0.0
    assert engine_phase.read(ctx, "admit", ["copy_s"]) == pytest.approx(35.0)


def test_diag_holds_counts_sums_and_closure(ring):
    ctx = _ctx()
    engine_phase.read(ctx, "decode", ["copy_s"])
    note = ctx.notes["engine_phases"]
    assert note["decode"]["n"] == 3 and note["admit"]["n"] == 2 and note["ops"]["n"] == 1
    assert note["decode"]["copy_s"] == pytest.approx(0.060)
    assert note["decode"]["wait_s"] == pytest.approx(0.3)
    assert "queue_wait_s" not in note["admit"]
    closure = note["closure"]
    assert closure["worst_uncovered_share_of_a_record"] == pytest.approx(0.0, abs=1e-9)
    assert closure["span_s"] == pytest.approx(0.95)
    assert closure["loop_overhead_s"] == pytest.approx(
        0.95 - note["decode"]["dur_s"] - note["admit"]["dur_s"] - 0.05)
    json.dumps(ctx.notes)   # it goes to the diag line


def test_no_such_record_fails_by_name(ring):
    with pytest.raises(SystemExit, match="engine/decode"):
        del ring[1:4]
        engine_phase.read(_ctx(), "decode", ["copy_s"])
    with pytest.raises(SystemExit, match="outcome 'rejected'"):
        engine_phase.read(_ctx(), "admit", ["copy_s"], outcome="rejected")


def test_a_program_without_the_clock_or_the_counter_leaves_the_metric_out(
        ring, monkeypatch):
    """The parent of ISSUE 24 has neither: the readers return None, raise
    nothing, and (the files say optional) the line leaves the metrics out."""
    from benchmarks.harness import spec
    from ray_tpu.util import compile_cache, timeline

    monkeypatch.delattr(timeline, "PhaseClock")
    monkeypatch.delattr(compile_cache, "compile_totals")
    ctx = _ctx()
    assert engine_phase.read(ctx, "decode", ["copy_s"]) is None
    assert compile_seconds.read(ctx) is None
    assert ctx.notes == {}
    cell = spec.Cell("serve-docs-batch")
    new = [m for m in cell.per_layer if m["name"] in NEW]
    assert len(new) == 3
    assert spec.read_metrics(new, ctx) == ({}, [])


def test_compile_counter_is_read_once_with_its_counts(monkeypatch):
    from ray_tpu.util import compile_cache

    monkeypatch.setattr(compile_cache, "compile_totals", lambda: (7, 12.5, 5, 0.5))
    ctx = _ctx()
    assert compile_seconds.read(ctx) == 12.5
    assert ctx.notes["compile"] == {"compiles": 7, "compile_s": 12.5,
                                    "cache_loads": 5, "cache_load_s": 0.5}


def test_the_seven_metric_files_load_and_name_a_reader_that_takes_their_arguments():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    # appended in ISSUE 24's order; what later PRs append comes after them
    assert [n for n in declared if n in NEW] == list(NEW)
    for name, cell in NEW.items():
        with open(os.path.join(ROOT, "benchmarks", "metrics", name + ".json")) as f:
            m = json.load(f)
        assert m["optional"] is True and m["name"] == name
        assert declared[name]["workloads"] == (
            [cell] if cell else ["serve-chat-steady", "serve-docs-batch"])
        assert m["source"] == ("program_span" if cell else "program_counter")
        reader = importlib.import_module(f"benchmarks.readers.{m['reader']['module']}")
        inspect.signature(reader.read).bind(_ctx(), **m["reader"].get("args", {}))


def test_on_a_toy_engine_only_the_session_s_records_count(tmp_path):
    """The readers against the program itself: a paged engine on the CPU,
    one request outside and one inside `jax.profiler.trace`."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=llama.LlamaConfig.tiny(), max_batch_size=2, max_seq_len=64))
    try:
        eng.generate_sync(list(range(1, 11)), 3)
        with jax.profiler.trace(str(tmp_path)):
            eng.generate_sync(list(range(1, 11)), 4)
            eng.shutdown()
    finally:
        eng.shutdown()
    ctx = _ctx()
    host = engine_phase.read(ctx, "decode", ["dispatch_s", "copy_s", "sample_s", "finish_s"])
    copy = engine_phase.read(ctx, "admit", ["copy_s"], outcome="admitted")
    assert host > 0 and copy > 0
    note = ctx.notes["engine_phases"]
    assert note["decode"]["n"] == 3 and note["admit"]["n"] == 1
    assert note["closure"]["worst_uncovered_share_of_a_record"] < 0.01
    assert note["decode"]["compile_s"] == note["admit"]["compile_s"] == 0.0
    assert compile_seconds.read(ctx) > 0 and ctx.notes["compile"]["compiles"] >= 2
