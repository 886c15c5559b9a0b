"""The three readers of ISSUE 35 (`engine_offcpu`: the engine thread's wall
less CPU and its turns, from the profiled records; `record_ratio`: sums of
fields over sums of fields, from the same; `record_window`: the same over the
whole window's records outside the profiler session) on hand-made ring
entries, on records that lack the fields (a program from before the clock read
CPU), and the ten metric files that name them."""

import importlib
import inspect
import json
import os

import pytest

from conftest import ROOT

from benchmarks.harness.measure import Measurement
from benchmarks.readers import engine_offcpu, engine_phase, record_ratio, record_window

BATCH = ["serve-docs-batch", "serve-ouro-shortin-batch", "serve-kimi-longin-batch"]
NEW = {"loop_turn_ms.batch": BATCH, "loop_turn_ms.chat": ["serve-chat-steady"],
       # docs traces ~17 passes: their CPU is a handful of the clock's 10 ms ticks
       "dispatch_offcpu_ms.batch": BATCH[1:], "dispatch_offcpu_ms.chat": ["serve-chat-steady"],
       "engine_offcpu_share.batch": BATCH, "engine_offcpu_share.chat": ["serve-chat-steady"],
       "stream_wake_ms.batch": BATCH, "stream_wake_ms.chat": ["serve-chat-steady"],
       "stream_cpu_us_per_token.batch": BATCH,
       # docs' detokeniser is a handful of the CPU clock's 10 ms ticks in a trace
       "stream_detok_share.batch": BATCH[1:]}
NONWAIT = ["dispatch", "copy", "sample", "finish", "alloc", "prefill"]
CPU4 = ["st_detok_cpu", "st_relay_cpu", "st_fetch_cpu", "st_write_cpu"]


def _ctx(window=()):
    """`window`: the `dur_s` of the window's decode records, as the harness
    keeps them (`engine_records.reduce`)."""
    return Measurement(config={}, traffic={}, peaks={},
                       series={"decode_step_s": list(window)})


def _span(name, t0, dur, **args):
    return ["span", 0, "engine", name, 1, t0, dur, args]


def _decode(t0, turn, disp, disp_cpu, taken, profiled=True, backlog=0, dur=0.010,
            **more):
    """A pass of 10 ms wall: `dispatch` as given, 5 ms of `wait` without CPU,
    the rest `sample` on the CPU."""
    rest = dur - disp - 0.005
    return _span("decode", t0, dur, turn=turn, turn_cpu=turn / 4,
                 dispatch_s=disp, dispatch_cpu=disp_cpu, wait_s=0.005, wait_cpu=0.0,
                 sample_s=rest, sample_cpu=rest, cpu=disp_cpu + rest,
                 st_taken=taken, st_wake=0.002 * taken, st_detok_cpu=30e-6 * taken,
                 st_relay_cpu=10e-6 * taken, st_fetch_cpu=40e-6 * taken,
                 st_write_cpu=20e-6 * taken, st_backlog=backlog, profiled=profiled,
                 **more)


@pytest.fixture
def ring(monkeypatch):
    """Hand-made entries in place of the program's ring: four profiled passes
    and an admission, tiled by their turns over 10.000-10.100 s."""
    from ray_tpu.util import timeline

    events = [
        _decode(0.0, 0.5, 0.004, 0.0, 99, profiled=False),   # before the session
        _decode(10.000, 0.020, 0.002, 0.001, 10, backlog=3),  # its turn precedes the span
        _decode(10.014, 0.004, 0.003, 0.001, 20, backlog=5),
        _decode(10.030, 0.006, 0.004, 0.001, 30, backlog=7),
        _span("admit", 10.044, 0.040, turn=0.004, turn_cpu=0.001,
              alloc_s=0.001, alloc_cpu=0.001, prefill_s=0.005, prefill_cpu=0.002,
              wait_s=0.030, wait_cpu=0.0, copy_s=0.002, copy_cpu=0.001,
              sample_s=0.002, sample_cpu=0.002, cpu=0.006, outcome="admitted",
              profiled=True),
        _decode(10.090, 0.006, 0.005, 0.001, 0),
        ["span", 0, "plane", "pull", 1, 10.0, 1.0, {"profiled": True}],
    ]
    monkeypatch.setattr(timeline, "local_events", lambda: list(events))
    return events


def test_a_mean_of_wall_less_cpu_over_the_records_of_one_name(ring):
    read = lambda **kw: engine_offcpu.read(_ctx(), ["decode"], ["dispatch"], **kw)
    # dispatch off the CPU: 1, 2, 3, 4 ms; a coarse CPU clock leaves their
    # mean meaningful and no quantile, so the reader has none
    assert read(over="records", scale=1000) == pytest.approx(2.5)
    # with the turn before each record but the first profiled one's
    assert read(turn=True, over="records", scale=1000) == pytest.approx(2.5 + 4.0)
    with pytest.raises(TypeError):
        read(q=50)


def test_a_share_of_the_profiled_span(ring):
    # span 10.000 -> 10.100; turns 4 + 6 + 4 + 6 = 20 ms (the first left out);
    # decode off-CPU 1 + 2 + 3 + 4 (dispatch; sample runs on the CPU, wait is
    # not asked for); admit 0 + 3 + 1 + 0 (alloc, prefill, copy, sample)
    share = engine_offcpu.read(_ctx(), ["decode", "admit"], NONWAIT, turn=True,
                               scale=100)
    assert share == pytest.approx(100 * (0.020 + 0.010 + 0.004) / 0.100)
    only_decode = engine_offcpu.read(_ctx(), ["decode"], NONWAIT, turn=True, scale=100)
    assert only_decode == pytest.approx(100 * (0.016 + 0.010) / 0.100)


def test_diag_holds_the_turns_and_the_phases_off_the_cpu(ring):
    ctx = _ctx()
    engine_offcpu.read(ctx, ["decode"], ["dispatch"])
    note = ctx.notes["engine_offcpu"]
    assert note["decode"]["n"] == 4 and note["admit"]["n"] == 1
    assert note["decode"]["turn"] == pytest.approx(0.016)
    assert note["decode"]["turn_cpu"] == pytest.approx(0.004)
    assert note["decode"]["dispatch_off"] == pytest.approx(0.010)
    assert note["decode"]["wait_off"] == pytest.approx(0.020)
    assert note["decode"]["sample_off"] == pytest.approx(0.0)
    assert note["admit"]["prefill_off"] == pytest.approx(0.003)
    assert note["closure"]["turn"] == pytest.approx(0.020)
    assert 0 < note["closure"]["cpu_tick_s"] <= 0.03
    assert note["decode"]["st_taken"] == 60 and "st_taken" not in note["admit"]
    assert note["decode"]["st_fetch_cpu"] == pytest.approx(60 * 40e-6)
    assert note["decode"]["st_backlog"] == pytest.approx((3 + 5 + 7 + 0) / 4)
    assert "cpu_off" not in note["decode"] and "turn_off" not in note["decode"]
    json.dumps(ctx.notes)   # it goes to the diag line


def test_a_ratio_over_the_traced_window(ring):
    wake = record_ratio.read(_ctx(), "decode", ["st_wake"], ["st_taken"], scale=1000)
    assert wake == pytest.approx(2.0)
    per_token = record_ratio.read(_ctx(), "decode", CPU4, ["st_taken"], scale=1e6)
    assert per_token == pytest.approx(100.0)
    detok = record_ratio.read(_ctx(), "decode", ["st_detok_cpu"], CPU4, scale=100)
    assert detok == pytest.approx(30.0)
    assert record_ratio.read(_ctx(), "admit", ["st_wake"], ["st_taken"]) is None
    with pytest.raises(TypeError):
        record_ratio.read(_ctx(), "decode", ["st_wake"], ["st_taken"], q=50)


# the window of `window_ring`: its decode records' `dur_s`, in order
WINDOW = [0.011, 0.012, 0.010, 0.010, 0.013, 0.014]


@pytest.fixture
def window_ring(monkeypatch):
    """A window of six passes and an admission, two of the passes under a
    profiler session, with records before it (the fill) and after (the
    drain) that the harness did not count."""
    from ray_tpu.util import timeline

    events = [
        _decode(1.0, 0.9, 0.004, 0.0, 50, profiled=False, dur=0.011),   # the fill's
        _decode(2.0, 0.001, 0.002, 0.0, 10, profiled=False, dur=0.011),
        _decode(2.1, 0.002, 0.002, 0.0, 20, profiled=False, dur=0.012, backlog=4),
        _decode(2.2, 0.050, 0.008, 0.001, 5, dur=0.010),   # under the tracer
        _decode(2.3, 0.060, 0.008, 0.001, 5, dur=0.010),
        _span("admit", 2.4, 0.1, turn=0.003, alloc_s=0.001, wait_s=0.099,
              queue_wait_s=5.0, outcome="admitted", profiled=False),
        _decode(2.5, 0.001, 0.003, 0.0, 30, profiled=False, dur=0.013),
        _decode(2.6, 0.002, 0.003, 0.0, 0, profiled=False, dur=0.014),
        _decode(9.0, 0.7, 0.003, 0.0, 99, profiled=False, dur=0.010),    # the drain's
    ]
    monkeypatch.setattr(timeline, "local_events", lambda: list(events))
    return events


def test_the_window_s_records_outside_the_session(window_ring):
    found = record_window.window_records(_ctx(WINDOW), window_ring)
    assert [r[1] for r in found] == [2.0, 2.1, 2.2, 2.3, 2.4, 2.5, 2.6]
    ctx = _ctx(WINDOW)
    # turns a pass: the four passes' and the admission's over four passes; the
    # two traced passes' 50 and 60 ms are the tracer's and stay out
    turn = record_window.read(ctx, ["turn"], name="decode", scale=1000)
    assert turn == pytest.approx((1 + 2 + 3 + 1 + 2) / 4)
    wake = record_window.read(ctx, ["st_wake"], ["st_taken"], scale=1000)
    assert wake == pytest.approx(2.0)
    note = ctx.notes["engine_window"]
    assert note["decode"]["n"] == 4 and note["admit"]["n"] == 1
    assert note["decode"]["dispatch_s"] == pytest.approx(0.010)
    assert note["decode"]["st_taken"] == 60 and note["decode"]["st_backlog"] == 4
    assert note["admit"]["turn"] == pytest.approx(0.003)
    assert "queue_wait_s" not in note["admit"] and "turn_cpu" not in note["decode"]
    json.dumps(ctx.notes)


def test_a_window_that_is_not_found_leaves_the_metric_out(window_ring):
    assert record_window.window_records(_ctx(), window_ring) == []
    assert record_window.window_records(_ctx(WINDOW + [0.5]), window_ring) == []
    assert record_window.read(_ctx(), ["turn"]) is None
    assert record_window.read(_ctx(WINDOW[::-1]), ["turn"]) is None
    # nothing streamed in the window: no ratio
    for e in window_ring:
        e[7].update(st_taken=0, st_wake=0.0)
    assert record_window.read(_ctx(WINDOW), ["st_wake"], ["st_taken"]) is None


def test_records_without_the_fields_leave_every_metric_out(ring):
    """The parent of ISSUE 35 clocks no CPU, no turn and no stream: the readers
    return None and raise nothing, the files say optional, and the line leaves
    the ten metrics out."""
    from benchmarks.harness import spec

    for e in ring:
        e[7] = {k: v for k, v in e[7].items()
                if not (k.endswith("_cpu") or k.startswith(("st_", "turn")) or k == "cpu")}
    ctx = _ctx([0.010] * 4)
    assert engine_offcpu.read(ctx, ["decode"], ["dispatch"], over="records") is None
    assert engine_offcpu.read(ctx, ["decode", "admit"], NONWAIT, turn=True) is None
    assert record_ratio.read(ctx, "decode", ["st_wake"], ["st_taken"]) is None
    assert record_window.window_records(ctx, ring)
    assert record_window.read(ctx, ["turn"]) is None
    assert record_window.read(ctx, ["st_wake"], ["st_taken"]) is None
    assert "engine_offcpu" not in ctx.notes and "engine_window" not in ctx.notes
    for name in ("serve-kimi-longin-batch", "serve-chat-steady"):
        new = [m for m in spec.Cell(name).per_layer if m["name"] in NEW]
        assert len(new) == (6 if name.endswith("batch") else 4)
        assert spec.read_metrics(new, ctx) == ({}, [])
    docs = [m["name"] for m in spec.Cell("serve-docs-batch").per_layer]
    assert "stream_detok_share.batch" not in docs and "dispatch_offcpu_ms.batch" not in docs


def test_nothing_streamed_leaves_the_streams_metrics_out(ring):
    for e in ring:
        if "st_taken" in e[7]:
            e[7].update(st_taken=0, st_wake=0.0, **dict.fromkeys(CPU4, 0.0))
    assert record_ratio.read(_ctx(), "decode", ["st_wake"], ["st_taken"]) is None
    assert record_ratio.read(_ctx(), "decode", ["st_detok_cpu"], CPU4) is None


@pytest.mark.parametrize("name", list(NEW))
def test_the_metric_file_loads_and_names_a_reader_that_takes_its_arguments(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    # appended in this order, after everything that was there
    assert [n for n in declared if n in NEW] == list(NEW)
    assert list(declared)[-len(NEW):] == list(NEW)
    with open(os.path.join(ROOT, "benchmarks", "metrics", name + ".json")) as f:
        m = json.load(f)
    assert m["optional"] is True and m["name"] == name and "workloads" not in m
    assert declared[name]["workloads"] == NEW[name]
    assert declared[name] == {**{k: m[k] for k in (
        "name", "unit", "better", "source", "layer", "moves")}, "workloads": NEW[name]}
    assert m["source"] == "program_span" and m["unit"] in ("ms", "%", "us")
    assert m["layer"] == ("serve host path" if name.startswith("stream_") else "engine loop")
    assert m["moves"] == ("tpot_p50_ms" if name.endswith(".chat") else "served_tok_s")
    reader = importlib.import_module(f"benchmarks.readers.{m['reader']['module']}")
    inspect.signature(reader.read).bind(_ctx(), **m["reader"].get("args", {}))


def test_on_a_toy_engine_the_records_carry_what_the_readers_read(tmp_path):
    """The readers against the program itself: a paged engine on the CPU, a
    stream of five tokens beside a plain request that outlasts it, whose
    records note the stream's tail; once outside `jax.profiler.trace` and
    once inside."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=llama.LlamaConfig.tiny(), max_batch_size=2, max_seq_len=64))
    try:
        longer = eng.generate(list(range(20, 30)), 12)
        assert len(list(eng.generate_stream(list(range(1, 11)), 5))) == 5
        assert longer.result(timeout=120).num_generated == 12
        with jax.profiler.trace(str(tmp_path)):
            longer = eng.generate(list(range(20, 30)), 12)   # keeps the loop busy
            assert len(list(eng.generate_stream(list(range(1, 11)), 5))) == 5
            assert longer.result(timeout=120).num_generated == 12
            eng.shutdown()
    finally:
        eng.shutdown()
    events = timeline.local_events()
    # the harness's window: every decode record of the run, by its `dur_s`
    ctx = _ctx([e[6] for e in events if e[2] == "engine" and e[3] == "decode"])
    off = engine_offcpu.read(ctx, ["decode"], ["dispatch"], over="records", scale=1000)
    share = engine_offcpu.read(ctx, ["decode", "admit"], NONWAIT, turn=True, scale=100)
    assert off > -0.01 and 0 < share < 100   # the two clocks' grain
    assert record_ratio.read(ctx, "decode", CPU4, ["st_taken"]) == 0.0
    # no front end and no detokeniser ran: the four stages cost nothing
    assert record_ratio.read(ctx, "decode", ["st_detok_cpu"], CPU4) is None
    note = ctx.notes["engine_offcpu"]
    assert note["decode"]["n"] >= 11 and note["admit"]["n"] == 2
    # the loop never rested between the profiled records, so the turns ARE what
    # the other reader's closure gets by subtraction
    closure = engine_phase.summary(engine_phase.profiled_records(events))["closure"]
    assert note["closure"]["turn"] == pytest.approx(closure["loop_overhead_s"], rel=0.05)
    # outside the session: the same fields but the CPU, read by the third reader
    assert record_window.read(ctx, ["turn"], scale=1000) > 0
    assert record_window.read(ctx, ["st_wake"], ["st_taken"], scale=1000) > 0
    window = ctx.notes["engine_window"]
    # (both admissions came before the first decode record: the window, as the
    # harness has it, runs from its first decode record to its last)
    assert window["decode"]["n"] >= 11 and set(window) == {"decode"}
    assert window["decode"]["st_taken"] >= 4 and "dispatch_cpu" not in window["decode"]
