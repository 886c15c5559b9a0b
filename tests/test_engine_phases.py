"""The serving engine clocks its own step: one `engine` record per admission,
decode step and PD op in the timeline ring, the same phases as
TraceAnnotations in a profile, a compile counter, and a shutdown that ends
queued requests too (ISSUE 24)."""

import dataclasses
import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
from ray_tpu.util import timeline

NOT_PHASES = ("compile_s", "queue_wait_s")


@pytest.fixture(scope="module")
def shared_params():
    import jax

    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.init(cfg, jax.random.PRNGKey(7))


def _engine(shared_params, **kw):
    cfg, params = shared_params
    kw = {"max_batch_size": 4, "max_seq_len": 128, "block_size": 16, **kw}
    return PagedLLMEngine(PagedLLMConfig(model_config=cfg, **kw), params=params)


def _records(name=None):
    """(args, dur_s) of the ring's engine records, oldest first."""
    return [(e[7], e[6]) for e in timeline.local_events()
            if e[0] == "span" and e[2] == "engine" and name in (None, e[3])]


def _phase_sum(args):
    return sum(v for k, v in args.items()
               if k.endswith("_s") and k not in NOT_PHASES)


@pytest.fixture
def one_request(shared_params):
    """20 prompt tokens, 6 new ones: one admission and five decode steps.
    Records are read after shutdown(): the loop thread has joined, so the
    step that finished the request has closed its record."""
    timeline.clear()
    eng = _engine(shared_params)
    try:
        out = eng.generate_sync(list(range(1, 21)), 6)
    finally:
        eng.shutdown()
    assert out.num_generated == 6
    return _records()


def test_one_request_leaves_one_admit_record(one_request):
    admits = [a for a, _ in _records("admit")]
    assert len(admits) == 1
    a = admits[0]
    assert (a["outcome"], a["prompt"], a["bucket"], a["cached"], a["slot"]) == (
        "admitted", 20, 32, 0, 0)
    assert a["queue_wait_s"] >= 0 and a["compile_s"] >= 0
    assert a["profiled"] is False
    assert {"alloc_s", "prefill_s", "wait_s", "copy_s", "sample_s"} <= set(a)


def test_admit_record_counts_the_one_row_the_copy_brought(one_request, shared_params):
    """The prefill hands the host the row it samples from and no other: the
    `copy` phase is still there (a metric reads `copy_s`), and `logits_bytes`
    is one float32 row of the vocabulary, not the 32 bucket's."""
    (a, _), = _records("admit")
    assert a["copy_s"] > 0
    assert a["logits_bytes"] == 4 * shared_params[0].vocab_size


def test_decode_records_follow_the_request(one_request):
    steps = [a for a, _ in _records("decode")]
    assert [s["live"] for s in steps] == [1] * 5
    assert [s["ctx"] for s in steps] == [20, 21, 22, 23, 24]
    assert all({"dispatch_s", "wait_s", "copy_s", "sample_s", "finish_s"} <= set(s)
               for s in steps)


def test_decode_records_say_how_far_ahead_the_loop_ran(one_request):
    """The loop reads one step behind (ISSUE 34): the first pass enqueues a
    step and has none to read, every later one enqueues while the step before
    is unread (`ahead`), and the last reads two, its own too."""
    steps = [a for a, _ in _records("decode")]
    assert [s["ahead"] for s in steps] == [False, True, True, True, True]
    assert [s["late_rows"] for s in steps] == [0] * 5
    assert all(s["dispatch_s"] > 0 for s in steps)
    assert steps[0]["wait_s"] == steps[0]["copy_s"] == steps[0]["sample_s"] == 0.0
    assert all(s["copy_s"] > 0 and s["sample_s"] > 0 for s in steps[1:])


@pytest.mark.parametrize("name", ["admit", "decode"])
def test_phases_tile_the_record(one_request, name):
    recs = _records(name)
    assert recs
    for args, dur in recs:
        assert _phase_sum(args) == pytest.approx(dur, rel=0.01)


def test_profiled_only_inside_a_profiler_session(shared_params, tmp_path):
    """`profiled` is jax's own notion of tracing on, and the same phases are
    TraceAnnotations, nested, on the host plane of the profile."""
    import jax
    from jax.profiler import ProfileData

    timeline.clear()
    eng = _engine(shared_params)
    try:
        eng.generate_sync(list(range(1, 21)), 3)
        while eng.stats()["active_slots"]:
            time.sleep(0.01)
        time.sleep(0.05)  # the step that finished it has closed its record
        outside = _records()
        with jax.profiler.trace(str(tmp_path)):
            eng.generate_sync(list(range(30, 50)), 3)
            eng.shutdown()
    finally:
        eng.shutdown()
    inside = _records()[len(outside):]
    assert outside and not any(a["profiled"] for a, _ in outside)
    assert len(inside) == 3 and all(a["profiled"] for a, _ in inside)

    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(found[-1]).planes
                if p.name == "/host:CPU")
    spans = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("engine:"):
                spans.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    assert {"engine:admit", "engine:admit.copy", "engine:decode",
            "engine:decode.dispatch", "engine:decode.wait", "engine:decode.copy",
            "engine:decode.sample", "engine:decode.finish"} <= set(spans)
    for line, s, e in spans["engine:decode.wait"]:
        assert any(ln == line and s0 <= s and e <= e0
                   for ln, s0, e0 in spans["engine:decode"])


def test_pool_too_small_requeues_then_admits(shared_params):
    timeline.clear()
    # 4 usable blocks of 16: one request of 30 + 20 tokens takes them all
    eng = _engine(shared_params, num_blocks=5)
    try:
        futs = [eng.generate(list(range(k, k + 30)), 20) for k in (1, 50)]
        assert all(f.result(timeout=120).num_generated == 20 for f in futs)
    finally:
        eng.shutdown()
    outcomes = [(a["outcome"], a["prompt"]) for a, _ in _records("admit")]
    assert outcomes[0] == ("admitted", 30) and outcomes[-1] == ("admitted", 30)
    assert ("requeued", 30) in outcomes
    requeued = next(a for a, _ in _records("admit") if a["outcome"] == "requeued")
    assert requeued["prefill_s"] == requeued["copy_s"] == 0.0


def test_second_identical_prompt_is_cached(shared_params):
    timeline.clear()
    eng = _engine(shared_params)
    try:
        for _ in range(2):
            eng.generate_sync(list(range(1, 41)), 4)
    finally:
        eng.shutdown()
    first, second = [a for a, _ in _records("admit")]
    assert (first["cached"], first["bucket"]) == (0, 128)
    assert (second["cached"], second["bucket"]) == (32, 32)  # two full blocks


def test_compiles_rise_with_a_new_bucket_only(shared_params):
    eng = _engine(shared_params, prefill_buckets=(32, 64))
    try:
        before = eng.stats()
        eng.generate_sync(list(range(1, 21)), 3)
        first = eng.stats()
        eng.generate_sync(list(range(40, 65)), 3)   # the same bucket, 32
        second = eng.stats()
    finally:
        eng.shutdown()
    assert first["compiles"] >= before["compiles"] + 2   # prefill and decode
    assert first["compile_s"] > before["compile_s"]
    assert (second["compiles"], second["compile_s"]) == (
        first["compiles"], first["compile_s"])
    compiled = [a["compile_s"] > 0 for a, _ in _records("admit")[-2:]]
    assert compiled == [True, False]


def test_pd_ops_leave_one_record_each(shared_params):
    timeline.clear()
    eng = _engine(shared_params)
    try:
        handoff = eng.prefill_extract(list(range(1, 21)))
        eng.attach_sequence(handoff, 3).result(timeout=120)
    finally:
        eng.shutdown()
    assert [a["kind"] for a, _ in _records("ops")] == ["prefill_extract", "attach"]


def test_spec_decode_step_records_its_draft_phase():
    from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine

    tiny = dataclasses.replace(llama.LlamaConfig.tiny(), vocab_size=128)
    timeline.clear()
    eng = SpecDecodeLLMEngine(SpecDecodeConfig(
        model_config=tiny, draft_model_config=tiny, max_batch_size=2,
        max_seq_len=128, num_speculative_tokens=3))
    try:
        eng.generate_sync([5, 17, 3, 42], 8)
    finally:
        eng.shutdown()
    steps = _records("decode")
    assert steps and len(_records("admit")) == 1
    for args, dur in steps:
        assert args["draft_s"] > 0 and args["live"] == 1
        assert _phase_sum(args) == pytest.approx(dur, rel=0.01)


def test_export_draws_the_engine_records(one_request):
    drawn = [ev for ev in timeline.export() if ev.get("cat") == "engine"]
    assert [ev["name"] for ev in drawn].count("decode") == 5
    admit = next(ev for ev in drawn if ev["name"] == "admit")
    assert admit["ph"] == "X" and admit["args"]["outcome"] == "admitted"
    assert admit["dur"] == pytest.approx(
        1e6 * _phase_sum(_records("admit")[0][0]), abs=2)


def test_phase_clock_zero_fills_and_sums_repeats():
    timeline.clear()
    clock = timeline.PhaseClock("t", "loop", ("a", "b", "c"))
    clock.mark("b")
    clock.note(k=1, n=0)
    clock.mark("a")
    clock.note(k=2)
    clock.close(n=1)
    (args, dur), = [(e[7], e[6]) for e in timeline.local_events() if e[2] == "t"]
    assert args["c_s"] == 0.0 and args["n"] == 1 and args["profiled"] is False
    assert args["k"] == 2   # the later note; what `close` is given goes over both
    assert args["a_s"] + args["b_s"] == pytest.approx(dur, rel=1e-6)
    bare = timeline.PhaseClock("t", "bare")
    bare.close(kind="x")
    assert timeline.local_events()[-1][7] == {"kind": "x", "profiled": False}


def test_timeline_still_imports_without_jax():
    code = ("import sys, ray_tpu.util.timeline as t; "
            "assert 'jax' not in sys.modules; t.record_span('c', 'n', 0.0, 1.0)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_shutdown_ends_a_queued_stream_within_a_second(shared_params):
    """A request queued behind a full batch never reaches a slot; shutdown()
    fails its future and ends its stream at once, not after the stream's
    300 s poll."""
    eng = _engine(shared_params, max_batch_size=1)
    ended = {}

    def consume():
        try:
            list(eng.generate_stream(list(range(50, 60)), 20))
            ended["error"] = None
        except Exception as e:  # noqa: BLE001 - what the stream raised is the result
            ended["error"] = e
        ended["at"] = time.monotonic()

    try:
        running = eng.generate(list(range(1, 11)), 100)
        while not eng.stats()["active_slots"]:
            time.sleep(0.01)
        queued = eng.generate(list(range(20, 30)), 20)
        consumer = threading.Thread(target=consume, daemon=True)
        consumer.start()
        while eng.stats()["pending"] < 2:
            time.sleep(0.01)
        t0 = time.monotonic()
    finally:
        eng.shutdown()
    consumer.join(timeout=5)
    assert not consumer.is_alive() and ended["at"] - t0 < 1.0
    assert "shut down" in str(ended["error"])
    for fut in (running, queued):
        with pytest.raises(RuntimeError, match="shut down"):
            fut.result(timeout=1)
    assert eng.stats()["pending"] == 0
