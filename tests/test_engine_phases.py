"""The serving engine clocks its own step: one `engine` record per admission,
decode step and PD op in the timeline ring, the same phases as
TraceAnnotations in a profile, a compile counter, and a shutdown that ends
queued requests too (ISSUE 24). Since ISSUE 35 the time between two records is
the later one's `turn`, the `decode` records carry what the streams counted,
and under a profiler session the clock reads the thread's CPU beside the wall."""

import dataclasses
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

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


def test_admit_copies_the_one_row_the_prefill_hands_back(shared_params):
    """The prefill hands the host the row it samples from and no other: the
    `copy` phase is still there (a metric reads `copy_s`), and what it copies
    is one float32 row of the vocabulary, not the 32 bucket's (the record's
    constant `logits_bytes` said so until ISSUE 35; the step's result does)."""
    timeline.clear()
    eng = _engine(shared_params)
    prefill, handed = eng._prefill, []

    def spy(*args):
        logits, pool = prefill(*args)
        handed.append((logits.shape, logits.dtype.name))
        return logits, pool

    eng._prefill = spy
    try:
        eng.generate_sync(list(range(1, 21)), 2)
    finally:
        eng.shutdown()
    (a, _), = _records("admit")
    assert a["copy_s"] > 0 and "logits_bytes" not in a
    assert handed == [((1, shared_params[0].vocab_size), "float32")]


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


def _cpu_tick():
    """The grain of `time.thread_time()` on this machine: the smallest step it
    makes while this thread spins for 50 ms (nanoseconds on Linux, 10 ms under
    some sandboxes, where ONE record's `_cpu` is 0 or a whole tick)."""
    end, last, tick = time.monotonic() + 0.05, time.thread_time(), 0.05
    while time.monotonic() < end:
        c = time.thread_time()
        if c != last:
            tick, last = min(tick, c - last), c
    return tick


@pytest.fixture(scope="module")
def profiled_request(shared_params, tmp_path_factory):
    """`one_request` inside a profiler session, after a request outside one
    that took the compiles: -> ({name: [(args, dur_s)]}, the CPU clock's tick)."""
    import jax

    timeline.clear()
    eng = _engine(shared_params)
    try:
        eng.generate_sync(list(range(1, 21)), 6)
        while eng.stats()["active_slots"]:
            time.sleep(0.01)
        time.sleep(0.05)
        seen = len(_records())
        with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
            eng.generate_sync(list(range(30, 50)), 6)
            eng.shutdown()
    finally:
        eng.shutdown()
    recs = [(e[3], e[7], e[6]) for e in timeline.local_events()
            if e[0] == "span" and e[2] == "engine"][seen:]
    assert [n for n, _, _ in recs] == ["admit"] + ["decode"] * 5
    assert all(a["profiled"] for _, a, _ in recs)
    return ({n: [(a, d) for m, a, d in recs if m == n] for n in ("admit", "decode")},
            _cpu_tick())


@pytest.mark.parametrize("name", ["admit", "decode"])
def test_every_phase_has_its_cpu_beside_its_wall(profiled_request, name):
    """Under a profiler session `<phase>_cpu` is this thread's CPU inside the
    phase: never negative, never more than the wall by over a tick of the CPU
    clock (and a millisecond for the two clocks' reads), and `cpu` is the
    record's, the phases' sum."""
    records, tick = profiled_request
    for args, dur in records[name]:
        phases = [k[:-2] for k in args if k.endswith("_s") and k not in NOT_PHASES]
        assert phases
        for p in phases:
            assert 0.0 <= args[p + "_cpu"] <= args[p + "_s"] + tick + 1e-3, p
        assert sum(args[p + "_cpu"] for p in phases) == pytest.approx(args["cpu"], abs=1e-6)
        assert 0.0 <= args["cpu"] <= dur + tick + 1e-3
    # a turn's CPU is read where both of its records read theirs
    turns = [a for a, _ in records["decode"]]
    assert all(0.0 <= a["turn_cpu"] <= a["turn"] + tick + 1e-3 for a in turns)


@pytest.mark.parametrize("name", ["admit", "decode"])
def test_no_cpu_is_read_outside_a_profiler_session(one_request, name):
    """`time.thread_time()` is a system call (5.7 us on the benchmark's
    machine): with tracing off a record costs none, and says so by holding
    no `_cpu` key of its own rather than a zero (the streams' `st_*_cpu` sums
    are there and 0.0: `test_stream_stages_cost_cpu_only_inside_...`)."""
    for args, _ in _records(name):
        assert args["profiled"] is False
        assert not [k for k in args if k == "cpu"
                    or k.endswith("_cpu") and not k.startswith("st_")]


def _spans(name=None):
    """(t0, dur_s, args) of the ring's engine records, oldest first."""
    return [(e[5], e[6], e[7]) for e in timeline.local_events()
            if e[0] == "span" and e[2] == "engine" and name in (None, e[3])]


def test_records_and_turns_tile_a_busy_stretch(one_request):
    """From the admission's opening to the last step's close the loop never
    rested, so the records and the turns between them ARE that time: every
    record but the first notes the turn that ended at its opening."""
    spans = _spans()
    assert len(spans) == 6 and "turn" not in spans[0][2]
    assert all(a["turn"] >= 0.0 for _, _, a in spans[1:])
    span = spans[-1][0] + spans[-1][1] - spans[0][0]
    tiled = sum(dur for _, dur, _ in spans) + sum(a["turn"] for _, _, a in spans[1:])
    assert tiled == pytest.approx(span, rel=0.01)
    for (t0, dur, _), (t1, _, nxt) in zip(spans, spans[1:]):
        assert t0 + dur + nxt["turn"] == pytest.approx(t1, abs=1e-5)


def test_no_turn_after_an_idle_pass(shared_params):
    """A pass that found nothing to do ends the turn unrecorded: the first
    record after the loop slept has no `turn`, whatever follows it has."""
    timeline.clear()
    eng = _engine(shared_params)
    try:
        eng.generate_sync(list(range(1, 21)), 3)
        time.sleep(0.1)   # dozens of idle passes, 2 ms each
        eng.generate_sync(list(range(30, 50)), 3)
    finally:
        eng.shutdown()
    recs = [(e[3], e[7]) for e in timeline.local_events() if e[2] == "engine"]
    assert [n for n, _ in recs] == ["admit", "decode", "decode"] * 2
    assert ["turn" in a for _, a in recs] == [False, True, True] * 2


def _off_cpu(args):
    return (args.get("turn", 0.0) - args.get("turn_cpu", 0.0)
            + args["dispatch_s"] - args["dispatch_cpu"])


def test_a_spinning_thread_shows_as_time_off_the_cpu(shared_params, tmp_path):
    """The measurement sees contention: with a pure-Python thread spinning
    beside it the engine thread waits for the interpreter lock between and
    inside its records, and that is wall without CPU in `turn` and
    `dispatch` (neither blocks by design). Under a profiler session, where
    the CPU is read; without its Python hooks, which would trace the spin."""
    import jax
    from jax.profiler import ProfileOptions

    options = ProfileOptions()
    options.python_tracer_level = 0
    timeline.clear()
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            sum(i * i for i in range(2000))

    spinner = threading.Thread(target=spin, daemon=True)
    eng = _engine(shared_params)
    try:
        eng.generate_sync(list(range(1, 21)), 22)    # compiles: not measured
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            timeline.clear()
            eng.generate_sync(list(range(60, 80)), 22)
            quiet = [a for a, _ in _records("decode")][-20:]
            timeline.clear()
            spinner.start()
            eng.generate_sync(list(range(30, 50)), 22)
            stop.set()
            eng.shutdown()
    finally:
        stop.set()
        eng.shutdown()
    spinner.join(timeout=10)
    assert not spinner.is_alive()
    loud = [a for a, _ in _records("decode")][-20:]
    assert len(quiet) == len(loud) == 20
    assert all(a["profiled"] for a in quiet + loud)
    assert not any(a["compile_s"] for a in quiet + loud)
    # on this backend a quiet `dispatch` blocks a little by itself (the CPU
    # "device" runs on other threads); the spinner adds a lock wait of up to
    # the switch interval, 5 ms, at every call that let the lock go: 20
    # passes of it stand well clear of 5 ms and of the CPU clock's grain
    assert sum(map(_off_cpu, loud)) > sum(map(_off_cpu, quiet)) + max(0.005, 3 * _cpu_tick())


def test_stream_counts_ride_the_decode_records(shared_params):
    """A stream of N tokens straight off the engine: the `decode` records'
    `st_taken` sum to N once a later record has noted the stream's tail,
    nothing is left on its queue, the admission carries the request's id,
    and with no front end and no profiler session the other sums stay 0."""
    timeline.clear()
    eng = _engine(shared_params)
    try:
        toks = list(eng.generate_stream(list(range(1, 21)), 7, rid="req-0042"))
        backlog = eng.stats()["stream_backlog"]
        eng.generate_sync(list(range(30, 50)), 2)   # its records note the tail
    finally:
        eng.shutdown()
    assert len(toks) == 7 and backlog == 0
    assert [a.get("rid") for a, _ in _records("admit")] == ["req-0042", None]
    steps = [a for a, _ in _records("decode")]
    assert sum(s["st_taken"] for s in steps) == 7
    assert sum(s["st_wake"] for s in steps) > 0
    assert steps[-1]["st_backlog"] == 0 and all(s["st_backlog"] >= 0 for s in steps)
    for name in ("st_detok_cpu", "st_relay_cpu", "st_fetch_cpu", "st_write_cpu"):
        assert all(s[name] == 0 for s in steps), name
    assert eng._streams == []   # the ended cell was folded away


class _IdTokenizer:
    """Text is the ids in decimal, one token a number: every token is a delta."""

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return "".join(f"{i} " for i in ids)


def _sse(url, body):
    """The `data:` frames of one streamed completion, `[DONE]` left out."""
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    frames = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: ") and line != "data: [DONE]":
                frames.append(json.loads(line[len("data: "):]))
    return frames


def _post(url, body):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def served_streams(shared_params, tmp_path_factory):
    """Through the whole path (proxy, router, replica, `_stream_deltas`, the
    engine, all in this process): one streamed completion of 6 tokens outside
    a profiler session and one inside, each followed by a plain completion
    whose `decode` records note the stream's tail. -> per stream the frames
    the client got and the `decode` records written meanwhile; the requests'
    ids as the proxy made them; the engine's `stream_backlog` at the end."""
    import jax

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import anatomy

    cfg, _ = shared_params
    timeline.clear()
    anatomy.clear()
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    out = {}
    try:
        app = serve.build_openai_app(
            PagedLLMConfig(model_config=cfg, max_batch_size=4, max_seq_len=128,
                           block_size=16), tokenizer=_IdTokenizer())
        handle = serve.run(app, route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        url = f"http://127.0.0.1:{proxy.port}/v1/completions"
        prompt = " ".join(map(str, range(1, 21)))

        def one(key):
            seen = len(_records("decode"))
            frames = _sse(url, {"prompt": prompt, "max_tokens": 6, "stream": True})
            _post(url, {"prompt": prompt, "max_tokens": 3})
            out[key] = (frames, [a for a, _ in _records("decode")][seen:])

        one("outside")
        with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
            one("inside")
        out["backlog"] = ray_tpu.get(handle.stats.remote())["stream_backlog"]
        out["rids"] = [e[2] for e in anatomy.local_events()
                       if e[0] == "sp" and e[3] == "ingress_admit"]
        out["admits"] = [a for a, _ in _records("admit")]
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return out


@pytest.mark.parametrize("session", ["outside", "inside"])
def test_served_stream_counts_its_tokens(served_streams, session):
    """Six tokens taken off the queue for the seven frames the client got (six
    deltas and the chunk that says `stop`), nothing left on the queue; the
    frames themselves are counted where they always were, on the request's
    ledger (`anatomy.complete(ntokens=)`)."""
    frames, steps = served_streams[session]
    assert len(frames) == 7
    assert sum(s["st_taken"] for s in steps) == 6
    assert steps[-1]["st_backlog"] == 0 and served_streams["backlog"] == 0


def test_stream_stages_cost_cpu_only_inside_a_profiler_session(served_streams):
    """Beside `test_profiled_only_inside_a_profiler_session`: the stages'
    `thread_time()` pairs are taken under a session alone, since only
    profiled records are read; counts and `st_wake` are kept always."""
    stages = ("st_detok_cpu", "st_relay_cpu", "st_fetch_cpu", "st_write_cpu")
    _, outside = served_streams["outside"]
    _, inside = served_streams["inside"]
    assert not any(s["profiled"] for s in outside)
    assert any(s["profiled"] for s in inside)
    fine = _cpu_tick() < 5e-6   # else six tokens' stages may fall between ticks
    for name in stages:
        assert sum(s[name] for s in outside) == 0.0, name
        assert sum(s[name] for s in inside) >= 0.0, name
        assert not fine or sum(s[name] for s in inside) > 0.0, name
    assert sum(s["st_wake"] for s in outside) > 0.0


def test_admit_record_carries_the_request_s_id(served_streams):
    """The id the proxy gave the request (`serve/anatomy.py`) is on the
    engine's `admit` record of a streamed request, so one request's spans
    share an identifier; a plain completion's admission has none."""
    rids, admits = served_streams["rids"], served_streams["admits"]
    assert len(rids) == 4 and len(admits) == 4
    assert [a.get("rid") for a in admits] == [rids[0], None, rids[2], None]


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
            "engine:decode.sample", "engine:decode.finish",
            "engine:turn"} <= set(spans)
    # a turn lies between two records, inside neither
    for line, s, e in spans["engine:turn"]:
        assert not any(ln == line and s0 < e and s < e0
                       for ln, s0, e0 in spans["engine:decode"] + spans["engine:admit"])
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
