"""The serving cells' reduction of the engine's own records, on a hand-made
ring; the check's choice of the sampled row; and a serving cell at the tiny
size over an engine whose loop methods carry other names."""

import inspect
import textwrap

import numpy as np
import pytest

from benchmarks.harness import engine_records
from benchmarks.harness.serve_cell import sampled_row

ANCHOR = 1000.0   # the hand-made ring's wall clock is monotonic + 1000


def _span(seq, name, t0, dur, cat="engine", **args):
    return ["span", seq, cat, name, 1, t0 + ANCHOR, dur, args]


@pytest.fixture
def ring(monkeypatch):
    """An admitted, a requeued and a rejected admission; decode steps before
    the window, inside it (profiled and not) and after it; other entries."""
    from ray_tpu.util import timeline

    events = [
        _span(1, "decode", 1.0, 0.2, live=1, ctx=10, profiled=False),   # before the mark
        _span(2, "decode", 9.9, 0.2, live=9, ctx=900, profiled=False),  # begins before the window
        _span(3, "admit", 10.5, 0.100, outcome="admitted", queue_wait_s=0.030,
              prompt=64, profiled=False),
        _span(4, "admit", 10.7, 0.001, outcome="requeued", queue_wait_s=0.5,
              prompt=80, profiled=False),
        _span(5, "admit", 10.8, 0.002, outcome="rejected", queue_wait_s=0.1,
              prompt=9000, profiled=False),
        _span(6, "decode", 11.0, 0.2, live=2, ctx=100, profiled=False),
        _span(7, "decode", 11.9, 0.2, live=4, ctx=300, profiled=True),  # the trace's edge cuts it
        _span(8, "decode", 12.1, 0.2, live=4, ctx=304, profiled=True),
        _span(9, "admit", 12.3, 0.300, outcome="admitted", queue_wait_s=0.010,
              prompt=80, profiled=True),
        _span(10, "decode", 12.6, 0.4, live=5, ctx=389, profiled=True),
        _span(11, "pull", 12.0, 1.0, cat="plane"),
        ["phase", 12, None, 1, 0.0, 0.0, 0.0, 0.0, 0.0, "ok"],
        _span(13, "ops", 13.0, 0.05, kind="attach", profiled=False),
        _span(14, "decode", 21.0, 0.2, live=1, ctx=5, profiled=False),  # after the window
    ]
    monkeypatch.setattr(timeline, "local_events", lambda: list(events))
    return events


CLIENT = [{"id": 1, "prompt_len": 64, "sent": 10.4680}, {"id": 2, "prompt_len": 80, "sent": 10.1},
          {"id": 3, "prompt_len": 80, "sent": 12.2875}, {"id": 4, "prompt_len": 80, "sent": 12.4},
          {"id": 5, "prompt_len": 99, "sent": None}]


def test_records_since_the_mark_on_the_monotonic_clock(ring):
    records = engine_records.since({"seq": 1, "anchor": ANCHOR})
    assert [r[0] for r in records] == ["decode", "admit", "admit", "admit", "decode",
                                       "decode", "decode", "admit", "decode", "ops",
                                       "decode"]
    assert records[0][1] == pytest.approx(9.9) and records[0][2] == 0.2
    assert records[1][3]["outcome"] == "admitted"


def test_window_series_take_admitted_admissions_and_every_decode_step(ring):
    records = engine_records.since({"seq": 1, "anchor": ANCHOR})
    series, counters = engine_records.reduce(records, 10.0, 20.0, CLIENT)
    assert series["prefill_s"] == pytest.approx([0.100, 0.300])
    assert series["queue_wait_s"] == pytest.approx([0.030, 0.010])
    assert series["decode_step_s"] == pytest.approx([0.2, 0.2, 0.2, 0.4])
    # 10.5 - 0.030 - 10.4680; 12.3 - 0.010 - 12.2875: of the two requests of
    # 80 tokens sent before the stamp, the later one
    assert series["host_path_s"] == pytest.approx([0.002, 0.0025])
    assert counters == {}


def test_traced_steps_count_a_cut_step_for_its_part_inside(ring):
    records = engine_records.since({"seq": 1, "anchor": ANCHOR})
    _, counters = engine_records.reduce(records, 10.0, 20.0, CLIENT, traced=(12.0, 12.8))
    # half of the step at 11.9, the whole step at 12.1, half of the one at 12.6
    assert counters["traced_decode_steps"] == pytest.approx(2.0)
    assert counters["traced_context_tokens"] == pytest.approx(
        (0.5 * 300 + 304 + 0.5 * 389) / 2.0)
    assert counters["traced_live_slots"] == pytest.approx((0.5 * 4 + 4 + 0.5 * 5) / 2.0)
    # an interval that cuts no step counts whole steps, as a wrapper around the step did
    _, whole = engine_records.reduce(records, 10.0, 20.0, CLIENT, traced=(12.1, 12.35))
    assert whole["traced_decode_steps"] == pytest.approx(1.0)
    assert whole["traced_context_tokens"] == 304 and whole["traced_live_slots"] == 4


def test_a_ring_that_lost_the_window_s_first_records_fails_by_name(ring, monkeypatch):
    from ray_tpu.util import timeline

    monkeypatch.setattr(timeline, "MAX_EVENTS", len(ring) - 3)
    monkeypatch.setattr(timeline, "local_events", lambda: list(ring[3:]))
    with pytest.raises(SystemExit, match="MAX_EVENTS"):
        engine_records.since({"seq": 1, "anchor": ANCHOR})
    # full, but nothing since the mark is gone
    assert engine_records.since({"seq": 3, "anchor": ANCHOR})[0][0] == "admit"


def test_mark_reads_the_newest_number_and_an_anchor(ring):
    import time

    mark = engine_records.mark()
    assert mark["seq"] == 14
    assert mark["anchor"] == pytest.approx(time.time() - time.monotonic(), abs=0.01)


@pytest.mark.parametrize("shape", [(32, 7), (1, 7), (7,)])
def test_the_check_takes_the_row_the_engine_samples_from(shape):
    """`[bucket, vocab]` as today, or one row once the program cuts the last
    live position out before the head (ROADMAP S5)."""
    logits = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    row = sampled_row(logits, prompt_len=24)
    assert row.shape == (7,)
    assert row[0] == (23 * 7 if shape[0] == 32 else 0)


def _rename_in(cls, method: str, old: str, new: str):
    """`cls.method` compiled again from its own source with one name changed."""
    src = textwrap.dedent(inspect.getsource(getattr(cls, method))).replace(old, new)
    scope: dict = {}
    exec(compile(src, f"<{method} renamed>", "exec"), vars(inspect.getmodule(cls)), scope)
    return scope[method]


@pytest.mark.parametrize("one_row", [False, True], ids=["bucket_rows", "one_row"])
def test_the_check_passes_on_either_shape_of_a_prefill_s_logits(
        tiny_root, monkeypatch, one_row):
    """`check_against_reference` over a stub in the engine's place, which
    answers with the reference's own logits: as `[bucket, vocab]` with noise
    in every row but the sampled one (today), or as that row alone (once the
    program cuts it out before the head, ROADMAP S5). Both are `correct`;
    taking any other row would not be."""
    import types

    from benchmarks.harness import serve_cell, spec

    cell = spec.Cell("serve-chat-steady", root=tiny_root)
    m, bucket = cell.config["model"], 32
    params = cell.family.seeded_params(cell.family.model_config(m), 11)
    tap = types.SimpleNamespace(
        engine=types.SimpleNamespace(params=params), captured=[],
        capture_logits=lambda: None, unwrap=lambda: None)

    def post(url, body):
        prompt = [int(t) for t in body["prompt"].split()]
        new = []
        for step in range(body["max_tokens"]):
            logits = np.asarray(cell.reference.logits(params, prompt + new, m))
            if step == 0:
                rows = np.random.default_rng(0).normal(size=(bucket, logits.shape[1]))
                rows[len(prompt) - 1] = logits[-1]
                tap.captured.append(("prefill", np.asarray(prompt),
                                     logits[-1] if one_row else rows.astype(np.float32)))
            else:
                tap.captured.append(("decode", np.array([0]), logits[-1][None]))
            new.append(int(logits[-1].argmax()))
        return {"choices": [{"text": " ".join(map(str, new))}]}

    monkeypatch.setattr(serve_cell, "_post", post)
    check = serve_cell.check_against_reference(tap, "http://stub", cell, seed=11)
    assert check["ok"] and check["rel_rms"] < 1e-5
    if not one_row:
        monkeypatch.setattr(serve_cell, "sampled_row", lambda logits, n: logits[n - 2])
        assert not serve_cell.check_against_reference(tap, "http://stub", cell, 11)["ok"]


def test_a_serving_cell_stands_on_no_method_of_the_engine_s_loop(
        tiny_root, cpu_as_device, monkeypatch):
    """The program with `_admit_one` and `_step_decode` under other names (as
    ROADMAP S4 and S6 may leave them): the traced serving cell still checks
    `correct` and reads its spans, from the engine's own records."""
    import time

    from benchmarks.harness import device, spec
    from benchmarks.harness.peaks import peaks_for
    from ray_tpu.serve.llm_paged import PagedLLMEngine as cls

    monkeypatch.setattr(cls, "_step_admit",
                        _rename_in(cls, "_step_admit", "_admit_one", "_admit_request"))
    monkeypatch.setattr(cls, "_loop_step",
                        _rename_in(cls, "_loop_step", "_step_decode", "_decode_once"))
    monkeypatch.setattr(cls, "_admit_request", cls._admit_one, raising=False)
    monkeypatch.setattr(cls, "_decode_once", cls._step_decode, raising=False)
    monkeypatch.delattr(cls, "_admit_one")
    monkeypatch.delattr(cls, "_step_decode")
    cell = spec.Cell("serve-chat-steady", root=tiny_root)
    dev = device.describe()
    ms = cell.kind.run(cell, 7, 2.0, True, time.monotonic(), dev, peaks_for(dev["kind"]))
    assert ms.correct and ms.failed == 0
    for name in ("prefill_s", "decode_step_s", "queue_wait_s", "host_path_s"):
        assert ms.series[name], name
    assert ms.counters["traced_decode_steps"] > 0
    assert ms.counters["traced_live_slots"] >= 1
