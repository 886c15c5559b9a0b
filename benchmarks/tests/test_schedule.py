"""Traffic is a fixed multiset that the seed permutes and never resamples."""

import json
import os

from conftest import ROOT

from benchmarks.harness import schedule


def _traffic(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


def _lengths(requests):
    return sorted((r["prompt_len"], r["max_tokens"]) for r in requests)


def test_two_seeds_send_the_same_multiset_in_another_order():
    t = _traffic("chat-paced")
    a = schedule.open_loop_plan(t, 7, 50, 32)
    b = schedule.open_loop_plan(t, 2 ** 31 + 123, 50, 32)
    assert len(a["window"]) == len(b["window"]) == int(t["rate_rps"] * 50)
    assert _lengths(a["window"]) == _lengths(b["window"])
    assert _lengths(a["fill"]) == _lengths(b["fill"])
    order = lambda p: [(r["prompt_len"], r["max_tokens"]) for r in p["window"]]
    assert order(a) != order(b)
    assert schedule.open_loop_plan(t, 7, 50, 32) == a       # same seed, same plan
    # the seed orders requests inside each cycle of 16: every cycle of either
    # seed holds every prompt stratum once
    prompts = schedule.strata(t["prompt"])
    for plan in (a, b):
        for c in range(0, len(plan["window"]) - 15, 16):
            assert sorted(r["prompt_len"] for r in plan["window"][c:c + 16]) == prompts


def test_arrivals_are_evenly_spaced_with_bounded_jitter():
    t = _traffic("chat-paced")
    plan = schedule.open_loop_plan(t, 3, 50, 32)
    rate, jit = t["rate_rps"], t["jitter"]
    for k, r in enumerate(plan["window"]):
        assert abs(r["due_s"] * rate - (k + 0.5)) <= jit + 1e-9
        assert 0 <= r["due_s"] < 50


def test_strata_are_the_published_shape_cut_to_the_context():
    t = _traffic("chat-paced")
    prompts, outputs = schedule.strata(t["prompt"]), schedule.strata(t["output"])
    assert len(prompts) == len(outputs) == 16
    assert prompts == sorted(prompts) and 32 <= prompts[0] and prompts[-1] <= 1536
    assert prompts[7] < 320 < prompts[8] and outputs[7] < 128 < outputs[8]  # medians
    assert max(prompts) + max(outputs) <= 2048
    # the fill carries the generated part in the prompt: still inside the context
    plan = schedule.open_loop_plan(t, 1, 50, 32)
    assert all(r["prompt_len"] + r["max_tokens"] <= 2048 for r in plan["fill"])
    assert 0 < len(plan["fill"]) <= 32


def test_pair_design_meets_every_pair_once_in_n_out_cycles():
    pairs = schedule.pair_design(256, 16, 16)
    assert len(set(pairs)) == 256
    first = pairs[:16]
    assert sorted(i for i, _ in first) == list(range(16))
    assert sorted(j for _, j in first) == list(range(16))
    # a cut-off cycle holds lengths from the whole range
    assert {i for i, _ in pairs[:4]} == {0, 8, 4, 12}


def test_closed_loop_cycles_over_the_strata_and_staggers_the_first_requests():
    t = _traffic("docs-batch")
    a = schedule.closed_loop_plan(t, 5, 50, 32)
    b = schedule.closed_loop_plan(t, 6, 50, 32)
    assert a["clients"] == len(a["fill"]) == 40 > 32     # more than the slots
    assert sorted(r["max_tokens"] for r in a["fill"]) == sorted(
        1 + (k * 16) // 40 for k in range(40))
    work = lambda p: [r["prompt_len"] for r in p["fill"] + p["sequence"]]
    for c in range(0, 160, 16):  # every cycle of either seed holds every stratum
        assert sorted(work(a)[c:c + 16]) == sorted(work(b)[c:c + 16]) \
            == schedule.strata(t["prompt"])
    assert work(a)[:16] != work(b)[:16]
    assert all(r["max_tokens"] == 16 for r in a["sequence"])
