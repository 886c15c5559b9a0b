"""The Ouro family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its configuration against
the published shape, its cell through the whole command on the CPU stand-in,
its four per-layer metrics read from a toy engine's own records, and what it
says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import ouro
from benchmarks.harness.measure import Measurement

CELL = "serve-ouro-shortin-batch"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/ouro-2.6b-serve-1chip.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in ouro.MODEL_KEYS}
    return cfg


def test_parameters_cache_and_pool_by_hand(config):
    m = config["model"]
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert ouro.params_per_layer(m) == layer == 51_388_416
    # the layers ONCE, embedding and untied head, the final norm: 2,668.0 M, 5.34 GB
    params = 48 * layer + 2 * 49152 * 2048 + 2048
    assert params == 2_667_972_608
    assert ouro.cache_layers(m) == 192
    token = ouro.cache_layers(m) * 2 * 16 * 128 * 2             # a token's K and V
    assert token == 1_572_864                                   # 1.5 MiB
    assert ouro.kv_pool_blocks(config) == 320
    pool = 321 * 16 * token // 2                                # keys, or values
    assert pool == 192 * 321 * 16 * 2048 * 2 == 4_039_114_752
    # the engine's dense-parity default would be 32 slots x 128 blocks: 103 GB
    parity = ouro.kv_pool_blocks({**config, "engine": {**config["engine"], "num_blocks": 0}})
    assert parity == 4096 and parity * 16 * token > 100e9
    # weights and pool: 13.4 of the chip's 16 GB, far over the 25% floor
    assert 13.3e9 < 2 * params + 2 * pool < 13.5e9


def test_decode_stream_and_paged_attention_steps_by_hand(config):
    m = config["model"]
    work = ouro.decode_stream_step(m, 3500, 18)
    weights = (4 * 48 * 51_388_416 + 2048 * 49152 + 2048) * 2   # 19.7 GB + the head
    kv = 3500 * 1_572_864                                       # 5.5 GB
    qo = 192 * 2 * 18 * 16 * 128 * 2
    assert work["bytes"] == weights + kv + qo
    least, bound = shapes.least_seconds(work, V5E)
    assert bound == "memory" and least == pytest.approx(0.0310, abs=3e-4)   # 31 ms a step
    assert weights / 819e9 == pytest.approx(0.0243, abs=2e-4)
    # compute would bound it only from ~120 slots: 2 x batch FLOPs a weight byte pair
    assert shapes.least_seconds(ouro.decode_stream_step(m, 3500, 32), V5E)[1] == "memory"
    # the kernel's own: every pass reads its own K and V of the live context
    attn = ouro.paged_attention_step(m, 3500, 18)
    one = shapes.paged_attention_step({**m, "num_hidden_layers": 1}, 3500, 18)
    assert attn == {k: 192 * v for k, v in one.items()}
    assert attn["bytes"] == kv + qo


def test_the_configuration_is_the_published_shape_cut_in_context_alone(config):
    with open(f"{ROOT}/benchmarks/configs/published/Ouro-2.6B.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"]
    assert {k for k, v in pub["config"].items() if config.get(k, "missing") != v} == \
        {"max_position_embeddings"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["total_ut_steps"]) == (48, 4)
    assert {"sandwich_norms", "norm_between_passes", "cache_per_pass_and_layer",
            "exit_gate", "weights"} <= set(config["assumed"])
    eng = config["engine"]
    assert eng["num_blocks"] in (321, 289, 257) and eng["max_batch_size"] == 32
    assert eng["prefill_buckets"] == [128, 256]
    with open(f"{ROOT}/benchmarks/traffic/short-in-128-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 40
    assert traffic["prompt"] == {"dist": "uniform", "min": 32, "max": 224, "strata": 16}
    assert traffic["output"]["min"] == traffic["output"]["max"] == 128
    # a request reserves 11-22 blocks, 16.5 on average: ~19 fill the 320
    from benchmarks.harness.schedule import strata
    need = [-(-(p + 128) // 16) for p in strata(traffic["prompt"])]
    assert (min(need), max(need)) == (11, 22) and sum(need) / 16 == 16.5


def test_a_threshold_under_one_and_a_window_are_refused_by_name(config):
    m = config["model"]
    assert ouro.model_config(m).loop_steps == 4
    with pytest.raises(SystemExit, match="early_exit_threshold"):
        ouro.model_config({**m, "early_exit_threshold": 0.9})
    with pytest.raises(SystemExit, match="sliding window"):
        ouro.model_config({**m, "sliding_window": 4096})


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 31 has no `ray_tpu/models/ouro.py`: the new cell must
    end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "ouro", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.ouro", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.ouro"):
        ouro.model_config(config["model"])
    assert not hasattr(ouro, "train_state_and_step")   # it serves only


def test_the_four_metrics_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU with a pool smaller than its slots, requests inside `jax.profiler.
    trace`; blocks bind, so `requeued` is recorded and the pool reads full."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m, eng_cfg = cell.config["model"], cell.config["engine"]
    eng_cfg["num_blocks"] = 7       # 6 usable: one request of 40 + 24 takes 4
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=ouro.model_config(m), max_batch_size=4, max_seq_len=128,
        block_size=16, num_blocks=7, prefill_buckets=(32, 64)))
    try:
        eng.generate_sync(list(range(1, 11)), 3)
        with jax.profiler.trace(str(tmp_path)):
            futs = [eng.generate(list(range(1, n + 1)), new)
                    for n, new in ((40, 24), (20, 12), (50, 14))]
            assert [f.result(120).num_generated for f in futs] == [24, 12, 14]
            eng.shutdown()
    finally:
        eng.shutdown()
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    new = [x for x in cell.per_layer if x["name"] in (
        "decode_stream_roofline.batch", "kv_pool_used_share.batch",
        "admit_requeued_share.batch", "paged_attn_roofline.batch")]
    assert len(new) == 4 and all(x["moves"] == "served_tok_s" for x in new)
    values, missing = spec.read_metrics(new, ctx)
    # without a device trace the two rooflines' readers find nothing and do not raise
    assert missing == ["decode_stream_roofline.batch", "paged_attn_roofline.batch"]
    assert 60 < values["kv_pool_used_share.batch"]["value"] <= 100   # 4-6 of 6 blocks
    assert 0 < values["admit_requeued_share.batch"]["value"] < 100
    assert ctx.notes["engine_phases"]["decode"]["n"] >= 24
    # a program whose records lack `blocks` (any before PR 31) leaves them out
    events = [e[:7] + [{k: v for k, v in e[7].items() if k not in ("blocks", "outcome")}]
              if e[0] == "span" and isinstance(e[7], dict) else e
              for e in map(list, timeline.local_events())]
    import unittest.mock as mock
    with mock.patch.object(timeline, "local_events", lambda: events):
        values, missing = spec.read_metrics(new, Measurement(
            config=cell.config, traffic=cell.traffic, family=cell.family, peaks=ctx.peaks))
    assert "kv_pool_used_share.batch" in missing
    assert values.get("admit_requeued_share.batch", {"value": 0})["value"] == 0


def test_the_decode_program_s_roofline_is_read_from_the_device_trace(config):
    """`decode_stream_roofline.batch` by hand on a made trace: least seconds of
    the family's `decode_stream_step` at the traced steps' mean context and
    slots, times the steps, over the device's self time in every operation but
    those traced under another program's name: a prefill is not in the
    denominator, operations without an `op_name` are (the share must not read
    high), and a trace without `op_name`s leaves the metric out."""
    from benchmarks.harness.xplane import TraceSummary

    cell = spec.Cell(CELL)
    metric = [x for x in cell.per_layer if x["name"] == "decode_stream_roofline.batch"]
    assert metric[0]["source"] == "device_trace" and metric[0]["layer"] == "model"
    scopes = {"jit(decode)/jit(main)/while/body/loop/while/body/attn/dot_general": 1.2,
              "jit(decode)/jit(main)/dot_general": 0.3,
              "jit(prefill_256)/jit(main)/while/body/loop/while/body/mlp/dot_general": 0.5}
    trace = TraceSummary(window_s=4.0, busy_s=2.1, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"a": 1.5, "b": 0.5, "unnamed": 0.1},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 30.0, "traced_context_tokens": 3500.0,
                "traced_live_slots": 18.0}
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metric, ctx)
    least = ouro.decode_stream_step(config["model"], 3500.0, 18.0)["bytes"] / 819e9
    assert values["decode_stream_roofline.batch"]["value"] == pytest.approx(
        100 * 30 * least / 1.6)
    assert ctx.notes["decode_stream_step_bound"] == "memory"
    assert ctx.notes["trace_unscoped_s"] == pytest.approx(0.1)
    trace.scope_self_s = {}
    assert spec.read_metrics(metric, ctx)[1] == ["decode_stream_roofline.batch"]
