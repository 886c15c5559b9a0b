"""The Kimi-K2 family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its configuration against
the published shape and the floors of a chip's share, its cell's traffic, its
five per-layer metrics read from a toy engine's own records and from a made
trace, and what it says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import kimi_k2
from benchmarks.harness.measure import Measurement

CELL = "serve-kimi-longin-batch"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("latent_attn_roofline.batch", "moe_route_share.batch", "moe_experts_share.batch",
       "decode_wait_ms.batch", "moe_rows_p50.batch")


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/kimi-k2.7-code-serve-ep32-1chip.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in kimi_k2.MODEL_KEYS}
    return cfg


def test_parameters_cache_and_pool_by_hand(config):
    m = config["model"]
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 64 * 512 * (128 + 128)
            + 64 * 128 * 7168)
    assert kimi_k2.attention_params(m) == attn == 101_122_048          # 101.12 M
    assert kimi_k2.expert_params(m) == 3 * 7168 * 2048 == 44_040_192   # 44.04 M
    here = kimi_k2.params_here(m)
    assert here["dense_layers"] == attn + 3 * 7168 * 18432 == 497_483_776
    outside = attn + 7168 * 384 + 44_040_192                           # router, shared
    assert outside == 147_914_752 and here["expert_layers_outside_experts"] == 7 * outside
    assert here["experts_held"] == 7 * 12 * 44_040_192                 # 528.48 M a layer
    assert here["embedding_and_head"] == 2 * 20480 * 7168 == 293_601_280
    total = sum(here.values())
    assert 11.04e9 < 2 * total < 11.06e9                               # 11.05 GB in bf16
    # a whole expert layer would be 34.1 GB: 384 experts and what lies outside them
    assert 34.0e9 < 2 * (outside + 384 * 44_040_192) < 34.2e9
    # the latent cache: 576 values a token and layer in 640 lanes
    assert kimi_k2.cache_layers(m) == 8
    token = 8 * 640 * 2
    assert token == 10_240 and 8 * 576 * 2 == 9_216
    # per-head keys (192 wide) and values (128) of the same model: 32 times the row
    assert 8 * 64 * (192 + 128) * 2 == 327_680 == 32 * token
    assert kimi_k2.kv_pool_blocks(config) == 8192
    pool = 8193 * 16 * token
    assert pool == 8 * 8193 * 16 * 640 * 2 == 1_342_341_120            # 1.34 GB
    # 64 slots x 127 blocks (a 1,892-token prompt and 128 out, the longest) fit: slots bind
    assert -(-(1892 + 128) // 16) == 127 and 64 * 127 <= 8192
    # weights and pool: 12.4 of the chip's 16 GB, far over the 25% floor
    assert 12.3e9 < 2 * total + pool < 12.5e9


def test_latent_attention_step_by_hand(config):
    m = config["model"]
    work = kimi_k2.latent_attention_step(m, 100_000, 64)
    rows = 100_000 * 576 * 2                      # 1,152 B a live token and cache layer
    qo = 64 * 64 * (576 + 512) * 2
    assert work["bytes"] == 8 * (rows + qo)
    assert work["flops"] == 8 * 2 * 64 * (576 + 512) * 100_000
    least, bound = shapes.least_seconds(work, V5E)
    # 112 FLOPs a byte against a balance of 240: memory bounds it, 1.2 ms a step
    assert bound == "memory" and least == pytest.approx(1.21e-3, rel=0.02)
    assert work["flops"] / work["bytes"] == pytest.approx(112, abs=2)


def test_the_configuration_is_the_published_shape_cut_to_a_chip_s_share(config):
    with open(f"{ROOT}/benchmarks/configs/published/Kimi-K2.7-Code.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.7-Code")
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"]
    changed = {k for k, v in pub["config"].items() if config.get(k, "missing") != v}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size",
                       "max_position_embeddings"} == set(config["reduced"])
    assert config["published"] == {k: pub["config"][k] for k in changed}
    assert not changed & set(pub["widths"])
    # every width as published
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "q_lora_rank", "kv_lora_rank", "moe_intermediate_size",
        "num_experts_per_tok", "intermediate_size")] == [
            7168, 64, 128, 64, 128, 1536, 512, 2048, 8, 18432]
    share = config["share"]
    assert share["router_outputs"] == 384 and share["chips"] == 32
    assert share["chips"] * config["n_routed_experts"] == 384
    assert share["vocab_chips"] * config["vocab_size"] == 163840
    # the floors of a chip's share: a period and 4 more layers, 8 experts, 1/8
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["num_hidden_layers"] in (8, 7, 6) and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= 163840
    assert "32" in config["deployment"] and "exchange" in config["deployment"]
    assert {"torch_dtype", "vision_tower", "rope_lanes", "correction_bias",
            "weights"} <= set(config["assumed"])
    eng = config["engine"]
    assert (eng["max_batch_size"], eng["block_size"], eng["num_blocks"]) == (64, 16, 8193)
    assert eng["prefill_buckets"] == [128, 2048]
    chk = config["check"]
    assert {"measured", "would_fail", "reason"} <= set(chk)
    for wrong in ("softmax", "m^2", "shared expert", "8-bit"):
        assert wrong in chk["would_fail"], wrong
    with open(f"{ROOT}/benchmarks/traffic/long-in-128-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 80
    assert traffic["output"]["min"] == traffic["output"]["max"] == 128
    with open(f"{ROOT}/benchmarks/traffic/docs-batch.json") as f:
        assert traffic["prompt"] == json.load(f)["prompt"]    # serve-docs-batch's strata
    from benchmarks.harness.schedule import strata
    lens = strata(traffic["prompt"])
    assert (min(lens), max(lens), len(lens)) == (1052, 1892, 16)
    assert traffic["trace"] == {"start_s": 5.0, "seconds": 4.0}


def test_the_program_s_configuration_and_what_it_refuses(config):
    m = config["model"]
    cfg = kimi_k2.model_config(m)
    assert (cfg.first_k_dense, cfg.base.num_layers, cfg.cache_layers) == (1, 7, 8)
    assert cfg.experts.num_experts == 384 and cfg.experts.experts_held == (0, 12)
    assert cfg.experts.score_func == "sigmoid" and cfg.experts.routed_scaling == 2.827
    assert cfg.latent_row == 640 and cfg.vocab_size == 20480
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    for key, value in (("n_group", 8), ("scoring_func", "softmax"),
                       ("topk_method", "greedy"), ("attention_bias", True)):
        with pytest.raises(SystemExit, match=key):
            kimi_k2.model_config({**m, key: value})
    whole = kimi_k2.model_config({**m, "n_routed_experts": 384})
    assert whole.experts.experts_held is None


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 33 has no `ray_tpu/models/kimi_k2.py`: the new cell
    must end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "kimi_k2", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.kimi_k2", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.kimi_k2"):
        kimi_k2.model_config(config["model"])
    assert not hasattr(kimi_k2, "train_state_and_step")   # it serves only


def test_the_cell_s_metrics_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU, requests inside `jax.profiler.trace`. The counter and the two record
    metrics are read; the three of the device trace find none and do not raise."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m = cell.config["model"]
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=kimi_k2.model_config(m), max_batch_size=4, max_seq_len=128,
        block_size=16, num_blocks=25, prefill_buckets=(32, 64)))
    try:
        eng.generate_sync(list(range(1, 11)), 3)
        with jax.profiler.trace(str(tmp_path)):
            futs = [eng.generate(list(range(1, n + 1)), new)
                    for n, new in ((40, 12), (20, 8), (50, 10))]
            assert [f.result(120).num_generated for f in futs] == [12, 8, 10]
            eng.shutdown()
    finally:
        eng.shutdown()
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    new = [x for x in cell.per_layer if x["name"] in NEW]
    assert len(new) == 5 and all(x["moves"] == "served_tok_s" for x in new)
    values, missing = spec.read_metrics(new, ctx)
    assert sorted(missing) == ["latent_attn_roofline.batch", "moe_experts_share.batch",
                               "moe_route_share.batch"]
    assert values["decode_wait_ms.batch"]["value"] > 0
    # 2 expert layers, 4 choices of 16 experts of which 8 are held: up to
    # live x 4 x 2 pairs a step, about half of them
    assert 0 < values["moe_rows_p50.batch"]["value"] <= 3 * 4 * 2
    # a program whose records lack the counter (any before PR 33) leaves it out
    events = [e[:7] + [{k: v for k, v in e[7].items() if k != "moe_rows"}]
              if e[0] == "span" and isinstance(e[7], dict) else e
              for e in map(list, timeline.local_events())]
    import unittest.mock as mock
    with mock.patch.object(timeline, "local_events", lambda: events):
        _, missing = spec.read_metrics(new, Measurement(
            config=cell.config, traffic=cell.traffic, family=cell.family, peaks=ctx.peaks))
    assert "moe_rows_p50.batch" in missing


def test_the_kernel_s_roofline_and_the_scope_shares_from_a_made_trace(config):
    """`latent_attn_roofline.batch` by hand: least seconds of the family's
    `latent_attention_step` at the traced steps' mean context and slots, times
    the steps, over the device time of the kernel's own operations; the two
    shares over busy time, an operation under both `moe/experts` and a loop
    counted once."""
    from benchmarks.harness.xplane import TraceSummary

    cell = spec.Cell(CELL)
    metrics = [x for x in cell.per_layer if x["name"] in NEW[:3]]
    scopes = {"jit(decode)/jit(main)/while/body/moe/experts/grouped_matmul_fwd": 0.6,
              "jit(decode)/jit(main)/while/body/moe/shared/dot_general": 0.2,
              "jit(decode)/jit(main)/while/body/moe/route/sort": 0.1,
              "jit(decode)/jit(main)/while/body/moe/combine/reduce": 0.1,
              "jit(decode)/jit(main)/while/body/attn/latent_read/latent_attention_decode": 0.4}
    trace = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"latent_attention_decode.3 custom-call": 0.4, "other": 1.6},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 50.0, "traced_context_tokens": 100_000.0,
                "traced_live_slots": 64.0}
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metrics, ctx)
    assert not missing
    least = kimi_k2.latent_attention_step(config["model"], 100_000.0, 64.0)["bytes"] / 819e9
    assert values["latent_attn_roofline.batch"]["value"] == pytest.approx(100 * 50 * least / 0.4)
    assert ctx.notes["latent_attention_step_bound"] == "memory"
    assert values["moe_experts_share.batch"]["value"] == pytest.approx(40.0)
    assert values["moe_route_share.batch"]["value"] == pytest.approx(10.0)
