"""The LFM2 family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its configuration against
the published shape, the catalog and the floors of a chip's share, its cell's
traffic, its two per-layer metrics from a made trace and from a toy engine's
own trace, and what it says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import lfm2
from benchmarks.harness.measure import Measurement

CELL = "serve-lfm2-docs4k-96-out"
CONFIG = "lfm2-8b-a1b-serve-ep2-1chip"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("conv_mix_share.batch", "conv_state_share.batch")


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/{CONFIG}.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in lfm2.MODEL_KEYS}
    return cfg


@pytest.mark.parametrize("part", ["parameters", "pool", "decode_stream_step"])
def test_parameters_cache_and_the_step_s_bytes_by_hand(config, part):
    m = config["model"]
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048          # in, taps, out
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512              # q, o; k, v
    expert, dense = 3 * 2048 * 1792, 3 * 2048 * 7168
    here = lfm2.params_here(m)
    if part == "parameters":
        assert lfm2.conv_params(m) == conv == 16_783_360                # 16.78 M
        assert lfm2.attention_params(m) == attn == 10_485_760           # 10.49 M
        assert lfm2.expert_params(m) == expert == 11_010_048            # 11.01 M
        assert (lfm2.layers_of(m, "conv"), lfm2.cache_layers(m)) == (18, 6)
        assert here == {"embedding_and_head": 65536 * 2048, "conv_mixers": 18 * conv,
                        "attention": 6 * attn, "dense_mlps": 2 * dense,
                        "routers": 22 * 2048 * 32, "experts_held": 22 * 16 * expert}
        total = sum(here.values())
        assert total == 4_464_291_840 and 8.92e9 < 2 * total < 8.94e9     # 8.93 GB in bf16
        # the WHOLE model: every expert; the published 8.3 B with the head tied
        whole = total + 22 * 16 * expert
        assert whole == 8_339_828_736 and 16.6e9 < 2 * whole < 16.7e9
        assert whole + 65536 * 2048 > 8.47e9                              # untied: not 8.3 B
    elif part == "pool":
        # a block: 16 tokens' K and V rows of 6 layers at 8 x 128 lanes, and
        # 2 state rows of 2,048 of 18 layers
        kv, state = 2 * 6 * 16 * 1024 * 2, 18 * 2 * 2048 * 2
        assert (kv, state, kv + state) == (393_216, 147_456, 540_672)
        assert lfm2.pool_row(m) == 1024 and lfm2.head_dim(m) == 64
        assert lfm2.kv_pool_blocks(config) == 8384 == 32 * 262
        assert -(-(4096 + 96) // 16) == 262
        pool = 8385 * (kv + state)
        assert pool == 4_533_534_720                                      # 4.53 GB
        assert 13.45e9 < 2 * sum(here.values()) + pool < 13.47e9          # of the chip's 16
        # a sequence of 3,000 tokens carries 188 blocks' state where a slot would carry 2 rows
        assert 188 * state == 27_721_728 and 18 * 2 * 2048 * 2 == 147_456
    else:
        # 32 rows touch 15.78 of the 16 held experts if the router spreads evenly
        touched = 16 * (1 - (28 / 32) ** 32)
        assert lfm2.experts_touched(m, 32) == pytest.approx(touched) == pytest.approx(15.78, abs=0.01)
        work = lfm2.decode_stream_step(m, 100_000, 32)
        fixed = 65536 * 2048 + 18 * conv + 6 * attn + 2 * dense + 22 * 2048 * 32
        attn_work = lfm2.paged_attention_step(m, 100_000, 32)
        # K and V at the pool's width (8 heads x 128 lanes), q and o at the heads' 64
        assert attn_work["bytes"] == 6 * (2 * 100_000 * 1024 * 2 + 2 * 32 * 32 * 64 * 2)
        assert attn_work["flops"] == 6 * 2 * 2 * 100_000 * 32 * 64
        state = lfm2.conv_state_step(m, 32)
        assert state["bytes"] == 18 * 3 * 32 * 2048 * 2 == 7_077_888
        assert work["bytes"] == pytest.approx(
            2 * (fixed + 22 * touched * expert) + attn_work["bytes"] + state["bytes"])
        # a row goes through 4 x 16 / 32 of an expert held here a layer
        assert work["flops"] == pytest.approx(
            2 * 32 * (fixed + 22 * 2 * expert) + attn_work["flops"] + state["flops"])
        least, bound = shapes.least_seconds(work, V5E)
        # 11.3 GB a step: 13.8 ms at the HBM's speed, and memory bounds it
        assert bound == "memory" and work["bytes"] == pytest.approx(11.33e9, rel=0.01)
        assert least == pytest.approx(13.8e-3, rel=0.02)


def test_the_configuration_is_the_published_shape_at_full_depth(config):
    with open(f"{ROOT}/benchmarks/configs/published/LFM2-8B-A1B.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"]
    changed = {k for k, v in pub["config"].items() if config.get(k, "missing") != v}
    assert changed == {"num_experts", "max_position_embeddings"} == set(config["reduced"])
    assert config["published"] == {k: pub["config"][k] for k in changed}
    assert not changed & set(pub["widths"])
    # every width as published, every layer in its published order, the whole vocabulary
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "num_hidden_layers",
        "num_dense_layers", "conv_L_cache", "vocab_size")] == [
            2048, 32, 8, 7168, 1792, 4, 24, 2, 3, 65536]
    assert config["layer_types"] == pub["config"]["layer_types"]
    assert (config["layer_types"].count("conv"), config["layer_types"].count("full_attention")) == (18, 6)
    share = config["share"]
    assert share["router_outputs"] == 32 and share["chips"] == 2
    assert share["chips"] * config["num_experts"] == 32 and share["vocab_chips"] == 1
    assert config["num_experts"] >= 8                    # the floor of a chip's share
    assert "2 chips" in config["deployment"] and "exchange" in config["deployment"]
    assert {"tie_word_embeddings", "torch_dtype", "rope_lanes", "expert_bias", "w_in_order",
            "weights", "state_in_pages"} <= set(config["assumed"])
    eng = config["engine"]
    assert (eng["max_batch_size"], eng["block_size"], eng["num_blocks"]) == (32, 16, 8385)
    assert eng["prefill_buckets"] == [2048, 4096]
    chk = config["check"]
    assert {"measured", "would_fail", "reason"} <= set(chk)
    assert chk["prompt_tokens"] <= eng["prefill_buckets"][0]
    for wrong in ("tap dropped", "taps reversed", "gate", "parity", "position 0",
                  "whole vector", "after rope", "softmax", "bias", "8-bit"):
        assert wrong in chk["would_fail"], wrong
    with open(f"{ROOT}/benchmarks/traffic/docs-4k-in-96-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 40
    assert traffic["output"]["min"] == traffic["output"]["max"] == 96
    from benchmarks.harness.schedule import strata
    lens = strata(traffic["prompt"])
    assert (min(lens), max(lens), len(lens)) == (1616, 4016, 16)
    assert sum(n <= 2048 for n in lens) == 3             # about a fifth take the 2,048 bucket
    assert traffic["trace"] == {"start_s": 5.0, "seconds": 4.0}
    assert traffic["max_requests_per_s"] == 20 and traffic["prefix_sharing"] == "none"


def test_the_program_s_configuration_and_what_it_refuses(config):
    from ray_tpu.models import lfm2 as program

    m = config["model"]
    cfg = lfm2.model_config(m)
    assert isinstance(cfg, program.Lfm2Config)
    assert (cfg.base.num_layers, cfg.num_dense_layers, cfg.conv_taps, cfg.state_rows) == (24, 2, 3, 2)
    assert (cfg.kinds.count("conv_dense"), cfg.kinds.count("conv_moe"),
            cfg.kinds.count("attn_moe")) == (2, 16, 6)
    assert cfg.experts.num_experts == 32 and cfg.experts.experts_held == (0, 16)
    assert cfg.experts.score_func == "sigmoid" and cfg.experts.routed_scaling == 1.0
    assert cfg.experts.norm_topk_prob and cfg.experts.norm_topk_eps == 1e-6
    assert cfg.base.tie_embeddings and cfg.base.hd == 64 and cfg.vocab_size == 65536
    for key, value in (("conv_bias", True), ("use_expert_bias", False),
                       ("tie_word_embeddings", False),
                       ("layer_types", m["layer_types"][:-1] + ["sliding_attention"])):
        with pytest.raises(SystemExit, match=key):
            lfm2.model_config({**m, key: value})


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 40 has no `ray_tpu/models/lfm2.py`: the new cell must
    end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "lfm2", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.lfm2", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.lfm2"):
        lfm2.model_config(config["model"])
    assert not hasattr(lfm2, "train_state_and_step")   # it serves only


def test_the_two_shares_and_both_rooflines_from_a_made_trace(config):
    """`conv_mix_share.batch` over the scope `conv` (a segment of the path:
    `convert_element_type` and `convolution` are none) and
    `conv_state_share.batch` over its two state scopes; the decode program's
    streaming roofline from the family's `decode_stream_step` and the paged
    kernel's from its `paged_attention_step`, by hand."""
    from benchmarks.harness.xplane import TraceSummary

    cell = spec.Cell(CELL)
    names = NEW + ("decode_stream_roofline.batch", "paged_attn_roofline.batch")
    metrics = [x for x in cell.per_layer if x["name"] in names]
    assert len(metrics) == 4 and all(x["moves"] == "served_tok_s" for x in metrics)
    body = "jit(decode)/jit(main)/while/body/"
    scopes = {body + "conv/in_proj/dot_general": 0.10, body + "conv/state_read/gather": 0.01,
              body + "conv/mix/mul": 0.02, body + "conv/state_write/scatter": 0.02,
              "jit(decode)/jit(main)/conv/dot_general": 0.05,          # a run of one: no loop
              body + "moe/experts/grouped_matmul_fwd": 0.6,
              body + "moe/combine/convert_element_type": 0.1,
              "jit(decode)/jit(main)/attn/kv_read/paged_attention_decode": 0.3,
              "jit(prefill)/jit(main)/while/body/conv/in_proj/dot_general": 0.3,
              "jit(prefill)/jit(main)/while/body/mlp/convolution": 0.5}
    trace = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"paged_attention_decode.3 custom-call": 0.3, "other": 1.7},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 60.0, "traced_context_tokens": 90_000.0,
                "traced_live_slots": 32.0}
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metrics, ctx)
    assert not missing
    assert values["conv_mix_share.batch"]["value"] == pytest.approx(100 * 0.50 / 2.0)
    assert values["conv_state_share.batch"]["value"] == pytest.approx(100 * 0.03 / 2.0)
    m = config["model"]
    stream = lfm2.decode_stream_step(m, 90_000.0, 32.0)["bytes"] / 819e9
    assert values["decode_stream_roofline.batch"]["value"] == pytest.approx(
        100 * 60 * stream / (2.0 - 0.8))                 # all but the prefill's operations
    paged = lfm2.paged_attention_step(m, 90_000.0, 32.0)["bytes"] / 819e9
    assert values["paged_attn_roofline.batch"]["value"] == pytest.approx(100 * 60 * paged / 0.3)
    assert ctx.notes["decode_stream_step_bound"] == "memory"


def test_the_cell_s_counters_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU, requests inside `jax.profiler.trace`. The expert layers' two counters
    are read from its records with no reader of this family's own; the two
    shares of the device trace find none on the CPU and do not raise."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m = cell.config["model"]
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=lfm2.model_config(m), max_batch_size=4, max_seq_len=128,
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
    assert len(new) == 2 and all(x["layer"] == "model" and x["better"] == "lower" for x in new)
    assert sorted(spec.read_metrics(new, ctx)[1]) == sorted(NEW)
    counted = [x for x in cell.per_layer
               if x["name"] in ("moe_rows_p50.batch", "moe_moved_per_held.batch")]
    values, missing = spec.read_metrics(counted, ctx)
    assert not missing and values["moe_rows_p50.batch"]["value"] > 0
    # half the experts held: every pair moved, about half of them held
    assert 1.2 < values["moe_moved_per_held.batch"]["value"] < 4.0
