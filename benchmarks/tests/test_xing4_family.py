"""The Xing4 family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its configuration against
the published shape, the catalog and the floors of a chip's share, its cell's
traffic, its three per-layer metrics read from a toy engine's own records and
from a made trace, and what it says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import xing4
from benchmarks.harness.measure import Measurement

CELL = "serve-xing-midin-384-out"
CONFIG = "xing4.0-29b-a4b-serve-ep8-1chip"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("hc_mix_share.batch", "hc_map_share.batch", "hc_residue_p50.batch")


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/{CONFIG}.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in xing4.MODEL_KEYS}
    return cfg


@pytest.mark.parametrize("part", ["parameters", "pool", "decode_stream_step"])
def test_parameters_cache_and_the_step_s_bytes_by_hand(config, part):
    m = config["model"]
    attn = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 32 * 512 * (128 + 128)
            + 32 * 128 * 3584)
    expert, hyper = 3 * 3584 * 1024, 2 * (4 * 3584) * (4 + 4 + 16)
    outside = attn + 3584 * 64 + expert                               # router, shared
    here = xing4.params_here(m)
    if part == "parameters":
        assert xing4.attention_params(m) == attn == 28_409_856         # 28.41 M
        assert xing4.expert_params(m) == expert == 11_010_048          # 11.01 M
        assert xing4.hyper_params(m) == hyper == 688_128               # 0.69 M a layer
        assert here["dense_layers"] == 2 * (attn + 3 * 3584 * 9216)    # 2 x 127.50 M
        assert here["expert_layers_outside_experts"] == 38 * outside   # 38 x 39.65 M
        assert here["experts_held"] == 38 * 8 * expert                 # 88.08 M a layer
        assert here["hyper_connections"] == 40 * hyper
        assert here["embedding_and_head"] == 2 * 131072 * 3584 == 939_524_096
        total = sum(here.values())
        assert total == 6_075_777_024 and 12.14e9 < 2 * total < 12.16e9    # 12.15 GB in bf16
        # the WHOLE model: every expert, and the prediction module left aside
        whole = total + 38 * 56 * expert
        assert 29.4e9 < whole < 29.6e9 and 58.9e9 < 2 * whole < 59.1e9
    elif part == "pool":
        # the latent cache: 576 values a token and layer in 640 lanes, 40 layers
        assert xing4.cache_layers(m) == 40
        token = 40 * 640 * 2
        assert token == 51_200 and 40 * 576 * 2 == 46_080
        assert xing4.kv_pool_blocks(config) == 2688 == 48 * 56
        pool = 2689 * 16 * token
        assert pool == 40 * 2689 * 16 * 640 * 2 == 2_202_828_800        # 2.20 GB
        # 48 slots x 56 blocks (a 512-token prompt and 384 out, the bucket's longest) fit
        assert -(-(512 + 384) // 16) == 56
        assert 14.34e9 < 2 * sum(here.values()) + pool < 14.37e9        # of the chip's 16
    else:
        # 48 rows touch 7.64 of the 8 held experts if the router spreads evenly
        touched = 8 * (1 - (60 / 64) ** 48)
        assert xing4.experts_touched(m, 48) == pytest.approx(touched) == pytest.approx(7.64, abs=0.01)
        work = xing4.decode_stream_step(m, 25_000, 48)
        fixed = (here["dense_layers"] + here["expert_layers_outside_experts"]
                 + here["hyper_connections"] + 131072 * 3584)
        attn_work = xing4.latent_attention_step(m, 25_000, 48)
        assert attn_work["bytes"] == 40 * (25_000 * 576 * 2 + 48 * 32 * (576 + 512) * 2)
        assert attn_work["flops"] == 40 * 2 * 32 * (576 + 512) * 25_000
        assert work["bytes"] == pytest.approx(
            2 * (fixed + 38 * touched * expert) + attn_work["bytes"])
        # a row goes through 4 x 8 / 64 of an expert held here a layer
        assert work["flops"] == pytest.approx(
            2 * 48 * (fixed + 38 * 0.5 * expert) + attn_work["flops"])
        least, bound = shapes.least_seconds(work, V5E)
        # 12.1 GB a step: 14.8 ms at the HBM's speed, and memory bounds it
        assert bound == "memory" and least == pytest.approx(14.8e-3, rel=0.02)


def test_the_configuration_is_the_published_shape_at_full_depth(config):
    with open(f"{ROOT}/benchmarks/configs/published/Xing4.0-29B-A4B.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"]
    changed = {k for k, v in pub["config"].items() if config.get(k, "missing") != v}
    assert changed == {"n_routed_experts", "max_position_embeddings",
                       "num_nextn_predict_layers"} == set(config["reduced"])
    assert config["published"] == {k: pub["config"][k] for k in changed}
    assert not changed & set(pub["widths"])
    # every width as published, every layer, the whole vocabulary
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "q_lora_rank", "kv_lora_rank", "moe_intermediate_size",
        "num_experts_per_tok", "intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "vocab_size", "hc_mult", "hc_sinkhorn_iters")] == [
            3584, 32, 128, 64, 128, 768, 512, 1024, 4, 9216, 40, 2, 131072, 4, 20]
    share = config["share"]
    assert share["router_outputs"] == 64 and share["chips"] == 8
    assert share["chips"] * config["n_routed_experts"] == 64 and share["vocab_chips"] == 1
    assert config["n_routed_experts"] >= 8                    # the floor of a chip's share
    assert "8 chips" in config["deployment"] and "exchange" in config["deployment"]
    assert {"streams", "maps", "map_init", "layout", "prediction_module", "torch_dtype",
            "rope_lanes", "correction_bias", "weights"} <= set(config["assumed"])
    eng = config["engine"]
    assert (eng["max_batch_size"], eng["block_size"], eng["num_blocks"]) == (48, 16, 2689)
    assert eng["prefill_buckets"] == [128, 256, 512]
    chk = config["check"]
    assert {"measured", "would_fail", "reason"} <= set(chk)
    for wrong in ("plain residual", "1 Sinkhorn iteration", "factor 2", "bfloat16-rounded",
                  "softmax", "shared expert", "8-bit"):
        assert wrong in chk["would_fail"], wrong
    with open(f"{ROOT}/benchmarks/traffic/mid-in-384-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 64
    assert traffic["output"]["min"] == traffic["output"]["max"] == 384
    from benchmarks.harness.schedule import strata
    lens = strata(traffic["prompt"])
    assert (min(lens), max(lens), len(lens)) == (109, 499, 16)
    assert traffic["trace"] == {"start_s": 5.0, "seconds": 4.0}
    assert traffic["max_requests_per_s"] == 20 and traffic["prefix_sharing"] == "none"


def test_the_program_s_configuration_and_what_it_refuses(config):
    from ray_tpu.models import llama
    from ray_tpu.models import xing4 as program

    m = config["model"]
    cfg = xing4.model_config(m)
    assert isinstance(cfg, program.Xing4Config)
    assert (cfg.first_k_dense, cfg.base.num_layers, cfg.cache_layers) == (2, 38, 40)
    assert cfg.experts.num_experts == 64 and cfg.experts.experts_held == (0, 8)
    assert cfg.experts.score_func == "sigmoid" and cfg.experts.routed_scaling == 2
    assert cfg.latent_row == 640 and cfg.vocab_size == 131072
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    assert cfg.hyper == llama.HyperConnections(n=4, sinkhorn_iters=20, eps=1e-6,
                                               clamp=(-30.0, 30.0))
    # the prediction module is refused, not guessed: the file lists the key under `reduced`
    for key, value in (("num_nextn_predict_layers", 1), ("scoring_func", "softmax"),
                       ("n_group", 8)):
        with pytest.raises(SystemExit, match=key):
            xing4.model_config({**m, key: value})


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 37 has no `ray_tpu/models/xing4.py`: the new cell
    must end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "xing4", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.xing4", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.xing4"):
        xing4.model_config(config["model"])
    assert not hasattr(xing4, "train_state_and_step")   # it serves only


def test_the_cell_s_metrics_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU, requests inside `jax.profiler.trace`. The counter's metric is read
    from the decode records; the two of the device trace find none and do not
    raise; a program whose records lack the counter leaves its metric out."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m = cell.config["model"]
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=xing4.model_config(m), max_batch_size=4, max_seq_len=128,
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
    assert len(new) == 3 and all(x["moves"] == "served_tok_s" for x in new)
    assert all(x["layer"] == "model" and x["better"] == "lower" for x in new)
    values, missing = spec.read_metrics(new, ctx)
    assert sorted(missing) == ["hc_map_share.batch", "hc_mix_share.batch"]
    assert 0 < values["hc_residue_p50.batch"]["value"] < 1e-4
    # the cell also reports Kimi's counter through the same records
    rows = [x for x in cell.per_layer if x["name"] == "moe_rows_p50.batch"]
    assert spec.read_metrics(rows, ctx)[0]["moe_rows_p50.batch"]["value"] > 0
    events = [e[:7] + [{k: v for k, v in e[7].items() if k != "hc_residue"}]
              if e[0] == "span" and isinstance(e[7], dict) else e
              for e in map(list, timeline.local_events())]
    import unittest.mock as mock
    with mock.patch.object(timeline, "local_events", lambda: events):
        _, missing = spec.read_metrics(new, Measurement(
            config=cell.config, traffic=cell.traffic, family=cell.family, peaks=ctx.peaks))
    assert "hc_residue_p50.batch" in missing


def test_the_scope_shares_and_both_rooflines_from_a_made_trace(config):
    """The two shares over busy time (`hc/map|hc/sinkhorn|hc/mix` and the
    chain alone), the decode program's streaming roofline from the family's
    `decode_stream_step` and the kernel's from `latent_attention_step` at 32
    heads and 40 layers, by hand."""
    from benchmarks.harness.xplane import TraceSummary

    cell = spec.Cell(CELL)
    names = NEW[:2] + ("decode_stream_roofline.batch", "latent_attn_roofline.batch")
    metrics = [x for x in cell.per_layer if x["name"] in names]
    assert len(metrics) == 4
    body = "jit(decode)/jit(main)/while/body/"
    scopes = {body + "hc/map/dot_general": 0.02, body + "hc/sinkhorn/mul": 0.03,
              body + "hc/mix/add": 0.05, body + "moe/experts/grouped_matmul_fwd": 0.9,
              body + "attn/latent_read/latent_attention_decode": 0.4,
              "jit(prefill)/jit(main)/while/body/hc/mix/add": 0.1,
              "jit(prefill)/jit(main)/while/body/mlp/dot_general": 0.5}
    trace = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"latent_attention_decode.3 custom-call": 0.4, "other": 1.6},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 50.0, "traced_context_tokens": 25_000.0,
                "traced_live_slots": 48.0}
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metrics, ctx)
    assert not missing
    assert values["hc_mix_share.batch"]["value"] == pytest.approx(100 * 0.2 / 2.0)
    assert values["hc_map_share.batch"]["value"] == pytest.approx(100 * 0.05 / 2.0)
    m = config["model"]
    stream = xing4.decode_stream_step(m, 25_000.0, 48.0)["bytes"] / 819e9
    assert values["decode_stream_roofline.batch"]["value"] == pytest.approx(
        100 * 50 * stream / (2.0 - 0.6))                 # all but the prefill's operations
    latent = xing4.latent_attention_step(m, 25_000.0, 48.0)["bytes"] / 819e9
    assert values["latent_attn_roofline.batch"]["value"] == pytest.approx(100 * 50 * latent / 0.4)
    assert ctx.notes["decode_stream_step_bound"] == "memory"
