"""The Nemotron-H family's own tests, added with it: its shapes functions
against numbers worked out by hand at the published widths, its configuration
against the published shape, the catalog and the floors of a chip's share, its
cell's traffic, its five per-layer metrics from a made trace and from a toy
engine's own records, the shapes functions' bytes against the pool's leaves,
and what it says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import nemotron_h
from benchmarks.harness.measure import Measurement

CELL = "serve-nemotron-tools4k-256-out"
CONFIG = "nemotron-3-nano-30b-a3b-serve-ep8-1chip"
PUBLISHED = "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("ssm_mix_share.batch", "ssm_state_share.batch", "ssm_state_roofline.batch",
       "ssm_step_roofline.batch", "state_pool_used_share.batch")


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/{CONFIG}.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in nemotron_h.MODEL_KEYS}
    return cfg


@pytest.mark.parametrize("part", ["parameters", "pool", "decode_stream_step"])
def test_parameters_cache_and_the_step_s_bytes_by_hand(config, part):
    m = config["model"]
    mamba = (2688 * (4096 + 6144 + 64) + 4096 * 2688       # in, out
             + 5 * 6144 + 3 * 64 + 4096 + 2688)             # taps and bias, A D dt, two norms
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688          # q, o; k, v; the norm
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    router = 2688 * 128 + 128 + 2688
    here = nemotron_h.params_here(m)
    if part == "parameters":
        assert nemotron_h.mamba_params(m) == mamba == 38_744_896            # 38.74 M
        assert nemotron_h.attention_params(m) == attn == 23_399_040         # 23.40 M
        assert nemotron_h.expert_params(m) == expert == 9_977_856           # 9.978 M
        assert nemotron_h.shared_params(m) == shared == 19_955_712
        assert [nemotron_h.blocks_of(m, c) for c in "M*E"] == [23, 6, 23]
        assert here == {"embedding": 16384 * 2688, "head": 16384 * 2688 + 2688,
                        "mamba_mixers": 23 * mamba, "attention": 6 * attn,
                        "routers": 23 * router, "shared_experts": 23 * shared,
                        "experts_held": 23 * 16 * expert}
        total = sum(here.values())
        assert total == 5_258_420_544 and 10.51e9 < 2 * total < 10.53e9      # 5,258 M, 10.52 GB
        # the WHOLE model from the published file: every expert, the whole vocabulary
        with open(f"{ROOT}/benchmarks/configs/published/{PUBLISHED}.json") as f:
            pub = json.load(f)["config"]
        whole = nemotron_h.params_here({**pub, "torch_dtype": "bfloat16", "share": {
            "router_outputs": 128}})
        assert sum(whole.values()) == 31_577_940_288                         # the published 31.6 B
        assert sum(whole.values()) == total + 23 * 112 * expert + 2 * 2688 * (131072 - 16384)
        # one whole expert layer is 2.59 GB: a chip holds 5 and nothing else
        assert 2.59e9 < 2 * (128 * expert + shared + router) < 2.60e9
    elif part == "pool":
        # a sequence's state: 64 heads x 64 x 128 float32 and 3 x 6,144 bfloat16 a block
        assert nemotron_h.state_page_bytes(m) == 2_097_152 + 36_864
        assert 23 * nemotron_h.state_page_bytes(m) == 49_082_368              # 49.1 MB a SEQUENCE
        # kept a block of 16 tokens it would be 49.1 MB a block: 9.2 GB for 3,000 tokens
        assert 9.2e9 < 188 * 49_082_368 < 9.3e9
        # a token's K and V over the 6 attention blocks: 6,144 B
        assert nemotron_h.pool_row(m) == 256 and 2 * 6 * 256 * 2 == 6_144
        eng = config["engine"]
        slots, blocks = eng["max_batch_size"], eng["num_blocks"]
        assert blocks == slots * 272 + 1 and -(-(4096 + 256) // 16) == 272
        assert nemotron_h.kv_pool_blocks(config) == blocks - 1
        assert nemotron_h.state_pool_pages(config) == slots
        pool = (slots + 1) * 49_082_368 + 2 * 6 * blocks * 16 * 256 * 2
        assert 2 * sum(here.values()) + pool < 14.3e9                          # of the chip's 16
    else:
        # 48 rows touch 14.4 of the 16 held experts if the router spreads evenly
        touched = 16 * (1 - (122 / 128) ** 48)
        assert nemotron_h.experts_touched(m, 48) == pytest.approx(touched) == pytest.approx(14.40, abs=0.01)
        state = nemotron_h.ssm_state_step(m, 48)
        assert state["bytes"] == 48 * 23 * 2 * (2_097_152 + 36_864) == 4_711_907_328   # 4.7 GB
        attn_work = nemotron_h.paged_attention_step(m, 144_000, 48)
        assert attn_work["bytes"] == 6 * (2 * 144_000 * 256 * 2 + 2 * 48 * 32 * 128 * 2)
        assert attn_work["flops"] == 6 * 2 * 2 * 144_000 * 32 * 128
        work = nemotron_h.decode_stream_step(m, 144_000, 48)
        fixed = (16384 * 2688 + 2688) + 23 * mamba + 6 * attn + 23 * router + 23 * shared
        assert work["bytes"] == pytest.approx(
            2 * (fixed + 23 * touched * expert) + attn_work["bytes"] + state["bytes"])
        assert work["flops"] == pytest.approx(
            2 * 48 * (fixed + 23 * 6 * expert * 16 / 128) + attn_work["flops"] + state["flops"])
        least, bound = shapes.least_seconds(work, V5E)
        # 15.3 GB a step: 18.7 ms at the HBM's speed, and memory bounds it
        assert bound == "memory" and work["bytes"] == pytest.approx(15.3e9, rel=0.01)
        assert least == pytest.approx(18.7e-3, rel=0.02)


def test_the_configuration_is_the_published_shape_at_full_depth(config):
    with open(f"{ROOT}/benchmarks/configs/published/{PUBLISHED}.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == PUBLISHED)
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"] == config["source"]
    changed = {k for k, v in pub["config"].items() if config.get(k, "missing") != v}
    assert changed == {"n_routed_experts", "vocab_size", "max_position_embeddings"} \
        == set(config["reduced"])
    assert config["published"] == {k: pub["config"][k] for k in changed}
    assert not changed & set(pub["widths"])
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == changed and entry["source"] == row["source_url"]
    # every width as published, every block in its published order
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "num_hidden_layers", "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
        "conv_kernel", "chunk_size")] == [2688, 32, 2, 128, 1856, 3712, 6, 52, 64, 64, 128, 8, 4, 128]
    assert config["hybrid_override_pattern"] == pub["config"]["hybrid_override_pattern"]
    assert len(config["hybrid_override_pattern"]) == 52
    assert "MM" not in config["hybrid_override_pattern"] and "EE" not in config["hybrid_override_pattern"]
    share = config["share"]
    assert share == {"chips": 8, "rank": 0, "router_outputs": 128, "vocab_chips": 8}
    assert share["chips"] * config["n_routed_experts"] == 128
    assert share["vocab_chips"] * config["vocab_size"] == 131072
    assert config["n_routed_experts"] >= 8                # the floor of a chip's share
    assert "8 chips" in config["deployment"] and "exchange" in config["deployment"]
    assert {"no_rotary", "state_dtypes", "selection_bias", "ssm_tensors", "weights",
            "state_pages", "e_up_transposed"} <= set(config["assumed"])
    eng = config["engine"]
    assert (eng["max_batch_size"], eng["num_blocks"]) in ((48, 13057), (40, 10881), (32, 8705))
    assert eng["block_size"] == 16 and eng["prefill_buckets"] == [2048, 4096]
    chk = config["check"]
    assert {"measured", "would_fail", "reason"} <= set(chk)
    assert (chk["prompt_tokens"], chk["new_tokens"]) == (1100, 64)
    for wrong in ("bfloat16", "padding", "tap dropped", "B and C swapped", "silu",
                  "shared expert", "4,096", "rotary"):
        assert wrong in chk["would_fail"], wrong
    with open(f"{ROOT}/benchmarks/traffic/tools-4k-in-256-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop"
    assert traffic["clients"] == eng["max_batch_size"] + 16
    assert traffic["output"]["min"] == traffic["output"]["max"] == 256
    from benchmarks.harness.schedule import strata
    lens = strata(traffic["prompt"])
    assert (min(lens), max(lens), len(lens)) == (1120, 4000, 16)
    assert sum(n <= 2048 for n in lens) == 5              # 5 take the 2,048 bucket, 11 the 4,096
    assert max(lens) + 256 <= config["max_position_embeddings"] == 4352
    assert traffic["trace"] == {"start_s": 5.0, "seconds": 4.0}
    assert traffic["prefix_sharing"] == "none"


def test_the_program_s_configuration_and_what_it_refuses(config):
    from ray_tpu.models import nemotron_h as program

    m = config["model"]
    cfg = nemotron_h.model_config(m)
    assert isinstance(cfg, program.NemotronHConfig)
    assert (cfg.base.num_layers, cfg.d_inner, cfg.conv_channels) == (52, 4096, 6144)
    assert [cfg.count(k) for k in ("mamba", "attn", "experts")] == [23, 6, 23]
    assert cfg.experts.num_experts == 128 and cfg.experts.experts_held == (0, 16)
    assert cfg.experts.score_func == "sigmoid" and cfg.experts.routed_scaling == 2.5
    assert cfg.experts.activation == "relu2" and cfg.experts.norm_topk_eps == 1e-20
    assert (cfg.base.num_heads, cfg.base.num_kv_heads, cfg.base.hd) == (32, 2, 128)
    assert not cfg.base.tie_embeddings and cfg.vocab_size == 16384 and cfg.shared_width == 3712
    for key, value in (("use_conv_bias", False), ("mlp_hidden_act", "silu"), ("n_group", 2),
                       ("tie_word_embeddings", True), ("mamba_proj_bias", True),
                       ("hybrid_override_pattern", m["hybrid_override_pattern"][:-1] + "-")):
        with pytest.raises(SystemExit, match=key):
            nemotron_h.model_config({**m, key: value})


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 45 has no `ray_tpu/models/nemotron_h.py`: the new cell
    must end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "nemotron_h", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.nemotron_h", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.nemotron_h"):
        nemotron_h.model_config(config["model"])
    assert not hasattr(nemotron_h, "train_state_and_step")   # it serves only


def test_the_shapes_functions_count_the_pool_s_own_leaves():
    """At a tiny size: `state_page_bytes` and `pool_row` against the leaves
    `nemotron_h.init_kv_pool` makes."""
    from conftest import tiny_config
    from ray_tpu.models import nemotron_h as program

    file = tiny_config("nemotron_h", "serve")
    m = {k: file[k] for k in nemotron_h.MODEL_KEYS}
    cfg = nemotron_h.model_config(m)
    pool = program.init_kv_pool(cfg, 9, 16, num_sequences=5)
    Lm = nemotron_h.blocks_of(m, "M")
    a_page = (pool["ssm"].nbytes + pool["conv"].nbytes) // (Lm * 5)
    assert a_page == nemotron_h.state_page_bytes(m)
    assert nemotron_h.ssm_state_step(m, 3)["bytes"] == 3 * Lm * 2 * a_page
    row = pool["k"].nbytes // (nemotron_h.cache_layers(m) * 9 * 16)
    assert row == nemotron_h.pool_row(m) * shapes._itemsize(m)


def test_the_shares_and_rooflines_from_a_made_trace(config):
    """`ssm_mix_share.batch` over the mixer's own scopes, `ssm_state_share.batch`
    over the two state scopes, `ssm_state_roofline.batch` from the family's
    `ssm_state_step` over the decode program's state scopes, the decode
    program's streaming roofline and the paged kernel's, by hand."""
    from benchmarks.harness.xplane import TraceSummary

    cell = spec.Cell(CELL)
    names = NEW[:4] + ("decode_stream_roofline.batch", "paged_attn_roofline.batch")
    metrics = [x for x in cell.per_layer if x["name"] in names]
    assert len(metrics) == 6 and all(x["moves"] == "served_tok_s" for x in metrics)
    d, p = "jit(decode)/jit(main)/", "jit(prefill)/jit(main)/"
    scopes = {d + "ssm/in_proj/dot_general": 0.10, d + "ssm/state_read/gather": 0.05,
              d + "ssm/step/ssm_state_step": 0.25, d + "ssm/state_write/scatter": 0.08,
              d + "ssm/conv/add": 0.01, d + "ssm/gate_norm/mul": 0.01,
              d + "moe/experts/grouped_matmul_fwd": 0.4,
              d + "attn/kv_read/paged_attention_decode": 0.08,
              p + "ssm/scan/dot_general": 0.3, p + "ssm/state_write/scatter": 0.01,
              p + "ssm/in_proj/dot_general": 0.2, p + "mlp/convolution": 0.39}
    trace = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"paged_attention_decode.3 custom-call": 0.08,
                                    "ssm_state_step.5 custom-call": 0.25, "other": 1.67},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 30.0, "traced_context_tokens": 140_000.0,
                "traced_live_slots": 48.0}
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metrics, ctx)
    assert not missing
    assert values["ssm_mix_share.batch"]["value"] == pytest.approx(100 * 0.57 / 2.0)
    assert values["ssm_state_share.batch"]["value"] == pytest.approx(100 * 0.14 / 2.0)
    m = config["model"]
    state = nemotron_h.ssm_state_step(m, 48.0)["bytes"] / 819e9
    named, total = sum(scopes.values()), 2.0
    assert values["ssm_state_roofline.batch"]["value"] == pytest.approx(
        100 * 30 * state / (total - (named - 0.38)))        # the decode program's three scopes
    kernel = nemotron_h.ssm_kernel_step(m, 48.0)["bytes"] / 819e9
    assert nemotron_h.ssm_kernel_step(m, 48.0)["bytes"] == 48 * 23 * 2 * 2_097_152
    assert values["ssm_step_roofline.batch"]["value"] == pytest.approx(100 * 30 * kernel / 0.25)
    stream = nemotron_h.decode_stream_step(m, 140_000.0, 48.0)["bytes"] / 819e9
    assert values["decode_stream_roofline.batch"]["value"] == pytest.approx(
        100 * 30 * stream / (total - 0.9))                   # all but the prefill's operations
    paged = nemotron_h.paged_attention_step(m, 140_000.0, 48.0)["bytes"] / 819e9
    assert values["paged_attn_roofline.batch"]["value"] == pytest.approx(100 * 30 * paged / 0.08)
    assert ctx.notes["ssm_state_step_bound"] == "memory"
    for v in values.values():
        assert 0 < v["value"] < 100


def test_the_cell_s_counters_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU, requests inside `jax.profiler.trace`. `state_pool_used_share.batch`
    and the expert blocks' two counters are read from its records; the three
    shares of the device trace find none on the CPU and do not raise."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m = cell.config["model"]
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=nemotron_h.model_config(m), max_batch_size=4, max_seq_len=128,
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
    assert len(new) == 5
    values, missing = spec.read_metrics(new, ctx)
    assert sorted(missing) == sorted(NEW[:4])
    # 4 slots (the stand-in's engine section), up to 3 sequences live
    assert 25 <= values["state_pool_used_share.batch"]["value"] <= 75
    counted = [x for x in cell.per_layer
               if x["name"] in ("moe_rows_p50.batch", "moe_moved_per_held.batch",
                                "kv_pool_used_share.batch")]
    values, missing = spec.read_metrics(counted, ctx)
    assert not missing and values["moe_rows_p50.batch"]["value"] > 0
    assert 1.2 < values["moe_moved_per_held.batch"]["value"] < 4.0
