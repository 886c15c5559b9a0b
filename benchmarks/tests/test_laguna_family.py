"""The Laguna family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its configuration against
the published shape, the catalog and the floors of a chip's share, its cell's
traffic, its six per-layer metrics from a made trace and from a toy engine's
own records, the shapes functions' bytes against the pool's leaves, and what it
says to a program that cannot serve it."""

import json
import sys

import pytest

from conftest import ROOT

from benchmarks.harness import shapes, spec
from benchmarks.harness.families import laguna
from benchmarks.harness.measure import Measurement

CELL = "serve-laguna-code8k-256-out"
CONFIG = "laguna-s-2.1-serve-ep8-12l-1chip"
PUBLISHED = "Laguna-S-2.1"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("window_attn_roofline.batch", "flash_window_roofline.batch", "attn_win_share.batch",
       "attn_full_share.batch", "window_pool_used_share.batch", "kv_held_of_uniform.batch")
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"}


@pytest.fixture(scope="module")
def config():
    with open(f"{ROOT}/benchmarks/configs/{CONFIG}.json") as f:
        cfg = json.load(f)
    cfg["model"] = {k: cfg[k] for k in laguna.MODEL_KEYS}
    return cfg


@pytest.mark.parametrize("part", ["parameters", "pool", "decode_stream_step", "prefill"])
def test_parameters_cache_and_the_step_s_bytes_by_hand(config, part):
    m = config["model"]
    attn = lambda nh: (2 * 3072 * nh * 128 + 2 * 3072 * 1024 + 3072 * nh + 2 * 128 + 2 * 3072)
    dense, expert, router = 3 * 3072 * 12288, 3 * 3072 * 1024, 3072 * 256
    here = laguna.params_here(m)
    if part == "parameters":
        assert laguna.heads_of(m, laguna.FULL) == 48 and laguna.heads_of(m, laguna.WINDOW) == 72
        assert laguna.attention_params(m, laguna.FULL) == attn(48) == 44_194_048       # 44.19 M
        assert laguna.attention_params(m, laguna.WINDOW) == attn(72) == 63_142_144     # 63.14 M
        assert laguna.expert_params(m) == laguna.shared_params(m) == expert == 9_437_184
        assert [len(laguna.layers_of(m, t)) for t in (laguna.FULL, laguna.WINDOW)] == [3, 9]
        assert laguna.layers_of(m, mlp="dense") == [0] and len(laguna.layers_of(m, mlp="sparse")) == 11
        assert here == {"embedding": 12544 * 3072, "head": 12544 * 3072 + 3072,
                        "attention_full": 3 * attn(48), "attention_window": 9 * attn(72),
                        "dense_mlp": dense, "routers": 11 * router,
                        "shared_experts": 11 * expert, "experts_held": 11 * 32 * expert}
        # layer 0 157.4 M, a full expert layer 356.4 M, a sliding one 375.4 M
        assert attn(48) + dense == 157_440_256
        assert attn(48) + 33 * expert + router == 356_407_552
        assert attn(72) + 33 * expert + router == 375_355_648
        total = sum(here.values())
        assert total == 4_325_529_600 and 8.65e9 < 2 * total < 8.66e9          # 4,325.5 M, 8.65 GB
        # the WHOLE model from the published file: every layer, expert and row
        with open(f"{ROOT}/benchmarks/configs/published/{PUBLISHED}.json") as f:
            pub = json.load(f)["config"]
        whole = laguna.params_here({**pub, "torch_dtype": "bfloat16", "share": {
            "router_outputs": 256}})
        assert sum(whole.values()) == 117_561_965_568                          # the catalog's "118B"
        # one layer's 256 experts are 4.83 GB, 4.94 with its attention, router and shared expert
        assert 4.83e9 < 2 * 256 * expert < 4.84e9 < 2 * (257 * expert + router + attn(48)) < 4.95e9
    elif part == "pool":
        # a window layer's ring a sequence: 512 K rows and 512 V rows of 1,024 lanes
        assert laguna.pool_row(m) == 1024 and laguna.ring_bytes(m) == 2_097_152
        assert 9 * laguna.ring_bytes(m) == 18_874_368                          # 18.9 MB a SEQUENCE
        eng = config["engine"]
        slots, blocks = eng["max_batch_size"], eng["num_blocks"]
        assert blocks == slots * 528 + 1 and -(-(8192 + 256) // 16) == 528
        assert laguna.kv_pool_blocks(config) == blocks - 1
        assert laguna.window_pool_pages(config) == slots
        # a sequence at the cell's longest context: 415 MB in one table, 122.7 MB here
        row = 2 * 1024 * 2
        assert 12 * row * 8448 == 415_236_096 and 3 * row * 8448 + 18_874_368 == 122_683_392
        pool = (slots + 1) * 18_874_368 + 3 * blocks * 16 * row
        assert 4.92e9 < pool < 4.94e9 and 2 * sum(here.values()) + pool < 13.6e9   # of the chip's 16
    elif part == "decode_stream_step":
        # 40 rows touch 25.5 of the 32 held experts if the router spreads evenly
        touched = 32 * (1 - (246 / 256) ** 40)
        assert laguna.experts_touched(m, 40) == pytest.approx(touched) == pytest.approx(25.5, abs=0.05)
        full = laguna.paged_attention_step(m, 188_000, 40)
        assert full["bytes"] == 3 * (2 * 188_000 * 1024 * 2 + 2 * 40 * 48 * 128 * 2)
        assert full["flops"] == 3 * 2 * 2 * 188_000 * 48 * 128
        # the rings' live rows and the rings, as the pool's own counters give them
        win = laguna.window_attention_step(m, 40 * 512, 40)
        assert win["bytes"] == 9 * (2 * 40 * 512 * 1024 * 2 + 2 * 40 * 72 * 128 * 2)
        # under the window a sequence's own rows
        assert laguna.window_attention_step(m, 3_000, 40)["bytes"] == 9 * (
            2 * 3_000 * 1024 * 2 + 2 * 40 * 72 * 128 * 2)
        work = laguna.decode_stream_step(m, 188_000, 40)
        fixed = (12544 * 3072 + 3072) + 3 * attn(48) + 9 * attn(72) + dense + 11 * (router + expert)
        assert work["bytes"] == pytest.approx(
            2 * (fixed + 11 * touched * expert) + full["bytes"] + win["bytes"])
        assert work["flops"] == pytest.approx(
            2 * 40 * (fixed + 11 * 10 * expert * 32 / 256) + full["flops"] + win["flops"])
        least, bound = shapes.least_seconds(work, V5E)
        # 10.3 GB a step: 12.6 ms at the HBM's speed, and memory bounds it
        assert bound == "memory" and work["bytes"] == pytest.approx(10.3e9, rel=0.01)
        assert least == pytest.approx(12.6e-3, rel=0.02)
    else:
        # the band at 8,192: 1.35 TFLOP, an eighth of the triangle's 11.1
        band = laguna.flash_window_prefill(m, 8192)
        pairs = 512 * 513 // 2 + (8192 - 512) * 512
        assert band["flops"] == 9 * 4 * 72 * 128 * pairs and band["flops"] == pytest.approx(1.35e12, rel=0.01)
        triangle = 9 * 4 * 72 * 128 * (8192 * 8193 // 2)
        assert triangle == pytest.approx(11.1e12, rel=0.01) and 7.9 < triangle / band["flops"] < 8.3
        assert laguna.flash_window_prefill(m, 300)["flops"] == 9 * 4 * 72 * 128 * (300 * 301 // 2)
        assert shapes.least_seconds(band, V5E)[1] == "compute"


def test_the_configuration_is_the_published_shape_at_a_stage_s_depth(config):
    with open(f"{ROOT}/benchmarks/configs/published/{PUBLISHED}.json") as f:
        pub = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == PUBLISHED)
    assert pub["config"] == row["config"] and pub["source"] == row["source_url"] == config["source"]
    changed = {k for k, v in pub["config"].items() if config.get(k, "missing") != v}
    assert changed == REDUCED == set(config["reduced"])
    assert config["published"] == {k: pub["config"][k] for k in changed}
    assert not changed & set(pub["widths"])
    bench = spec.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == changed and entry["source"] == row["source_url"]
    # every width as published; the per-layer lists whole, their first 12 read
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts_per_tok",
        "sliding_window", "num_hidden_layers")] == [3072, 48, 8, 128, 12288, 1024, 1024, 10, 512, 12]
    for key in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"):
        assert config[key] == pub["config"][key] and len(config[key]) == 48
    assert config["layer_types"][:12] == ["full_attention"] + ["sliding_attention"] * 3 + (
        ["full_attention"] + ["sliding_attention"] * 3) * 2
    assert config["num_attention_heads_per_layer"][:4] == [48, 72, 72, 72]
    share = config["share"]
    assert share == {"chips": 8, "rank": 0, "router_outputs": 256, "vocab_chips": 8, "stages": 4}
    assert share["chips"] * config["num_experts"] == 256
    assert share["vocab_chips"] * config["vocab_size"] == 100352
    assert share["stages"] * config["num_hidden_layers"] == 48
    # the floors of a chip's share: a whole period, four layers after the dense
    # one, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 1 + 4 and config["num_experts"] >= 8
    assert "8 chips" in config["deployment"] and "exchange" in config["deployment"]
    assert {"head_gate", "score_func", "qk_norm", "shared_expert", "rope_pairs", "weights",
            "rings"} <= set(config["assumed"])
    eng = config["engine"]
    assert (eng["max_batch_size"], eng["num_blocks"]) in ((40, 21121), (32, 16897))
    assert eng["block_size"] == 16 and eng["prefill_buckets"] == [2048, 4096, 8192]
    chk = config["check"]
    assert {"measured", "would_fail", "reason"} <= set(chk)
    assert (chk["prompt_tokens"], chk["new_tokens"]) == (1100, 64)
    for wrong in ("window ignored", "511", "513", "ropes swapped", "attention_factor",
                  "128 lanes", "gate", "48 heads", "shared expert", "sigmoid", "8-bit"):
        assert wrong in chk["would_fail"], wrong
    with open(f"{ROOT}/benchmarks/traffic/code-8k-in-256-out.json") as f:
        traffic = json.load(f)
    assert traffic["kind"] == "closed_loop"
    assert traffic["clients"] == eng["max_batch_size"] + 16
    assert traffic["output"]["min"] == traffic["output"]["max"] == 256
    from benchmarks.harness.schedule import strata
    lens = strata(traffic["prompt"])
    assert (min(lens), max(lens), len(lens)) == (1248, 7968, 16)
    # two strata take the 2,048 bucket, five the 4,096, nine the 8,192
    assert [sum(lo < n <= hi for n in lens) for lo, hi in ((0, 2048), (2048, 4096), (4096, 8192))] \
        == [2, 5, 9]
    assert max(lens) + 256 <= config["max_position_embeddings"] == 8448
    assert min(lens) > 2 * config["sliding_window"]     # every request is past the window
    assert traffic["trace"] == {"start_s": 5.0, "seconds": 4.0}
    assert traffic["prefix_sharing"] == "none" and traffic["max_requests_per_s"] == 20


def test_the_program_s_configuration_and_what_it_refuses(config):
    from ray_tpu.models import laguna as program

    m = config["model"]
    cfg = laguna.model_config(m)
    assert isinstance(cfg, program.LagunaConfig)
    assert cfg.kinds == ["lead"] + ["win"] * 3 + (["full"] + ["win"] * 3) * 2
    assert (cfg.base.num_layers, cfg.base.num_heads, cfg.window_heads, cfg.window) == (12, 48, 72, 512)
    assert (cfg.base.num_kv_heads, cfg.base.hd, cfg.base.intermediate_size) == (8, 128, 12288)
    assert cfg.experts.num_experts == 256 and cfg.experts.experts_held == (0, 32)
    assert cfg.experts.score_func == "softmax" and cfg.experts.routed_scaling == 2.5
    assert cfg.experts.norm_topk_prob and cfg.experts.top_k == 10 and cfg.shared_width == 1024
    assert cfg.rope_full == program.Rope(500000.0, 64, (128.0, 8192, 32.0, 1.0), 1.4852030263919618)
    assert cfg.rope_window == program.Rope(10000.0, None, None, 1.0)
    assert not cfg.base.tie_embeddings and cfg.vocab_size == 12544 and cfg.base.rms_eps == 1e-6
    heads = list(m["num_attention_heads_per_layer"])
    heads[5] = 64
    for key, value in (("attention_bias", True), ("tie_word_embeddings", True),
                       ("gating", True), ("moe_apply_router_weight_on_input", True),
                       ("moe_router_logit_softcapping", 30.0), ("decoder_sparse_step", 2),
                       ("mlp_only_layers", [0, 1]), ("num_attention_heads", 64)):
        with pytest.raises(SystemExit, match=key):
            laguna.model_config({**m, key: value})
    with pytest.raises(SystemExit, match="one count a kind"):
        laguna.model_config({**m, "num_attention_heads_per_layer": heads})


def test_a_program_without_the_family_is_told_so_by_name(config, monkeypatch):
    """The parent of PR 48 has no `ray_tpu/models/laguna.py`: the new cell
    must end there at once, before anything is built."""
    import ray_tpu.models

    monkeypatch.delattr(ray_tpu.models, "laguna", raising=False)
    monkeypatch.setitem(sys.modules, "ray_tpu.models.laguna", None)
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.laguna"):
        laguna.model_config(config["model"])
    assert not hasattr(laguna, "train_state_and_step")   # it serves only


def test_the_shapes_functions_count_the_pool_s_own_leaves():
    """At a tiny size: `ring_bytes` and `pool_row` against the leaves
    `laguna.init_kv_pool` makes."""
    from conftest import tiny_config
    from ray_tpu.models import laguna as program

    file = tiny_config("laguna", "serve")
    m = {k: file[k] for k in laguna.MODEL_KEYS}
    cfg = laguna.model_config(m)
    pool = program.init_kv_pool(cfg, 9, 16, num_sequences=5)
    Lw, Lf = len(laguna.layers_of(m, laguna.WINDOW)), len(laguna.layers_of(m, laguna.FULL))
    assert (pool["k_win"].nbytes + pool["v_win"].nbytes) // (Lw * 5) == laguna.ring_bytes(m)
    assert pool["k"].nbytes // (Lf * 9 * 16) == laguna.pool_row(m) * shapes._itemsize(m)
    step = laguna.window_attention_step(m, 3.0 * m["sliding_window"], 3.0)
    assert step["bytes"] == Lw * (3 * laguna.ring_bytes(m) + 2 * 3 * 6 * 16 * 4)


def test_the_shares_and_rooflines_from_a_made_trace(config, monkeypatch):
    """`attn_win_share.batch` and `attn_full_share.batch` over the two layer
    scopes, `window_attn_roofline.batch` from the family's
    `window_attention_step` over the window kernel's own name (the full
    layers' kernel's time is not in it, nor the reverse),
    `flash_window_roofline.batch` from the profiled admit records' prompts
    over the banded forward's name, the decode program's streaming roofline and
    the paged kernel's over the 3 full layers, by hand."""
    from benchmarks.harness.xplane import TraceSummary
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL)
    names = NEW[:4] + ("decode_stream_roofline.batch", "paged_attn_roofline.batch",
                       "pool_write_share.batch")
    metrics = [x for x in cell.per_layer if x["name"] in names]
    assert len(metrics) == 7 and all(x["moves"] == "served_tok_s" for x in metrics)
    d, p = "jit(decode)/jit(main)/", "jit(prefill)/jit(main)/"
    scopes = {d + "attn_win/attn/kv_read/paged_attention_window": 0.09,
              d + "attn_win/attn/dot_general": 0.20, d + "attn_win/attn/kv_write/scatter": 0.01,
              d + "attn_full/attn/kv_read/paged_attention_decode": 0.24,
              d + "attn_full/attn/dot_general": 0.05, d + "moe/experts/grouped_matmul_fwd": 0.5,
              p + "attn_win/attn/prompt_attend/flash_attention_window": 0.09,
              p + "attn_win/attn/dot_general": 0.25, p + "attn_win/attn/kv_write/scatter": 0.01,
              p + "attn_full/attn/prompt_attend/flash_attention_fwd": 0.08,
              p + "attn_full/attn/kv_write/scatter": 0.01, p + "moe/combine/dot_general": 0.32}
    trace = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                         op_self_s={"paged_attention_window.3 custom-call": 0.09,
                                    "paged_attention_decode.5 custom-call": 0.24,
                                    "flash_attention_window.7 custom-call": 0.09,
                                    "flash_attention_fwd.9 custom-call": 0.08, "other": 1.50},
                         scope_self_s=scopes)
    counters = {"traced_decode_steps": 60.0, "traced_context_tokens": 190_000.0,
                "traced_live_slots": 40.0}
    records = [("admit", {"outcome": "admitted", "prompt": 7968, "profiled": True}),
               ("admit", {"outcome": "admitted", "prompt": 1248, "profiled": True}),
               ("admit", {"outcome": "requeued", "profiled": True}),
               ("admit", {"outcome": "admitted", "prompt": 5000}),      # cut by the trace's edge
               # 58 whole steps of the trace's 60: one sequence still under the window
               *[("decode", {"win_rows": 39 * 512 + 300, "win_rings": 40, "profiled": True})] * 58,
               ("decode", {"win_rows": 40 * 512, "win_rings": 40})]     # cut by the trace's edge
    monkeypatch.setattr(timeline, "local_events", lambda: [
        ("span", i, "engine", name, None, 0.0, 0.1, args) for i, (name, args) in enumerate(records)])
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=trace)
    values, missing = spec.read_metrics(metrics, ctx)
    assert not missing
    assert values["attn_win_share.batch"]["value"] == pytest.approx(100 * 0.65 / 2.0)
    assert values["attn_full_share.batch"]["value"] == pytest.approx(100 * 0.38 / 2.0)
    assert values["pool_write_share.batch"]["value"] == pytest.approx(100 * 0.03 / 2.0)
    m = config["model"]
    window = laguna.window_attention_step(m, 39 * 512 + 300, 40)["bytes"] / 819e9
    assert values["window_attn_roofline.batch"]["value"] == pytest.approx(100 * 58 * window / 0.09)
    paged = laguna.paged_attention_step(m, 190_000.0, 40.0)["bytes"] / 819e9
    assert values["paged_attn_roofline.batch"]["value"] == pytest.approx(100 * 60 * paged / 0.24)
    band = sum(laguna.flash_window_prefill(m, n)["flops"] for n in (7968, 1248)) / 197e12
    assert values["flash_window_roofline.batch"]["value"] == pytest.approx(100 * band / 0.09)
    stream = laguna.decode_stream_step(m, 190_000.0, 40.0)["bytes"] / 819e9
    assert values["decode_stream_roofline.batch"]["value"] == pytest.approx(
        100 * 60 * stream / (2.0 - 0.76))                    # all but the prefill's operations
    assert ctx.notes["window_attention_step_bound"] == "memory"
    assert ctx.notes["flash_window_prefill_bound"] == "compute"
    for v in values.values():
        assert 0 < v["value"] < 100
    # a program without the kernels (the parent's) leaves both rooflines out
    bare = TraceSummary(window_s=4.0, busy_s=2.0, n_chips=1, op_calls_n={}, gaps=[],
                        op_self_s={"other": 2.0}, scope_self_s=scopes)
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks=V5E, counters=counters, trace=bare)
    assert sorted(spec.read_metrics([x for x in metrics if x["name"] in NEW[:2]], ctx)[1]) == sorted(NEW[:2])


def test_the_cell_s_counters_read_a_toy_engine_s_own_records(tiny_root, tmp_path):
    """The readers against the program itself: the stand-in's engine on the
    CPU, requests inside `jax.profiler.trace`. `window_pool_used_share.batch`,
    `kv_held_of_uniform.batch` and the expert layers' two counters are read
    from its records; the four metrics of the device trace find none on the
    CPU and do not raise."""
    import jax

    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
    from ray_tpu.util import timeline

    cell = spec.Cell(CELL, root=tiny_root)
    m = cell.config["model"]
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=laguna.model_config(m), max_batch_size=4, max_seq_len=128,
        block_size=16, num_blocks=33, prefill_buckets=(32, 64)))
    try:
        eng.generate_sync(list(range(1, 11)), 3)
        with jax.profiler.trace(str(tmp_path)):
            futs = [eng.generate(list(range(1, n + 1)), new)
                    for n, new in ((40, 30), (20, 24), (50, 28))]
            assert [f.result(120).num_generated for f in futs] == [30, 24, 28]
            eng.shutdown()
    finally:
        eng.shutdown()
    ctx = Measurement(config=cell.config, traffic=cell.traffic, family=cell.family,
                      peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
    new = [x for x in cell.per_layer if x["name"] in NEW]
    assert len(new) == 6
    values, missing = spec.read_metrics(new, ctx)
    assert sorted(missing) == sorted(NEW[:4])
    # 4 slots (the stand-in's engine section), up to 3 sequences live
    assert 25 <= values["window_pool_used_share.batch"]["value"] <= 75
    # 2 full layers of 8: a quarter, and six rings of 24 rows beside 48-80 rows a layer
    assert 25 < values["kv_held_of_uniform.batch"]["value"] < 75
    counted = [x for x in cell.per_layer
               if x["name"] in ("moe_rows_p50.batch", "moe_moved_per_held.batch",
                                "kv_pool_used_share.batch")]
    values, missing = spec.read_metrics(counted, ctx)
    assert not missing and values["moe_rows_p50.batch"]["value"] > 0
    assert 1.0 <= values["moe_moved_per_held.batch"]["value"] < 4.0
