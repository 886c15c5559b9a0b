"""The OLMoE family's own tests, added with it: its shapes functions against
numbers worked out by hand at the published widths, its cell through the whole
command on the CPU stand-in with the step's scalars reaching their reader, and
what it says to a program that cannot train it."""

import json

import pytest

from conftest import ROOT

from benchmarks.harness import shapes
from benchmarks.harness.families import olmoe

OLMOE_3L = {"hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 3,
            "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
            "vocab_size": 50304, "num_experts": 64, "num_experts_per_tok": 8,
            "torch_dtype": "bfloat16"}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameters_and_flops_by_hand():
    attention = 4 * 2048 * 2048                       # 16.8 M
    router = 2048 * 64                                # 0.13 M
    experts = 64 * 3 * 2048 * 1024                    # 402.7 M
    assert (attention, router, experts) == (16_777_216, 131_072, 402_653_184)
    assert olmoe.params_per_layer(OLMOE_3L) == attention + router + experts + 4 * 2048 \
        == 419_569_664                                # 419.6 M a layer
    active = attention + router + 8 * 3 * 2048 * 1024
    assert olmoe.active_params_per_layer(OLMOE_3L) == active == 67_239_936
    # a token's expert products forward: 100.7 MFLOP of a layer's 151
    assert 2 * 8 * 3 * 2048 * 1024 == 100_663_296
    pairs = 16 * 2 * 2 * 128 * (4096 + 1) / 2         # QK^T and PV, causal
    assert 2 * active + pairs == pytest.approx(151.3e6, rel=1e-3)
    head = 2048 * 50304
    want = 3 * (2 * (3 * active + head) + 3 * pairs)
    assert olmoe.train_flops_per_token(OLMOE_3L, 4096) == pytest.approx(want)
    assert want == pytest.approx(1.979e9, rel=1e-3)   # 100% of a v5e is 99.5k tokens/s
    assert 197e12 / want == pytest.approx(99.5e3, rel=2e-3)
    # the head is 31% of the required FLOPs at three layers and 8% at sixteen
    assert 6 * head / want == pytest.approx(0.31, abs=0.005)
    at_16 = olmoe.train_flops_per_token({**OLMOE_3L, "num_hidden_layers": 16}, 4096)
    assert 6 * head / at_16 == pytest.approx(0.08, abs=0.005)
    # attention is counted as the Llama family counts it
    assert olmoe.flash_attention_step is shapes.flash_attention_step


def test_grouped_matmul_step_by_hand():
    work = olmoe.grouped_matmul_step(OLMOE_3L, 2, 4096)
    rows = 2 * 4096 * 8                               # 65,536: 1,024 an expert
    product = 2 * rows * 2048 * 1024                  # 274.9 GFLOP
    assert work["flops"] == 3 * 9 * product
    weights = 64 * 2048 * 1024 * 2                    # one of gate, up, down: 268 MB
    moved = rows * (2048 + 1024) * 2                  # a product's two row operands
    assert work["bytes"] == 3 * 9 * (weights + moved)
    least, bound = shapes.least_seconds(work, V5E)
    assert bound == "compute"
    assert least == pytest.approx(3 * 9 * product / 197e12)      # 37.7 ms a step
    # compute bounds it from a few hundred rows an expert: at one sequence of
    # 512 tokens (64 rows an expert) the weights' bytes do
    assert shapes.least_seconds(olmoe.grouped_matmul_step(OLMOE_3L, 1, 512), V5E)[1] == "memory"


def test_the_cell_runs_on_its_stand_in_and_its_scalars_reach_the_reader(
        tiny_root, cpu_as_device, capsys, monkeypatch):
    from benchmarks import run
    from benchmarks.harness import spec

    seen = {}
    read_metrics = spec.read_metrics

    def keep(metrics, ms):
        seen["ms"] = ms
        return read_metrics(metrics, ms)

    monkeypatch.setattr(spec, "read_metrics", keep)
    rc = run.main(["--workload", "train-olmoe-4k-1chip", "--seed", str(2 ** 31 + 27),
                   "--seconds", "1", "--trace", "0"], root=tiny_root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s_chip", "setup_s"}
    ms = seen["ms"]
    for name in ("loss", "nll", "aux_loss", "router_load_max", "grad_norm"):
        assert len(ms.series[f"step.{name}"]) == line["attempted"], name
    cell = spec.Cell("train-olmoe-4k-1chip", root=tiny_root)
    by_name = {m["name"]: m for m in cell.per_layer}
    assert {"grouped_mm_roofline", "moe_route_share", "router_load_max_p50",
            "flash_attn_roofline", "train_mfu", "step_ms"} <= set(by_name)
    values, missing = read_metrics([by_name["router_load_max_p50"], by_name["train_mfu"]], ms)
    assert missing == [] and 1.0 <= values["router_load_max_p50"]["value"] <= 4.0
    # without a device trace the trace's readers find nothing and do not raise
    values, missing = read_metrics(
        [by_name["grouped_mm_roofline"], by_name["moe_route_share"]], ms)
    assert values == {} and missing == ["grouped_mm_roofline", "moe_route_share"]


def test_a_program_without_the_model_record_is_told_so_by_name(monkeypatch):
    """The parent of PR 27 has `models/moe.py` and no `MODEL`: the new cell
    must end there at once, before anything is built."""
    from ray_tpu.models import moe

    monkeypatch.delattr(moe, "MODEL")
    with pytest.raises(SystemExit, match=r"ray_tpu\.models\.moe\.MODEL"):
        olmoe.train_state_and_step(OLMOE_3L, {"remat_policy": "dots"}, None, None)


def test_the_configuration_is_the_published_shape_cut_in_depth_alone():
    with open(f"{ROOT}/benchmarks/configs/olmoe-1b-7b-0125-train-1chip.json") as f:
        cfg = json.load(f)
    with open(f"{ROOT}/benchmarks/configs/published/OLMoE-1B-7B-0125-Instruct.json") as f:
        pub = json.load(f)
    assert {k for k, v in pub["config"].items() if cfg.get(k, "missing") != v} == \
        {"num_hidden_layers"} == set(cfg["reduced"])
    assert {"head_dim", "router_aux_loss_coef", "torch_dtype"} <= set(cfg["assumed"])
    assert cfg["trainer"]["sequences_per_chip"] >= 2
    assert cfg["num_hidden_layers"] in (2, 3)
    model = {k: cfg[k] for k in olmoe.MODEL_KEYS}
    assert olmoe.params_per_layer(model) == 419_569_664
