"""Tests of the benchmark's own code. They run on the CPU:
`python -m pytest benchmarks/tests -q -p no:cacheprovider`."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
    "max_position_embeddings": 256, "tie_word_embeddings": False,
    "sliding_window": None, "hidden_act": "silu", "torch_dtype": "float32"}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout-shaped directory whose BENCHMARK.json names the real cells
    and metrics (the real metric files) over toy configurations and short
    traffic, so the whole command runs on the CPU in seconds."""
    root = tmp_path / "checkout"
    bench = root / "benchmarks"
    for d in ("configs", "traffic"):
        (bench / d).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "metrics"), bench / "metrics")

    def dump(path, obj):
        path.write_text(json.dumps(obj))

    dump(bench / "configs" / "tiny-serve.json", {
        **TINY_MODEL, "family": "llama", "reference": "llama_reference",
        "chips": 1, "mesh": {},
        "engine": {"max_batch_size": 4, "block_size": 16, "num_blocks": 0,
                   "prefill_buckets": [32, 64]},
        "check": {"sequences": 2, "prompt_tokens": 24, "new_tokens": 4,
                  "rel_rms": 1e-3, "rel_max": 1e-3}})
    dump(bench / "configs" / "tiny-train.json", {
        **TINY_MODEL, "family": "llama", "reference": "llama_reference",
        "chips": 1, "mesh": {},
        "trainer": {"warmup_steps": 1, "remat_policy": "dots",
                    "sequences_per_chip": 2, "warmup_steps_before_window": 1},
        "check": {"loss_abs": 1e-3}})
    prompt = {"dist": "lognormal", "median": 40, "sigma": 0.8, "min": 8,
              "max": 150, "strata": 16}
    output = {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
              "max": 40, "strata": 16}
    dump(bench / "traffic" / "chat-paced.json", {
        "kind": "open_loop_paced", "rate_rps": 6.0, "jitter": 0.2,
        "prompt": prompt, "output": output, "fill": {"lifetime_s": 0.4},
        "min_tokens_for_tpot": 4})
    dump(bench / "traffic" / "docs-batch.json", {
        "kind": "closed_loop", "clients": 4,
        "prompt": {"dist": "uniform", "min": 65, "max": 200, "strata": 16},
        "output": {"dist": "uniform", "min": 8, "max": 8, "strata": 1}})
    dump(bench / "traffic" / "pretrain-4k.json",
         {"kind": "train_job", "seq_len": 64})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for w in real["workloads"]:
        w["config"] = "tiny-serve" if "serve" in w["config"] else "tiny-train"
        w["chips"] = 1
    dump(root / "BENCHMARK.json", real)
    return str(root)


@pytest.fixture
def cpu_as_device(monkeypatch):
    """The test-only device stub: the CPU stands where the TPU is required,
    with a made-up row in the peak table."""
    from benchmarks.harness import device, peaks

    monkeypatch.setattr(device, "require_tpu", lambda chips: device.describe())
    monkeypatch.setitem(peaks.PEAKS, "cpu", {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
        "source": "test stub"})
