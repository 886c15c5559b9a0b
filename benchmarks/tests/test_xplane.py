"""The reduction from a profiler trace to numbers, on two traces recorded on
a TPU v5e (PR 23): 2.5 s of the paged engine decoding 32 sequences, and three
train steps of the 4-layer configuration at 2 x 4096 tokens."""

import os

import pytest

from benchmarks.harness import xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture(scope="module")
def decode():
    return xplane.reduce_planes(xplane.load_planes(
        os.path.join(FIXTURES, "decode_v5e.xplane.pb.gz")))


@pytest.fixture(scope="module")
def train():
    return xplane.reduce_planes(xplane.load_planes(
        os.path.join(FIXTURES, "train_step_v5e.xplane.pb.gz")))


def test_decode_trace(decode):
    assert decode.n_chips == 1
    assert decode.window_s == pytest.approx(2.501792, abs=1e-5)
    assert decode.busy_s == pytest.approx(2.452184, abs=1e-5)
    assert decode.busy_s <= decode.window_s
    # self times: a while loop is not counted on top of its body
    assert sum(decode.op_self_s.values()) == pytest.approx(decode.busy_s, rel=2e-3)
    # the Mosaic paged-attention call: 16 a decode step
    assert decode.op_seconds("tpu_custom_call") == pytest.approx(1.653965, abs=1e-5)
    assert decode.op_calls("tpu_custom_call") == 169
    top = decode.breakdown()["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("closed_call")
    assert "custom-call[tpu_custom_call]" in top[0][0]
    # idle time is named after the harness's annotation that covers it
    gaps = dict(decode.breakdown()["idle_gaps"])
    assert gaps["bench:decode"] == pytest.approx(0.046187, abs=1e-5)
    assert sum(gaps.values()) == pytest.approx(decode.window_s - decode.busy_s, abs=1e-6)


def test_train_trace(train):
    assert train.window_s == pytest.approx(2.822344, abs=1e-5)
    assert 0.99 < train.busy_s / train.window_s <= 1.0
    # forward, rematted forward, dQ and dK/dV: 4 kernels x 4 layers x 3 steps
    assert train.op_calls("tpu_custom_call") == 48
    assert train.op_seconds("tpu_custom_call") == pytest.approx(1.747038, abs=1e-5)
    assert train.op_seconds(r"all-gather|all-reduce|reduce-scatter") == 0.0


def test_label_leaves_operands_out():
    hlo = ('%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} '
           '%all-gather-done.2, f32[] %closed_call.10), kind=kLoop')
    label = xplane.label_of(hlo)
    assert label == "fusion.3 fusion bf16[8,128]"
    assert "all-gather" not in label and "closed_call" not in label
    assert xplane.label_of(
        '%ag = (bf16[4]{0}, bf16[16]{0}) all-gather-start(bf16[4]{0} %p)'
    ).startswith("ag all-gather-start")


def test_no_device_plane_reduces_to_nothing():
    assert xplane.reduce_planes([("/host:CPU", [("python3", [(0, 10, "x")])])]) is None
