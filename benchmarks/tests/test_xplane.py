"""The reduction from a profiler trace to numbers, on traces recorded on a
TPU v5e: 2.5 s of the paged engine decoding 32 sequences and three train
steps of the 4-layer configuration at 2 x 4096 tokens (PR 23, before the
program named its kernels and scopes), and 0.75 s cut from a traced run of
`serve-chat-steady` (PR 26: four decode steps and one admission, with the
kernels' names, the model's named scopes and the engine's annotations)."""

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


@pytest.fixture(scope="module")
def scoped():
    return xplane.reduce_file(os.path.join(FIXTURES, "decode_scoped_v5e.xplane.pb.gz"))


@pytest.mark.parametrize("name,planes,names,top", [
    ("decode_v5e", 1, 85, "jit(decode)/while/body/closed_call/pallas_call"),
    ("train_step_v5e", 1, 227,
     "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/pallas_call"),
])
def test_op_names_come_from_the_file_s_event_metadata(name, planes, names, top):
    """The two traces from before PR 24 hold `op_name`s too, without scopes."""
    path = os.path.join(FIXTURES, name + ".xplane.pb.gz")
    op_names = xplane.load_op_names(path)
    assert list(op_names) == ["/device:TPU:0"] and len(op_names) == planes
    assert len(op_names["/device:TPU:0"]) == names
    trace = xplane.reduce_planes(xplane.load_planes(path), op_names)
    assert max(trace.scope_self_s, key=trace.scope_self_s.get) == top
    # the kernels' time is the same found by label or by op_name
    assert trace.scope_seconds("pallas_call") == pytest.approx(
        trace.op_seconds("tpu_custom_call"), abs=1e-9)
    # without the table the reduction is what it was
    bare = xplane.reduce_planes(xplane.load_planes(path))
    assert bare.scope_self_s == {} and bare.op_self_s == trace.op_self_s


def test_scope_seconds_on_a_trace_with_named_scopes(scoped):
    assert scoped.window_s == pytest.approx(0.748043, abs=1e-6)
    assert scoped.busy_s == pytest.approx(0.621980, abs=1e-6)
    # an operation counts under every scope of its path: the kernel lies in
    # attn/kv_read, which lies in attn
    kernel = scoped.op_seconds("paged_attention_decode")
    assert kernel == pytest.approx(0.271612, abs=1e-6)
    assert scoped.op_seconds("tpu_custom_call") == kernel      # no other Mosaic call
    assert scoped.op_calls("paged_attention_decode") == 51
    assert scoped.scope_seconds("attn/kv_read/paged_attention_decode") == kernel
    both = scoped.scope_seconds("attn/kv_write|attn/kv_read")
    assert both == pytest.approx(0.323513, abs=1e-6)
    assert scoped.scope_seconds("/attn/") > both > kernel
    # the model's own pool writes and reads, the kernel left out
    assert scoped.scope_seconds(
        r"attn/kv_write|attn/kv_read/(?!paged_attention_decode)") == pytest.approx(
        both - kernel, abs=1e-9)
    assert scoped.scope_seconds("mlp") == pytest.approx(0.055238, abs=1e-6)
    # the scan's stacking of the pool is in none of the model's scopes
    assert scoped.scope_seconds(
        r"^jit\(decode\)/while/body/(dynamic_slice|squeeze|dynamic_update_slice)$"
    ) == pytest.approx(0.133749, abs=1e-6)
    assert scoped.scope_seconds("no/such/scope") == 0.0
    # self times: scopes partition what carries an op_name, and no more than busy
    assert sum(scoped.scope_self_s.values()) == pytest.approx(0.571030, abs=1e-6)
    assert sum(scoped.scope_self_s.values()) < scoped.busy_s


def test_idle_gaps_take_the_engine_s_step_annotations_not_its_phases(scoped):
    gaps = dict(scoped.breakdown()["idle_gaps"])
    assert set(gaps) == {"engine:decode", "engine:admit"}
    assert gaps["engine:decode"] == pytest.approx(0.073075, abs=1e-6)
    assert sum(gaps.values()) == pytest.approx(scoped.window_s - scoped.busy_s, abs=1e-6)


def test_scope_share_reader(scoped):
    from benchmarks.harness.measure import Measurement
    from benchmarks.readers import scope_share

    ctx = Measurement(config={}, traffic={}, peaks={})
    assert scope_share.read(ctx, scope="mlp") is None          # no trace
    ctx.trace = scoped
    assert scope_share.read(ctx, scope="mlp") == pytest.approx(
        100 * 0.055238 / 0.621980, rel=1e-4)
    assert scope_share.read(ctx, scope="no/such/scope") == 0.0
    ctx.trace = xplane.reduce_planes(xplane.load_planes(
        os.path.join(FIXTURES, "decode_v5e.xplane.pb.gz")))
    assert scope_share.read(ctx, scope="mlp") is None          # no op_name in it


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
