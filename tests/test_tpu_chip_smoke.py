"""chip_smoke.py's phases at LlamaConfig.tiny() sizes on the CPU.

`chip_smoke.main()` has no CPU mode and is not run here. These tests import
the file and call each phase function with small `Sizes`, so its control flow
(trainer, serve app, HTTP, SSE, prefix cache, sharding checks, teardown) is
debugged before chip time is spent on it. The kernels run interpreted and the
Mosaic assertions are skipped off-TPU; tests/test_tpu_aot.py covers those.

Also here: the engine's shutdown joins its loop thread (a daemon thread left
inside a jitted call aborts the interpreter at exit with status 134).
"""

import os
import subprocess
import sys
import threading
from concurrent.futures import Future

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from ray_tpu.models import llama  # noqa: E402


@pytest.fixture(scope="module")
def sizes():
    return chip_smoke.Sizes(
        model=llama.LlamaConfig.tiny(), flash=(1, 128, 4, 2, 16),
        paged_batch=8, train_batch=2, train_seq=64, train_steps=5,
        four_batch=4, serve_batch=4, serve_seq=256, max_tokens=4)


@pytest.fixture(scope="module")
def session(sizes):
    """phase_device starts the runtime the later phases use; teardown is the
    smoke's own, and must leave no engine thread behind."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    info = chip_smoke.phase_device(sizes)
    yield info
    chip_smoke.teardown()
    assert not ray_tpu.is_initialized()
    assert not [t for t in threading.enumerate()
                if t.name.endswith("LLMEngine") and t.is_alive()]


def test_main_refuses_without_tpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu()
    assert e.value.code not in (0, None) and "not 'tpu'" in str(e.value.code)


def test_main_last_stdout_line_is_the_contract_object(monkeypatch, tmp_path,
                                                      capsys):
    """The driver parses the LAST line of stdout and refuses any key beyond
    ok / device{platform, kind, count}. main()'s own control flow runs here
    with the device and the phases stubbed (the phases have their own tests)."""
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "teardown", lambda: None)
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    for name in ("phase_device", "phase_kernels", "phase_train", "phase_serve"):
        monkeypatch.setattr(chip_smoke, name, lambda sizes: {})
    monkeypatch.setattr("ray_tpu.util.compile_cache.ensure_compile_cache",
                        lambda platform: str(tmp_path / "cache"))
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    # what else the run learned is on the line before, not in the result
    detail = json.loads(lines[-2].split("summary: ", 1)[1])
    assert detail["four_chip"] == "skipped (1 devices)"
    assert detail["claim"] is None
    assert (tmp_path / "chip_smoke.jsonl").exists()


def test_phase_device(session):
    assert session["platform"] == "cpu" and session["runtime_tpus"] == 0
    assert os.path.exists(session["native_store"])


def test_phase_kernels(sizes):
    out = chip_smoke.phase_kernels(sizes)
    assert out["flash_fwd_err"] <= out["flash_fwd_tol"]
    assert out["paged_logit_err"] <= out["paged_logit_tol"]


def test_phase_train(session, sizes):
    out = chip_smoke.phase_train(sizes)
    assert len(out["losses"]) == sizes.train_steps
    assert out["losses"][-1] < out["losses"][0]


def test_phase_serve(session, sizes):
    import gc

    from ray_tpu.serve.llm_paged import PagedLLMEngine

    out = chip_smoke.phase_serve(sizes)
    assert out["platform"] == "cpu" and out["prefix_hits"] >= 1
    # the phase shut its app down; the engine (weights, KV pool) must be
    # freed by reference count alone, not wait for a cycle collection
    # (by type, not isinstance: a dead `weakref.proxy` that another test of
    # this process left behind, `serve/kv_transport.py`'s, raises ReferenceError
    # when asked for its class)
    assert not [o for o in gc.get_objects() if issubclass(type(o), PagedLLMEngine)]


def test_phase_four_chip(session, sizes):
    out = chip_smoke.phase_four_chip(sizes)
    assert set(out) == {"four_chip[fsdp=2,tensor=2]", "four_chip[fsdp=4]"}


def test_engine_shutdown_joins_loop_thread():
    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine

    eng = PagedLLMEngine(PagedLLMConfig(max_seq_len=64, max_batch_size=2))
    fut = eng.generate([1, 2, 3], max_new_tokens=2)
    assert fut.result(timeout=120).num_generated == 2
    assert eng.stats()["platform"] == "cpu"
    t = eng._loop_thread
    eng.shutdown()
    assert not t.is_alive()
    # the paged subclass still drains queued PD ops after the join
    op = Future()
    eng._ops.put(("prefill_extract", [1], op))
    eng.shutdown()
    with pytest.raises(RuntimeError, match="shut down"):
        op.result(timeout=1)


def test_process_with_engine_exits_cleanly():
    """Build an engine, use it, shut it down, exit: status 0, not 134."""
    code = (
        "from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine\n"
        "e = PagedLLMEngine(PagedLLMConfig(max_seq_len=64, max_batch_size=2))\n"
        "assert e.generate_sync([1, 2, 3], 2).num_generated == 2\n"
        "e.shutdown()\n"
        "print('done')\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run([sys.executable, "-c", code], env=env, timeout=300,
                       capture_output=True, text=True)
    assert r.returncode == 0, (r.returncode, r.stderr[-2000:])
    assert "done" in r.stdout
