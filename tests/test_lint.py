"""graftlint (ray_tpu.devtools.lint) — framework + rule fixtures.

Every rule gets at least one true-positive fixture and one clean (or
suppressed) fixture; the baseline gets an append-allowed /
edit-rejected round-trip; and one test runs the FULL analyzer over the
shipped tree inside the tier-1 budget (exit 0, baseline-aware).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import time

import pytest

from ray_tpu.devtools.lint import RULES, FileCtx, ProjectCtx, lint_source
from ray_tpu.devtools.lint import baseline as bl
from ray_tpu.devtools.lint.core import Finding, Suppressions
from ray_tpu.devtools.lint.runner import parse_all, repo_root, run_pass
from ray_tpu.devtools.lint.rules import concurrency, hotpath, wire

REPO = repo_root()


class FakeCtx:
    """ProjectCtx stand-in over in-memory sources (project-rule fixtures)."""

    def __init__(self, files: dict):
        self.root = "."
        self._files = {rel: FileCtx(".", rel, src,
                                    ast.parse(src, filename=rel))
                       for rel, src in files.items()}

    def get(self, rel):
        return self._files.get(rel)

    finding = ProjectCtx.finding


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ===================================================== framework mechanics

def test_rule_registry_names():
    import ray_tpu.devtools.lint.rules  # noqa: F401

    expected = {
        "schema-baseline", "handlers-schemad", "no-pickle-in-rpc",
        "blob-zero-copy", "dag-loop-rpc-free", "version-gating",
        "hot-path-purity", "lock-order", "ref-drop-under-lock",
        "blocking-under-lock", "reactor-blocking-handler",
        "thread-hygiene", "swallowed-exception",
    }
    assert expected <= set(RULES)


def test_suppressions_same_line_prev_line_and_file():
    src = (
        "x = 1  # graftlint: disable=some-rule\n"
        "# graftlint: disable=other-rule\n"
        "y = 2\n"
        "# graftlint: disable-file=file-rule\n"
        "z = 3\n"
    )
    sup = Suppressions(src)
    assert sup.is_suppressed("some-rule", 1)
    assert not sup.is_suppressed("some-rule", 2)
    assert sup.is_suppressed("other-rule", 3)   # comment line covers next
    assert sup.is_suppressed("file-rule", 5)    # anywhere in the file
    assert not sup.is_suppressed("unrelated", 5)


def test_parse_error_becomes_finding(tmp_path):
    pkg = tmp_path / "ray_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("def broken(:\n")
    (pkg / "good.py").write_text("x = 1\n")
    files, errors = parse_all(str(tmp_path), ["ray_tpu/bad.py",
                                              "ray_tpu/good.py"])
    assert "ray_tpu/good.py" in files
    assert [e.rule for e in errors] == ["parse-error"]


# ===================================================== baseline round-trip

def _mk_findings(n, rule="lock-order", path="ray_tpu/x.py"):
    return [Finding(rule=rule, path=path, line=i + 1, message="m",
                    key=f"k{i}") for i in range(n)]


def test_baseline_append_allowed_edit_and_renumber_rejected():
    doc = bl.append_entries({"version": 1, "entries": []}, _mk_findings(3))
    assert bl.validate(doc) == []
    # append: legal
    doc2 = bl.append_entries(doc, _mk_findings(1, rule="thread-hygiene"))
    assert bl.validate(doc2) == []
    assert len(doc2["entries"]) == 4
    assert doc2["entries"][:3] == doc["entries"]
    # edit a shipped entry's key: hash mismatch
    import copy

    tampered = copy.deepcopy(doc2)
    tampered["entries"][1]["key"] = "something-else"
    errs = bl.validate(tampered)
    assert any("must not be edited" in e for e in errs)
    # renumber / delete a shipped entry: dense-id violation
    renumbered = copy.deepcopy(doc2)
    del renumbered["entries"][0]
    errs = bl.validate(renumbered)
    assert any("append-only" in e or "renumber" in e for e in errs)
    # editing downstream of a deletion also breaks the hash chain
    assert any("hash mismatch" in e or "must not be edited" in e
               for e in errs)


def test_baseline_matching_and_stale_reporting(tmp_path):
    # a baseline entry tolerates its finding; a stale entry is reported
    f = _mk_findings(1)[0]
    doc = bl.append_entries({"version": 1, "entries": []},
                            [f, Finding(rule="lock-order", path="gone.py",
                                        line=1, message="m", key="stale")])
    ents = bl.entries(doc)
    tolerated = bl.match_key(ents)
    assert (f.rule, f.path, f.key) in tolerated
    assert ("lock-order", "gone.py", "stale") in tolerated


def test_shipped_baseline_file_validates():
    doc = bl.load(os.path.join(REPO, "scripts", "lint_baseline.json"))
    assert doc["entries"], "shipped baseline should freeze existing debt"
    assert bl.validate(doc) == []


# ============================================== concurrency rule fixtures

PR5_DEADLOCK = '''
import threading

class Runtime:
    def __init__(self):
        self._lock = threading.Lock()
        self._task_put_holds = {}

    def release_task_put_holds(self, task_bin):
        with self._lock:
            self._task_put_holds.pop(task_bin, None)
'''


def test_ref_drop_flags_the_pr5_deadlock_pattern():
    """Acceptance: the historical PR-5 ObjectRef.__del__-under-_lock
    deadlock, reintroduced verbatim, is flagged."""
    out = lint_source(PR5_DEADLOCK, ["ref-drop-under-lock"])
    assert len(out) == 1
    assert "__del__" in out[0].message
    assert out[0].key.startswith("Runtime.release_task_put_holds:")


def test_ref_drop_clean_when_value_dies_outside_lock():
    fixed = '''
import threading

class Runtime:
    def __init__(self):
        self._lock = threading.Lock()
        self._task_put_holds = {}

    def release_task_put_holds(self, task_bin):
        with self._lock:
            holds = self._task_put_holds.pop(task_bin, None)
        del holds  # dies outside the lock
'''
    assert lint_source(fixed, ["ref-drop-under-lock"]) == []


def test_ref_drop_del_and_clear_variants_and_rlock_exempt():
    src = '''
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._rlock = threading.RLock()
        self._m = {}

    def a(self, k):
        with self._lock:
            del self._m[k]

    def b(self):
        with self._lock:
            self._m.clear()

    def c(self, k):
        with self._rlock:
            self._m.pop(k, None)  # reentrant: __del__ re-entry is safe
'''
    out = lint_source(src, ["ref-drop-under-lock"])
    assert sorted(f.key for f in out) == [
        "S.a:self._lock:del self._m[k]",
        "S.b:self._lock:discarded self._m.clear()",
    ]


def test_ref_drop_suppressed_inline():
    sup = PR5_DEADLOCK.replace(
        "self._task_put_holds.pop(task_bin, None)",
        "self._task_put_holds.pop(task_bin, None)"
        "  # graftlint: disable=ref-drop-under-lock")
    assert lint_source(sup, ["ref-drop-under-lock"]) == []


def test_lock_order_cycle_detected():
    src = '''
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._b:
            with self._a:
                pass
'''
    out = lint_source(src, ["lock-order"])
    assert len(out) == 1 and "cycle" in out[0].message


def test_lock_order_consistent_nesting_clean():
    src = '''
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def two(self):
        with self._a:
            with self._b:
                pass
'''
    assert lint_source(src, ["lock-order"]) == []


def test_lock_order_reentrant_acquisition_via_self_call():
    src = '''
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()

    def outer(self):
        with self._lock:
            self.inner()

    def inner(self):
        with self._lock:
            pass
'''
    out = lint_source(src, ["lock-order"])
    assert len(out) == 1 and "self-deadlock" in out[0].message
    # RLock: same shape, legal
    assert lint_source(src.replace("threading.Lock()",
                                   "threading.RLock()"),
                       ["lock-order"]) == []


def test_lock_order_cross_method_cycle():
    """A->B in one method, B->(call)->A through a self-call in another."""
    src = '''
import threading

class S:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                pass

    def takes_a(self):
        with self._a:
            pass

    def two(self):
        with self._b:
            self.takes_a()
'''
    out = lint_source(src, ["lock-order"])
    assert len(out) == 1 and "cycle" in out[0].message


def test_blocking_under_lock_positive_and_exclusions():
    src = '''
import os, threading, time

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition()

    def bad(self, peer, fut, t):
        with self._lock:
            fut.result()
            peer.call("ping")
            time.sleep(1)
            t.join()

    def fine(self, parts, t):
        with self._cond:
            self._cond.wait()          # CV protocol releases the lock
        with self._lock:
            s = ", ".join(parts)       # str.join, not thread join
            p = os.path.join("a", "b")
        fut_result = None
        t.join()                       # no lock held
        return s, p
'''
    out = lint_source(src, ["blocking-under-lock"])
    assert [f.key.split(":")[-1] for f in out] == \
        ["result", "call", "sleep", "join"]
    assert all(f.key.startswith("S.bad:") for f in out)


def test_blocking_under_lock_event_wait_flagged():
    src = '''
import threading

class S:
    def __init__(self):
        self._lock = threading.Lock()
        self._evt = threading.Event()

    def bad(self):
        with self._lock:
            self._evt.wait()
'''
    out = lint_source(src, ["blocking-under-lock"])
    assert len(out) == 1 and "wait" in out[0].key


def test_thread_hygiene_positive_and_tracked_paths():
    leak = '''
import threading

def spawn(work):
    t = threading.Thread(target=work)
    t.start()
'''
    out = lint_source(leak, ["thread-hygiene"])
    assert len(out) == 1 and "leaked" in out[0].message

    ok = '''
import threading

class M:
    def start_all(self, work):
        self._t = threading.Thread(target=work)
        self._t.start()
        self._pool = []
        self._pool.append(threading.Thread(target=work))
        d = threading.Thread(target=work, daemon=True)
        d.start()

    def stop(self):
        self._t.join()
        for t in self._pool:
            t.join()
'''
    assert lint_source(ok, ["thread-hygiene"]) == []


def test_swallowed_exception_keys_discriminate_per_handler():
    """A baselined swallow must not mask a NEW broad except added to the
    same function: every handler gets a distinct key."""
    src = '''
def f(x, y):
    try:
        x()
    except Exception:
        pass
    try:
        y()
    except Exception:
        pass
    try:
        y()
    except:
        pass
'''
    keys = [f.key for f in lint_source(src, ["swallowed-exception"])]
    assert len(keys) == 3 and len(set(keys)) == 3


def test_swallowed_exception_positive_and_reported_paths():
    bad = '''
def f(x):
    try:
        x()
    except Exception:
        pass
'''
    out = lint_source(bad, ["swallowed-exception"])
    assert len(out) == 1 and out[0].key == "f:swallow:except Exception"

    ok = '''
import logging
logger = logging.getLogger(__name__)

def a(x):
    try:
        x()
    except Exception:
        logger.debug("x failed")

def b(x):
    try:
        x()
    except Exception:
        raise RuntimeError("wrapped")

def c(x, fut):
    try:
        x()
    except Exception as e:
        fut.set_exception(e)

def d(x):
    try:
        x()
    except ValueError:
        pass  # narrow catch: fine
'''
    assert lint_source(ok, ["swallowed-exception"]) == []


# ============================================ wire/project rule fixtures

def test_schema_baseline_clean_on_tree_and_flags_injected_violation():
    from ray_tpu.core.rpc import schema

    ctx = wire.OnDemandCtx(REPO)
    assert wire.schema_registry_findings(ctx) == []
    bogus = dataclasses.replace(schema.REGISTRY["ping"], num=5,
                                name="zz_lint_test_op")
    schema.REGISTRY["zz_lint_test_op"] = bogus
    try:
        keys = {f.key for f in wire.schema_registry_findings(ctx)}
        assert "dup-num:5" in keys
        assert "below-floor:zz_lint_test_op" in keys
    finally:
        del schema.REGISTRY["zz_lint_test_op"]


def test_version_gating_clean_on_tree_and_flags_ungated_op():
    from ray_tpu.core.rpc import schema

    ctx = wire.OnDemandCtx(REPO)
    assert wire.gate_findings(ctx) == []
    orig = schema.REGISTRY["kv_ack"]
    schema.REGISTRY["kv_ack"] = dataclasses.replace(orig, since=1)
    try:
        keys = {f.key for f in wire.gate_findings(ctx)}
        assert "gate:kv_ack" in keys
    finally:
        schema.REGISTRY["kv_ack"] = orig


def test_handlers_schemad_flags_unschemad_callsite():
    ctx = FakeCtx({"ray_tpu/core/cluster.py": '''
class CP:
    def f(self, peer):
        peer.call("zz_not_a_real_op", x=1)
'''})
    out = wire.handler_schema_findings(ctx)
    # the other HANDLER_FILES are absent from the fixture ctx: flagged as
    # missing (a renamed control-plane module must not silently drop out)
    assert [f.key for f in out if f.path == "ray_tpu/core/cluster.py"] == \
        ["callsite:zz_not_a_real_op"]
    assert all(f.key == "missing-module" for f in out
               if f.path != "ray_tpu/core/cluster.py")


def test_blob_zero_copy_flags_packing_blob_path():
    ctx = FakeCtx({
        "ray_tpu/core/rpc/peer.py": '''
def _send_blob(self, reply_to, view):
    frame = packb(view)
    self._sock.sendmsg([frame])

def _read_blob(self, n):
    return self._recv_exact_into(n)
''',
        "ray_tpu/core/object_plane.py": '''
def _h_chunk_raw(self, peer, msg):
    return RawReply(bytes(self._view))
'''})
    keys = {f.key for f in wire.blob_zero_copy_findings(ctx)}
    assert "packs:_send_blob:packb" in keys
    assert "copies:_h_chunk_raw:bytes" in keys


def test_dag_loop_rule_flags_control_plane_traffic():
    ctx = FakeCtx({"ray_tpu/dag/exec_loop.py": '''
from ray_tpu.core.rpc import peer

def run_plan(plan, chans):
    for ch in chans:
        ch.write(peer.call("dag_ch_write"))
'''})
    keys = {f.key for f in wire.dag_loop_findings(ctx)}
    assert "call:call" in keys
    assert "import:ray_tpu.core.rpc" in keys


def test_hot_path_purity_flags_construct_and_missing_plumbing():
    ctx = FakeCtx({"ray_tpu/serve/kv_transport.py": '''
def publish(self, pages):
    c = Counter("kv_pages", "")
    c.inc()

def pull(self, desc):
    return self._client.fetch(desc)
'''})
    out = hotpath.hot_path_findings(
        ctx, files={"ray_tpu/serve/kv_transport.py"})
    keys = {f.key for f in out}
    assert "publish:calls:Counter" in keys
    assert "pull:requires:pull_into|pull_into_or_pull" in keys


def test_hot_path_registry_covers_post_pr8_paths():
    """The satellite: kv_transport publish/pull, streaming map/reduce
    bodies, and timeline phase stamping are DECLARED in the one registry,
    not bespoke checks."""
    declared = {spec.file for spec in hotpath.HOT_PATHS}
    assert {"ray_tpu/serve/kv_transport.py", "ray_tpu/data/streaming.py",
            "ray_tpu/data/exchange.py", "ray_tpu/util/timeline.py",
            "ray_tpu/core/process_pool.py", "ray_tpu/dag/exec_loop.py",
            "ray_tpu/core/rpc/peer.py",
            "ray_tpu/core/object_plane.py"} <= declared


def test_engine_step_is_a_declared_hot_path():
    """ISSUE 24: the paged engine's decode step, its admission and the phase
    clock they run are declared, so a later PR cannot put an instrument
    lookup or an RPC into the step."""
    by_file = {spec.file: spec for spec in hotpath.HOT_PATHS}
    engine = by_file["ray_tpu/serve/llm_paged.py"]
    assert {"_step_decode", "_enqueue", "_emit", "_admit_one"} <= set(engine.funcs)
    assert engine.ban_metric_construct and engine.ban_rpc
    assert {"mark", "stop", "close", "clock", "rest"} <= set(
        by_file["ray_tpu/util/timeline.py"].funcs)
    assert "stream_cell" in by_file["ray_tpu/serve/stream_cell.py"].funcs
    ctx = FakeCtx({"ray_tpu/serve/llm_paged.py": '''
def _decode_clock(self, phases):
    yield self._records.clock("decode", phases)

def _step_decode(self):
    with self._decode_clock(()) as clock:
        self._head.notify("decode_step")
        self._emit(self._enqueue(None), clock)

def _enqueue(self, before):
    return self._decode(self.params, self.pool)

def _emit(self, flight, clock):
    stamp()

def _admit_one(self, prompt, max_new, fut, t_enq, tq, rid, slot):
    get_metric("admissions").inc()
'''})
    keys = {f.key for f in hotpath.hot_path_findings(
        ctx, files={"ray_tpu/serve/llm_paged.py"})}
    assert keys == {"_step_decode:calls:notify", "_admit_one:calls:get_metric",
                    "_admit_one:requires:clock"}


def test_reactor_blocking_handler_fixture():
    from ray_tpu.core.rpc import schema

    assert not schema.REGISTRY["ping"].blocking
    blocking_op = next(n for n, s in sorted(schema.REGISTRY.items())
                       if s.blocking)
    src = f'''
class CP:
    def _handlers(self):
        return {{"ping": self._h_ping, "{blocking_op}": self._h_b}}

    def _h_ping(self, peer, msg):
        return self._fut.result()

    def _h_b(self, peer, msg):
        return self._fut.result()   # schema'd blocking: dedicated thread
'''
    ctx = FakeCtx({"ray_tpu/core/cluster.py": src})
    out = concurrency.reactor_blocking_findings(ctx)
    assert [f.key for f in out] == ["ping:result"]


# ================================================== full pass + the shim

def test_full_pass_exits_clean_within_budget():
    """Tier-1 CI: the whole analyzer over the shipped tree — exit 0
    (baseline-aware), no baseline corruption, inside the 15s budget."""
    t0 = time.monotonic()
    report = run_pass(root=REPO)
    elapsed = time.monotonic() - t0
    assert report.baseline_errors == []
    assert report.findings == [], \
        "new findings:\n" + "\n".join(f.render() for f in report.findings)
    assert report.exit_code() == 0
    assert report.files_scanned > 100
    assert elapsed < 15.0, f"lint pass took {elapsed:.1f}s (budget 15s)"


def test_rule_subset_selection_and_unknown_rule():
    report = run_pass(root=REPO, rule_names={"lock-order"},
                      use_baseline=False)
    assert report.rules_run == 1
    with pytest.raises(ValueError, match="unknown rule"):
        run_pass(root=REPO, rule_names={"not-a-rule"})


def test_rule_subset_does_not_report_other_rules_debt_as_stale():
    """A --rules pass must leave unselected rules' baseline entries alone
    (they are neither stale nor prunable from a partial view)."""
    report = run_pass(root=REPO, rule_names={"thread-hygiene"})
    assert report.exit_code() == 0
    assert report.stale_entries == []


def test_check_wire_schemas_shim_verdicts():
    """The shim keeps its import surface: every old check_* returns [] on
    the shipped tree and run_all() prints OK without raising."""
    import importlib.util

    spec_ = importlib.util.spec_from_file_location(
        "check_wire_schemas_shim",
        os.path.join(REPO, "scripts", "check_wire_schemas.py"))
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    for name in ("check_registry", "check_handlers_have_schemas",
                 "check_no_pickle_in_rpc", "check_blob_zero_copy",
                 "check_dag_loop_steady_state",
                 "check_hot_path_instruments", "check_elastic_ops",
                 "check_kv_transport", "check_data_streaming_hot_path",
                 "check_profiler_op", "check_phase_stamp_hot_path"):
        assert getattr(mod, name)() == [], name
    assert mod.SCHEMA_BASELINE["hello"] == 1
    mod.run_all()  # raises SystemExit(1) on violation
