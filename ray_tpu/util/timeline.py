"""Cluster timeline: one Chrome/Perfetto trace for a whole session.

The consumption layer over the PR-8 telemetry plane (ISSUE 13). Two halves:

**Recording (every process).** Worker exec paths stamp per-task PHASE clocks
(received -> args-deserialized -> exec -> outputs-stored, monotonic reads,
``stamp_task_phases``) and subsystems record coarse windows (sampled
compiled-graph steps, whole plane pulls, ``record_span``; one entry per
serving-engine decode step and admission, ``PhaseClock``) into one bounded
in-process ring. The stamping path is bind-only by contract — a list append
under one small lock, no instrument construction/lookup, no RPC — pinned by
``scripts/check_wire_schemas.py::check_phase_stamp_hot_path`` exactly like
the dag exec loop. Entries ride the EXISTING v5 ``metrics_push`` notify
(``phases`` field, inbound-tolerant: old heads drop it) with the same
advance-cursor-only-on-success contract as flight events.

**Merging (the head).** ``export()`` folds every signal the session has into
ONE Chrome-trace JSON array: worker task phases (local + pushed), head-side
task state transitions, tracing spans, sampled dag exec-loop steps, plane
pull windows, flight-recorder instants and gang transitions — process lanes
= nodes, thread lanes = worker pids / stable actor lanes, flow arrows from
the head RUNNING dispatch to the worker's exec window, and cross-node
timestamps re-based onto the head clock via per-node offsets estimated from
heartbeat-borne wall-clock samples (max-filter: one-way delay biases every
sample DOWN, so the largest recent sample is the closest to the true
offset). Reference analog: ``ray timeline`` over the GCS task manager's
aggregated task events + worker profile events (SURVEY §5.1), grown to the
whole-cluster Perfetto view.

Served by ``ray_tpu.util.state.timeline()``, ``GET /api/v0/timeline``, and
``python scripts/timeline.py``.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Optional

# ------------------------------------------------------------- recording ring
# One bounded ring per process. Entry shapes (msgpack-native lists — they
# cross the wire inside metrics_push):
#   ["phase", seq, task_hex, pid, recv_w, args_w, exec0_w, exec1_w,
#    stored_w, status]
#   ["span",  seq, cat, name, pid, t0_w, dur_s, args|None]
# All *_w stamps are WALL seconds: stamped monotonic, converted once at
# append time via the process anchor (monotonic clocks are not comparable
# across processes; wall clocks are re-based per NODE at export).
MAX_EVENTS = int(os.environ.get("RAY_TPU_TIMELINE_EVENTS", "8192"))

_lock = threading.Lock()
_ring: deque = deque(maxlen=MAX_EVENTS)
_seq = itertools.count(1)
_PID = os.getpid()
# wall = monotonic + anchor for THIS process (one-time clock pair read)
_MONO_ANCHOR = time.time() - time.monotonic()
# env-gated so the phase-stamping A/B (MICROBENCH round 12) can switch the
# whole recording path off; checked per stamp as one module-global load
_ENABLED = os.environ.get("RAY_TPU_TASK_PHASES", "1") != "0"


def phase_reply(t_recv: float, t_args: float, t_exec1: float,
                t_stored: float) -> "list | None":
    """Worker half of phase stamping: convert the exec path's monotonic
    reads to wall seconds with the precomputed process anchor and return
    the 4-float clock list that rides the EXISTING done reply on the pool
    pipe (received -> args-deserialized -> exec-end -> outputs-stored;
    exec starts at args-deserialized). Bind-only: four float adds, no
    lock, no instruments, no RPC — pinned by check_phase_stamp_hot_path.
    Returns None when phase recording is off (the A/B switch)."""
    if not _ENABLED:
        return None
    a = _MONO_ANCHOR
    return [t_recv + a, t_args + a, t_exec1 + a, t_stored + a]


def stamp_task_phases(task_bin: "bytes | None", worker_pid: int, clocks,
                      status) -> None:
    """Pool-parent half: append one completed execution's phase record to
    THIS process's ring (``clocks`` = the worker's ``phase_reply`` list,
    already wall seconds on this machine's clock — pool workers are local
    children). The parent is the head driver or the node agent, both of
    which already push metrics — so worker phases ship without any worker
    dialing the control plane. One list append under the ring lock."""
    if not _ENABLED or not clocks or len(clocks) < 4:
        return
    entry = ["phase", next(_seq),
             task_bin.hex() if task_bin else None, worker_pid,
             clocks[0], clocks[1], clocks[1], clocks[2], clocks[3],
             status if isinstance(status, str) else "err"]
    with _lock:
        _ring.append(entry)


def record_span(cat: str, name: str, t0_wall: float, dur_s: float,
                args: "dict | None" = None) -> None:
    """A coarse timeline window (sampled dag step, whole plane pull, one
    engine decode step or admission): recorded at subsystem-chosen
    granularity, a few entries a second at most (a decode step is a 5 Hz
    event), NEVER per frame, per token or per task."""
    if not _ENABLED:
        return
    entry = ["span", next(_seq), cat, name, _PID, t0_wall, dur_s, args]
    with _lock:
        _ring.append(entry)


_annotation = None  # jax.profiler.TraceAnnotation, looked up at first use


def profiling() -> bool:
    """True while a profiler session is on in this process: what `profiled`
    is made of. False where no `PhaseClock` was ever opened (no record could
    carry what a stage would count, so the stage counts nothing)."""
    return _annotation is not None and _annotation.is_enabled()


class PhaseClock:
    """Clocks one step of a loop through its phases and leaves ONE
    ``record_span`` entry for it: ``args`` holds ``<phase>_s`` for every
    declared phase (0.0 for one never reached), whatever ``note`` (as the step
    learns it; a later value replaces an earlier one) and ``close`` are given,
    and ``profiled``. The phases tile the record: each ``mark`` ends the
    running phase and starts the next on the same ``time.monotonic()`` read,
    and ``stop`` ends the last one on the read that ends the record.

    The same intervals go to the profiler's clock as
    ``jax.profiler.TraceAnnotation``s, ``<cat>:<name>`` around
    ``<cat>:<name>.<phase>``, which cost a fraction of a microsecond while
    no profiler session is on. ``profiled`` is true only if one was on both
    when the clock opened and when it closed: such records are exactly those
    of the interval a device trace covers. jax is imported at first use, so
    this module still imports without it.

    A clock that opens under a profiler session also reads
    ``time.thread_time()`` beside each wall read (only then: the read is a
    system call, and only profiled records are read for it): its record, if
    it declares phases, holds ``<phase>_cpu`` for each and ``cpu`` for the
    whole, seconds of THIS thread's CPU (none ends in ``_s``: those keys are
    the parts of ``dur_s``). Wall less CPU of a phase that makes no blocking
    call is time the thread was runnable and not running: the wait for the
    interpreter lock, or a call that blocked after all.

    A clock opened by a ``PhaseLoop`` (``loop``) also notes ``turn``, and
    ``turn_cpu`` under a session: see there."""

    __slots__ = ("_c", "_c0", "_cat", "_cpu", "_inner", "_key", "_loop",
                 "_name", "_noted", "_outer", "_phases", "_profiled", "_t",
                 "_t1", "t0")

    def __init__(self, cat: str, name: str, phases: tuple = (),
                 loop: "PhaseLoop | None" = None):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._cat, self._name, self._loop = cat, name, loop
        self._phases = {p + "_s": 0.0 for p in phases}
        self._noted: dict = {}
        self._inner = self._t1 = None
        self._profiled = _annotation.is_enabled()
        self._cpu = {p + "_cpu": 0.0 for p in phases} if self._profiled else None
        after = loop is not None and loop._turn is not None
        if after:
            loop._turn.__exit__(None, None, None)
            loop._turn = None
        self._outer = _annotation(f"{cat}:{name}")
        self._outer.__enter__()
        self.t0 = self._t = time.monotonic()
        self._c0 = self._c = time.thread_time() if self._profiled else None
        if after:
            self._noted["turn"] = self.t0 - loop._t
            if self._profiled and loop._c is not None:
                self._noted["turn_cpu"] = self._c0 - loop._c
        if phases:
            self._open(phases[0])

    def _open(self, phase: str) -> None:
        self._key = phase
        self._inner = _annotation(f"{self._cat}:{self._name}.{phase}")
        self._inner.__enter__()

    def _end_phase(self, t: float) -> "float | None":
        """Ends the running phase on the wall read `t`; returns the CPU read
        that goes with it (None outside a session)."""
        c = time.thread_time() if self._profiled else None
        if self._inner is not None:
            self._inner.__exit__(None, None, None)
            self._inner = None
            wall = self._key + "_s"
            self._phases[wall] = self._phases.get(wall, 0.0) + (t - self._t)
            if c is not None:
                cpu = self._key + "_cpu"
                self._cpu[cpu] = self._cpu.get(cpu, 0.0) + (c - self._c)
            self._t, self._c = t, c
        return c

    def mark(self, phase: str) -> float:
        """Ends the running phase and starts `phase`; returns the
        ``time.monotonic()`` read on which it did both."""
        t = time.monotonic()
        self._end_phase(t)
        self._open(phase)
        return t

    def note(self, **args) -> None:
        self._noted.update(args)

    def stop(self) -> None:
        """The record ends HERE: the last phase and the record's annotation
        end on this read, and a loop's next turn begins on it. What the
        caller does between `stop` and `close` (adding up what it wants
        noted) is outside ``dur_s`` and inside that turn."""
        t = self._t1 = time.monotonic()
        c = self._end_phase(t)
        self._outer.__exit__(None, None, None)
        if self._cpu is not None and self._phases:   # no phases, no CPU noted
            self._cpu["cpu"] = c - self._c0
        if self._loop is not None:
            self._loop._closed(t, c)

    def close(self, **args) -> None:
        if self._t1 is None:
            self.stop()
        args = {**self._noted, **args, **self._phases}
        if self._cpu is not None and self._phases:
            args.update(self._cpu)
        args["profiled"] = self._profiled and _annotation.is_enabled()
        record_span(self._cat, self._name, self.t0 + _MONO_ANCHOR,
                    self._t1 - self.t0, args)


class PhaseLoop:
    """The records of ONE thread's loop, in a row, so that the thread's time
    is tiled: from a record's end to the opening of the next, while the
    loop stays busy, the time is a TURN (the loop's own code between two
    records, and whatever kept the thread from running it). `clock()` opens
    the next record, which notes the turn that ends there as ``turn``
    (seconds, outside its ``dur_s``) and, where both records read the
    thread's CPU, ``turn_cpu``; the same interval is a ``<cat>:turn``
    annotation on the profiler's clock. Both ends are reads the two records
    make anyway. A turn makes no call into any runtime, so its wall less its
    CPU is the interpreter lock's and the scheduler's alone. `rest()`, called
    when the loop finds nothing to do, ends the turn under way unrecorded."""

    __slots__ = ("_c", "_cat", "_t", "_turn")

    def __init__(self, cat: str):
        self._cat = cat
        self._turn = None   # the annotation of the turn under way

    def clock(self, name: str, phases: tuple = ()) -> PhaseClock:
        return PhaseClock(self._cat, name, phases, loop=self)

    def _closed(self, t: float, c: "float | None") -> None:
        self._t, self._c = t, c
        self._turn = _annotation(f"{self._cat}:turn")
        self._turn.__enter__()

    def rest(self) -> None:
        if self._turn is not None:
            self._turn.__exit__(None, None, None)
            self._turn = None


def drain_since(cursor: int) -> "tuple[list, int]":
    """Entries newer than ``cursor`` + the new cursor — the metrics_push
    incremental ship loop (same contract as flight_recorder.drain_since:
    the caller advances the cursor only after a successful push)."""
    out = []
    with _lock:
        for e in _ring:
            if e[1] > cursor:
                out.append(e)
    return out, (out[-1][1] if out else cursor)


def local_events() -> list:
    with _lock:
        return list(_ring)


def clear() -> None:
    with _lock:
        _ring.clear()


# ------------------------------------------------------- head-side ingestion
# Pushed entries keyed by origin (node_hex, source); bounded so a chatty
# fleet cannot grow the head without bound.
MAX_REMOTE_EVENTS = int(os.environ.get("RAY_TPU_TIMELINE_REMOTE_EVENTS",
                                       "65536"))
_remote_lock = threading.Lock()
_remote: deque = deque(maxlen=MAX_REMOTE_EVENTS)


def _sane_event(e) -> bool:
    if not isinstance(e, (list, tuple)):
        return False
    if e and e[0] == "phase":
        return (len(e) >= 10
                and all(isinstance(v, (int, float)) for v in e[4:9]))
    if e and e[0] == "span":
        # 8 slots minimum: _ring_event_rows unpacks e[:8] — a short entry
        # admitted here would fail EVERY later export, not just this one
        return (len(e) >= 8
                and isinstance(e[2], str) and isinstance(e[3], str)
                and isinstance(e[5], (int, float))
                and isinstance(e[6], (int, float)))
    return False


def ingest_remote(node_hex: str, source: str, events) -> None:
    """Head side: fold one process's pushed timeline entries in, tagged with
    the origin node (shape-sanitized — one buggy pusher degrades to missing
    lanes, never to an export crash)."""
    if not isinstance(events, (list, tuple)):
        return
    with _remote_lock:
        for e in events:
            if _sane_event(e):
                _remote.append((str(node_hex), str(source), list(e)))


def remote_events() -> list:
    with _remote_lock:
        return list(_remote)


# Note: a dead node's already-ingested entries are deliberately KEPT (the
# bounded deque ages them out) — a timeline is a post-mortem artifact, and
# a restarted node registers under a fresh NodeID/lane anyway.


# ------------------------------------------------------------- clock offsets
# offset[node] estimates (node_wall - head_wall). Every heartbeat-borne
# sample is remote_send_wall - head_recv_wall = offset - one_way_delay,
# i.e. biased DOWN by the (non-negative) network+queue delay — so the MAX
# of a recent window is the closest sample to the true offset (the classic
# one-way min-delay filter). Same-host agents sample ~0.
_CLOCK_WINDOW = 32
_clock_lock = threading.Lock()
_clock_samples: dict[str, deque] = {}


def note_clock_sample(node_hex: str, remote_wall: float,
                      local_wall: "float | None" = None) -> None:
    sample = float(remote_wall) - (local_wall if local_wall is not None
                                   else time.time())
    with _clock_lock:
        ring = _clock_samples.get(node_hex)
        if ring is None:
            ring = _clock_samples[node_hex] = deque(maxlen=_CLOCK_WINDOW)
        ring.append(sample)


def clock_offset(node_hex: str) -> float:
    """Best current estimate of ``node_wall - head_wall`` (0.0 unknown)."""
    with _clock_lock:
        ring = _clock_samples.get(node_hex)
        return max(ring) if ring else 0.0


def clock_offsets() -> dict:
    with _clock_lock:
        return {k: max(v) for k, v in _clock_samples.items() if v}


# ------------------------------------------------------------------- export
_NODE_LANE_BASE = 10     # remote node process lanes start here (1 = head,
#                          2 = legacy export-pipeline worker_exec lanes)
_SPAN_LANE_BASE = 200    # span thread lanes on the head process lane
_HEAD_PID = 1
_EXPORT_PID = 2


def _us(ts: float) -> int:
    return int(ts * 1e6)


def _node_lanes(node_hexes) -> dict:
    """Stable process-lane ids: head is pid 1; remote nodes take 10+i in
    sorted order (deterministic across exports and processes — the
    satellite fix for the per-process hash-salted lanes)."""
    lanes = {"head": _HEAD_PID, None: _HEAD_PID, "": _HEAD_PID}
    for i, nh in enumerate(sorted({h for h in node_hexes
                                   if h and h != "head"})):
        lanes[nh] = _NODE_LANE_BASE + i
    return lanes


def _head_transition_events(events: list, trace: list,
                            exec_flow: dict) -> None:
    """Head-observed state transitions -> complete X slices per task, with
    STABLE per-actor/task thread lanes, and open ``ph:"B"`` spans for tasks
    whose terminal event was evicted from the bounded buffer (previously
    silently dropped)."""
    # stable lane ids: sorted distinct lane keys -> 1..N (not hash-salted)
    lane_keys = sorted({ev.get("actor_id") or "tasks" for ev in events})
    lane_of = {k: i + 1 for i, k in enumerate(lane_keys)}
    starts: dict[str, dict] = {}
    for ev in events:
        tid_key = ev.get("actor_id") or "tasks"
        task_id = ev["task_id"]
        if ev["state"] == "RUNNING":
            starts[task_id] = ev
            exec_flow.setdefault(task_id, {})["submit_ts"] = ev["ts"]
        elif ev["state"] in ("FINISHED", "FAILED", "CANCELLED"):
            st = starts.pop(task_id, None)
            if st is None:
                continue
            trace.append({
                "name": ev["name"], "cat": "task", "ph": "X",
                "ts": _us(st["ts"]),
                "dur": max(0, _us(ev["ts"]) - _us(st["ts"])),
                "pid": _HEAD_PID, "tid": lane_of[tid_key],
                "args": {"state": ev["state"], "task_id": task_id},
            })
            exec_flow.setdefault(task_id, {})["end_ts"] = ev["ts"]
    # unpaired RUNNING: the task is live (or its terminal event was evicted)
    # — surface an open span instead of dropping it
    for task_id, st in starts.items():
        trace.append({
            "name": st["name"], "cat": "task", "ph": "B",
            "ts": _us(st["ts"]), "pid": _HEAD_PID,
            "tid": lane_of[st.get("actor_id") or "tasks"],
            "args": {"state": "RUNNING", "task_id": task_id},
        })


def _ring_event_rows(trace: list, exec_flow: dict, lanes: dict) -> None:
    """Local + pushed ring entries -> task_phase slices and subsystem spans,
    remote wall clocks re-based onto the head clock via the node offset."""
    rows = [("head", "local", e) for e in local_events()]
    rows.extend(remote_events())
    offsets = clock_offsets()
    for node_hex, _source, e in rows:
        off = offsets.get(node_hex, 0.0) if node_hex != "head" else 0.0
        pid_lane = lanes.get(node_hex)
        if pid_lane is None:  # client:<host> rows — give them a lane too
            pid_lane = lanes[node_hex] = (_NODE_LANE_BASE
                                          + len([k for k in lanes
                                                 if k not in ("head", None, "")]))
        if e[0] == "phase":
            _kind, _seq, task_hex, wpid, t_recv, t_args, t0, t1, t_store, \
                status = e[:10]
            short = (task_hex or "?")[:12]
            base = {"cat": "task_phase", "ph": "X", "pid": pid_lane,
                    "tid": wpid}
            for name, a, b in (("deser:" + short, t_recv, t_args),
                               ("exec:" + short, t0, t1),
                               ("store:" + short, t1, t_store)):
                trace.append({**base, "name": name, "ts": _us(a - off),
                              "dur": max(0, _us(b - off) - _us(a - off)),
                              "args": {"status": status,
                                       "node": node_hex, "worker_pid": wpid}})
            if task_hex:
                flow = exec_flow.setdefault(task_hex, {})
                flow["exec_ts"] = t0 - off
                flow["exec_pid"] = pid_lane
                flow["exec_tid"] = wpid
        else:  # span
            _kind, _seq, cat, name, wpid, t0, dur, args = e[:8]
            trace.append({
                "name": name, "cat": cat, "ph": "X", "ts": _us(t0 - off),
                "dur": max(0, int(dur * 1e6)),
                "pid": pid_lane, "tid": wpid,
                "args": {**(args if isinstance(args, dict) else {}),
                         "node": node_hex},
            })


def _span_events(trace: list) -> None:
    from ray_tpu.util import tracing

    lane_of: dict[str, int] = {}
    for s in sorted(tracing.spans(), key=lambda s: s.trace_id):
        tid = lane_of.setdefault(s.trace_id,
                                 _SPAN_LANE_BASE + len(lane_of))
        trace.append({
            "name": s.name, "cat": "span", "ph": "X",
            "ts": s.start_ns // 1000,
            "dur": max(0, (s.end_ns - s.start_ns) // 1000),
            "pid": _HEAD_PID, "tid": tid,
            "args": {**s.attributes, "status": s.status,
                     "trace_id": s.trace_id},
        })


def _flight_events(trace: list, lanes: dict) -> None:
    from ray_tpu.util import flight_recorder

    for ev in flight_recorder.records(limit=10000):
        sub = ev.get("subsystem", "?")
        node = ev.get("node_id") or "head"
        trace.append({
            "name": f"{sub}:{ev.get('event', '?')}",
            "cat": "gang" if sub == "gang" else "flight",
            "ph": "i", "s": "g", "ts": _us(ev["ts"]),
            "pid": lanes.get(node, _HEAD_PID), "tid": 0,
            "args": {k: v for k, v in ev.items()
                     if k not in ("seq", "ts")},
        })


def _export_pipeline_events(trace: list) -> None:
    """Worker-side execution windows from the export-event pipeline (when
    export events are on): the legacy ``worker_exec`` lanes on pid 2 —
    kept verbatim for consumers of the pre-ISSUE-13 shape."""
    import glob
    import json

    from ray_tpu._private import export_events
    from ray_tpu.core.runtime import get_runtime_or_none

    profile_dir = None
    rt = get_runtime_or_none()
    session_dir = getattr(rt, "session_dir", None)
    if session_dir is not None:
        profile_dir = os.path.join(session_dir, "export_events")
    elif export_events.enabled() and export_events._DIR is not None:
        profile_dir = export_events._DIR
    if profile_dir is None:
        return
    try:
        for p in glob.glob(os.path.join(profile_dir,
                                        "export_task_profile*.jsonl")):
            with open(p) as f:
                for line in f:
                    try:
                        ev = json.loads(line)["event_data"]
                    except (ValueError, KeyError):
                        continue
                    trace.append({
                        "name": f"exec:{(ev.get('task_id') or '?')[:12]}",
                        "cat": "worker_exec", "ph": "X",
                        "ts": _us(ev["exec_start"]),
                        "dur": max(0, _us(ev["exec_end"])
                                   - _us(ev["exec_start"])),
                        "pid": _EXPORT_PID,
                        "tid": ev.get("worker_pid") or 0,
                        "args": {"status": ev.get("status")},
                    })
    except OSError:
        pass


def _flow_arrows(trace: list, exec_flow: dict) -> None:
    """submit -> exec flow arrows: one ``s``/``f`` pair per task that has
    BOTH a head-side RUNNING dispatch and a worker-side exec window."""
    for task_hex, flow in exec_flow.items():
        if "submit_ts" not in flow or "exec_ts" not in flow:
            continue
        common = {"cat": "flow", "name": "submit", "id": task_hex[:16]}
        trace.append({**common, "ph": "s", "ts": _us(flow["submit_ts"]),
                      "pid": _HEAD_PID, "tid": 0})
        trace.append({**common, "ph": "f", "bp": "e",
                      "ts": _us(flow["exec_ts"]),
                      "pid": flow["exec_pid"], "tid": flow["exec_tid"]})


def _lane_metadata(trace: list, lanes: dict) -> None:
    names = {_HEAD_PID: "head (control plane)",
             _EXPORT_PID: "workers (export pipeline)"}
    for nh, pid in lanes.items():
        if nh not in ("head", None, "") and pid not in names:
            names[pid] = f"node {nh[:12]}"
    for pid, name in sorted(names.items()):
        # "cat" present on every event (consumers index by it freely)
        trace.append({"name": "process_name", "cat": "meta", "ph": "M",
                      "pid": pid, "tid": 0, "args": {"name": name}})


def export(path: Optional[str] = None) -> list[dict]:
    """The whole session as one Chrome/Perfetto trace (JSON array of trace
    events). Load in ``ui.perfetto.dev`` or ``chrome://tracing``."""
    from ray_tpu.core.runtime import get_runtime_or_none

    rt = get_runtime_or_none()
    events = rt.task_events() if hasattr(rt, "task_events") else []

    import sys as _sys

    trace: list[dict] = []
    exec_flow: dict[str, dict] = {}
    node_hexes = [t[0] for t in remote_events()]
    # nodes known only through store-occupancy samples (memory anatomy)
    # still deserve their own named lane for the counter track
    _mem = _sys.modules.get("ray_tpu.core.mem_anatomy")
    if _mem is not None:
        try:
            node_hexes += list(_mem.occupancy_nodes())
        except Exception:
            pass
    lanes = _node_lanes(node_hexes)

    _head_transition_events(events, trace, exec_flow)
    _span_events(trace)
    _ring_event_rows(trace, exec_flow, lanes)
    _flight_events(trace, lanes)
    _export_pipeline_events(trace)
    _flow_arrows(trace, exec_flow)
    _lane_metadata(trace, lanes)
    try:
        # serve request lanes + ingress->prefill->decode flow arrows
        # (serve/anatomy.py, ISSUE 16) — already offset-aligned via this
        # module's clock_offsets; lazy so non-serve sessions never import
        # the serve package here
        _an = _sys.modules.get("ray_tpu.serve.anatomy")
        if _an is not None:
            trace.extend(_an.trace_events())
    except Exception:
        pass  # a malformed ledger must not break the whole export
    try:
        # per-node plane-store occupancy counter tracks (memory anatomy,
        # ISSUE 18): samples carry head wall stamps from ingest time, so
        # they need no cross-node offset alignment
        if _mem is not None:
            trace.extend(_mem.trace_counter_events(
                lambda nh: lanes.get(nh, _HEAD_PID)))
    except Exception:
        pass
    trace.sort(key=lambda e: e.get("ts", 0))
    if path:
        import json

        with open(path, "w") as f:
            json.dump(trace, f)
    return trace
