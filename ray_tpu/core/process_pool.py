"""Process worker pool: pipelined OS-process task execution with crash FT.

Transport note: the parent<->worker pipes here are the intra-node DATA plane
between processes of one build (parent spawns the child, so versions match
by construction) — cloudpickle frames are the designed opaque-payload path.
Workers' CONTROL-plane traffic (nested submit/get/put against the head)
goes through client_runtime over the schema'd msgpack wire in core/rpc/.

This is the multi-process half of the execution story (the reference's model:
N `default_worker.py` processes per node, each embedding a CoreWorker —
python/ray/_private/workers/default_worker.py:203 + raylet WorkerPool
worker_pool.h:284). Tasks opted into process isolation run in exec'd workers:

- function/args travel by cloudpickle over a pipe; LARGE results come back
  through the node's shared-memory store (the worker maps the same segment —
  zero-copy handoff, like plasma), small results inline over the pipe.
- submission is PIPELINED: requests are seq-tagged and pushed to the
  least-loaded worker without waiting for earlier replies (the reference's
  lease-reuse + PushNormalTask pipeline, normal_task_submitter.cc:515 — many
  tasks in flight per leased worker, replies matched by id). A per-worker
  parent reader thread completes futures as `done` replies arrive.
- a worker that announces it is BLOCKED in a nested get releases its queued
  (not-yet-started) tasks back to the pool: the parent sends `cancel` for
  them; the worker's reader thread answers `skipped` for any it had not
  started, and those are resubmitted to other workers. This keeps nested
  task graphs deadlock-free without spawning a worker per blocked task.
- a worker crash (segfault/exit/kill) fails every in-flight future with
  WorkerCrashedError — a system failure the runtime's retry machinery
  handles, giving real worker-death fault tolerance.
- workers are reused across tasks (lease reuse economics) and respawned on
  death (WorkerPool PopWorker semantics).

Wire protocol (parent -> worker):
  ("run", seq, oid_bin, fn_blob, args_blob, task_bin)      seq-tagged task
  ("run_gen", seq, task_bin, fn_blob, args_blob, bp)       streaming generator task
  ("actor_call2", seq, method, args_blob, oid_bin)         seq-tagged actor call
                                                           (async methods overlap
                                                           on the worker's loop)
  ("actor_gen", seq, method, args_blob, task_bin, bp)      generator actor method
  ("ack", seq, consumed)                go-ahead: consumer progress for a stream
  ("cancel", seq)                       yank if unstarted; abort a stream
  ("actor_init", cls, args, renv)       dedicated actors (unnumbered reply)
  ("dag_install", seq, plan_blob, chan_names)  compiled-graph resident loop:
                                          attach the named shm channels and
                                          drive the actor through the static
                                          plan until they close
                                          (dag/exec_loop.py)
  ("exit",)
Worker -> parent:
  ("ready",)                            boot handshake
  ("start", seq)                        executor began the task (running-set upkeep)
  ("item", seq, index, status, payload, extra)  one generator yield
   ("done", seq, status, payload, extra[, contained[, phase_clocks]])
                                        status: "val" | "shm" | "err" | "gen_end";
                                        phase_clocks: wall [recv, args, exec_end,
                                        stored] for the cluster timeline
                                        (util/timeline.phase_reply)
  ("skipped", seq)                      cancel won; parent resubmits elsewhere
  ("badreq", None)                      undecodable frame: parent kills + respawns
  ("dag", seq, "ok"/"err", payload[, exc])  dag_install ack
  3-tuple (status, payload, extra)      actor_init reply (unnumbered)
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time
import traceback
from concurrent.futures import Future
from dataclasses import dataclass, field
from multiprocessing.connection import Connection
from typing import Any, Callable, Optional

import cloudpickle

from ray_tpu.exceptions import ActorError, TaskCancelledError
from ray_tpu.util import timeline as _timeline


class WorkerCrashedError(ActorError):
    """The worker process died while executing the task (system failure —
    retryable by default, matching the reference's max_retries semantics)."""


@dataclass
class ShmArg:
    """Marker for a task argument living in the node's shared-memory store:
    the worker resolves it zero-copy from the segment instead of the value
    traveling over the pipe (the reference passes plasma object ids in task
    specs the same way — args by reference, doc task-lifecycle.rst)."""

    oid_bin: bytes


def resolve_shm_args(args, kwargs, store, fetch=None):
    """Replace top-level ShmArg markers with their deserialized values."""
    from ray_tpu._private import serialization
    from ray_tpu._private.ids import ObjectID

    def conv(a):
        if isinstance(a, ShmArg):
            view = store.get_bytes(ObjectID(a.oid_bin)) if store is not None else None
            if view is None:
                if fetch is not None:
                    return fetch(a.oid_bin)
                raise WorkerCrashedError(
                    f"shm arg {a.oid_bin.hex()[:12]} missing in worker store"
                )
            return serialization.deserialize_from_bytes(view)
        return a

    return tuple(conv(a) for a in args), {k: conv(v) for k, v in kwargs.items()}


def _emit_profile_event(task_bin, exec_t0: float, status) -> None:
    """Worker-side profile event (reference: the TaskEventBuffer's
    worker-recorded profile events batched to the GCS —
    task_event_buffer.h:305): the WORKER's own wall-clock execution window,
    distinct from the head's dispatch-side RUNNING/FINISHED stamps, written
    to the session's export pipeline. Config-gated and line-buffered —
    effectively free when export events are off."""
    try:
        from ray_tpu._private import export_events

        if not export_events.enabled():
            return
        export_events.emit("task_profile", {
            "task_id": task_bin.hex() if task_bin else None,
            "worker_pid": os.getpid(),
            "exec_start": exec_t0,
            "exec_end": time.time(),
            "status": status if isinstance(status, str) else "err",
        })
    except Exception:
        pass


def worker_env() -> dict:
    """Child env for session-spawned processes (workers, node agents).

    Workers are pinned to the CPU: a chip belongs to one process, and by
    default that process is the driver. RAY_TPU_WORKER_TPU=1 in the driver's
    environment leaves jax's platform choice alone in EVERY worker of the
    pool at once, so it only suits a driver that stays off jax with one
    device-holding worker (README, "Running on the chip")."""
    env = dict(os.environ)
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [env.get("PYTHONPATH"), pkg_root]))
    if env.get("RAY_TPU_WORKER_TPU") != "1":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _set_current_task(task_bin: bytes | None) -> None:
    """Tag the worker's client runtime with the executing task id so nested
    get/wait can tell the head which task is blocking (resource release)."""
    from ray_tpu.core import runtime as rt_mod

    rt = rt_mod.get_runtime_or_none()
    if rt is not None:
        try:
            rt._current_task = task_bin
        except Exception:
            pass


def _client_fetch(oid_bin: bytes):
    """Fetch a missing arg through the head (only when a client runtime is
    installed in this worker; otherwise raises)."""
    from ray_tpu.core import runtime as rt_mod
    from ray_tpu.core.object_ref import ObjectRef
    from ray_tpu._private.ids import ObjectID

    rt = rt_mod.get_runtime_or_none()
    if rt is None:
        raise WorkerCrashedError(f"shm arg {oid_bin.hex()[:12]} missing and no head link")
    return rt.get([ObjectRef(ObjectID(oid_bin), rt)])[0]


def _worker_main(conn, shm_name: str | None, shm_size: int) -> None:
    """Child: a reader thread drains the pipe (so `cancel` is honored even
    while a task blocks); the main thread executes requests in arrival order."""
    store = None
    if shm_name:
        try:
            from ray_tpu.core.shm_store import SharedMemoryStore

            store = SharedMemoryStore(shm_name, size=shm_size)
        except Exception:
            store = None
    from ray_tpu._private import serialization

    reply_mu = threading.Lock()

    def _reply(payload) -> None:
        try:
            blob = cloudpickle.dumps(payload)
            with reply_mu:
                conn.send_bytes(blob)
        except (BrokenPipeError, OSError):
            # parent (driver or node agent) died: exit quietly; the head's
            # failure machinery re-runs the task elsewhere
            os._exit(0)

    def _result_payload(result, oid_bin):
        """Serialize a result: large through shm (zero-copy handoff), small
        inline over the pipe. Returns (status, payload, extra, contained) —
        `contained` lists binary ids of ObjectRefs serialized inside the
        blob, so the head can hold them while the blob lives (the head never
        deserializes shm results; without the report, the refs inside would
        dangle once this worker's borrows drop)."""
        import inspect as _inspect

        from ray_tpu.core.object_ref import collect_serialized_refs

        if _inspect.iscoroutine(result) or _inspect.isgenerator(result):
            result.close()
            raise TypeError(
                "async/generator results are not supported in worker processes"
            )
        with collect_serialized_refs() as contained:
            blob = serialization.serialize_to_bytes(result)
        if store is not None and len(blob) > 100 * 1024 and oid_bin is not None:
            from ray_tpu._private.ids import ObjectID

            try:
                store.put_bytes(ObjectID(oid_bin), blob)
                return ("shm", oid_bin, len(blob), contained)
            except Exception:
                pass  # store full/unreadable: fall back to the pipe
        return ("val", blob, len(blob), contained)

    def _error_payload(e: BaseException):
        try:
            exc_blob = cloudpickle.dumps(e)
        except Exception:
            exc_blob = None
        return ("err", traceback.format_exc(), exc_blob)

    def _maybe_post_mortem(e: BaseException) -> None:
        """RAY_TPU_POST_MORTEM=1 parks failing tasks (plain AND streaming)
        in the remote debugger before the error reply ships."""
        if os.environ.get("RAY_TPU_POST_MORTEM") == "1":
            try:
                from ray_tpu.util import rpdb

                rpdb.maybe_post_mortem(e)
            except Exception:
                pass

    import collections

    pending: "collections.deque" = collections.deque()
    pend_cv = threading.Condition()
    cancelled: set[int] = set()     # guarded by pend_cv's lock
    active_seqs: set[int] = set()   # popped-for-execution, not yet replied done
    gen_consumed: dict[int, int] = {}  # seq -> consumer's acked count (backpressure)
    _SEQ_TAGGED = ("run", "run_gen", "actor_call2", "actor_gen")
    _reply(("ready",))  # boot handshake: the pool gates growth/rebalance on it

    def _pipe_reader() -> None:
        """Drains the pipe so `cancel`/`ack` are honored even while a task
        blocks: a cancel for a STILL-QUEUED task removes it here and answers
        `skipped` immediately (the executor may be wedged in a nested get —
        it can never be relied on to process the yank); acks feed streaming
        generators' consumed-count backpressure."""
        while True:
            try:
                msg = conn.recv_bytes()
            except (EOFError, OSError):
                os._exit(0)
            try:
                req = cloudpickle.loads(msg)
            except Exception:
                # Protocol desync: the parent kills + respawns this worker on
                # seeing badreq (futures fail as WorkerCrashedError and retry).
                _reply(("badreq", None))
                continue
            if req[0] == "ack":
                with pend_cv:
                    gen_consumed[req[1]] = max(gen_consumed.get(req[1], 0), req[2])
                    pend_cv.notify_all()
                continue
            if req[0] == "cancel":
                seq = req[1]
                # Frames are ordered on the pipe, so a cancel whose task is no
                # longer queued means the task already STARTED. A migrate
                # cancel (pool rebalance / blocked-yank) must then lose — only
                # a user cancel may abort running work (streams poll the
                # cancelled set per item). Without the reason tag, a migrate
                # cancel racing the async `start` reply aborted a running
                # stream as CANCELLED though nobody asked (advisor r3).
                reason = req[2] if len(req) > 2 else "user"
                removed = False
                with pend_cv:
                    for i, r in enumerate(pending):
                        if r[0] in _SEQ_TAGGED and r[1] == seq:
                            del pending[i]
                            removed = True
                            break
                    # `run` always precedes `cancel` on the pipe, so a seq
                    # that is neither queued nor executing has RETIRED — a
                    # cancel for it is stale (e.g. a user frame chasing a
                    # migrate frame that already won) and must not enter the
                    # cancelled set, where nothing would ever consume it.
                    if not removed and reason == "user" and seq in active_seqs:
                        cancelled.add(seq)
                        pend_cv.notify_all()  # wake a paused generator
                if removed:
                    _reply(("skipped", seq))
                continue
            with pend_cv:
                pending.append(req)
                pend_cv.notify()

    threading.Thread(target=_pipe_reader, daemon=True, name="pipe-reader").start()

    def _anatomy_pusher() -> None:
        """Serve-anatomy uplink (ISSUE 16): a pool worker owns no head peer
        (the client runtime only piggybacks LIVE connections), so request
        phase stamps ride the reply pipe on the metrics beat — the same
        route as phase_reply — and the pool parent, which does run a push
        loop, re-homes them into its own ring (anatomy.adopt)."""
        import sys as _sys

        period = float(os.environ.get("RAY_TPU_METRICS_PUSH_PERIOD_S", "2")
                       or 2)
        if period <= 0:
            return
        cursor = 0
        while True:
            time.sleep(period)
            an = _sys.modules.get("ray_tpu.serve.anatomy")
            if an is None:
                continue  # this worker never loaded the serve stack
            try:
                entries, cursor = an.drain_since(cursor)
                if entries:
                    _reply(("serve_phases", entries))
            except Exception as e:  # telemetry never takes a worker down
                from ray_tpu.util import flight_recorder

                flight_recorder.record("serve", "anatomy_uplink_error",
                                       error=str(e)[:200])

    threading.Thread(target=_anatomy_pusher, daemon=True,
                     name="serve-anatomy-push").start()

    def _check_skip(seq: int) -> bool:
        with pend_cv:
            if seq in cancelled:
                cancelled.discard(seq)
                active_seqs.discard(seq)
                return True
        return False

    def _retire(seq: int) -> None:
        """The seq replied its terminal frame (done/skipped): late cancels for
        it are stale from here on, and any cancelled-set entry added while it
        ran was never consumed — drop both so neither set grows unbounded."""
        with pend_cv:
            active_seqs.discard(seq)
            cancelled.discard(seq)

    def _decode_call(args_blob):
        args, kwargs = serialization.deserialize_from_bytes(args_blob)
        return resolve_shm_args(args, kwargs, store, fetch=_client_fetch)

    def _item_oid(task_bin: bytes, index: int) -> bytes:
        from ray_tpu._private.ids import ObjectID, TaskID

        return ObjectID.for_task_return(TaskID(task_bin), index + 1).binary()

    def _stream_out(seq: int, task_bin: bytes, gen, backpressure: int) -> None:
        """Drive a (sync) generator, shipping each item as an `item` reply.
        Consumed-count backpressure: pause while produced - acked >= window
        (reference: generator_waiter.h:58 TotalNumObjectConsumed wait)."""
        index = 0
        for item in gen:
            status, payload, extra, contained = _result_payload(
                item, _item_oid(task_bin, index) if task_bin else None
            )
            _reply(("item", seq, index, status, payload, extra, contained))
            index += 1
            if backpressure > 0:
                with pend_cv:
                    while (seq not in cancelled
                           and index - gen_consumed.get(seq, 0) >= backpressure):
                        pend_cv.wait(0.5)
            with pend_cv:
                was_cancelled = seq in cancelled
                cancelled.discard(seq)
            if was_cancelled:
                # user code (finally blocks) runs OUTSIDE the worker lock:
                # the pipe reader must keep serving other streams' acks
                gen.close()
                raise TaskCancelledError("stream cancelled")
        _reply(("done", seq, "gen_end", index, None))

    async def _astream_out(seq: int, task_bin: bytes, agen, backpressure: int) -> None:
        """Async-generator variant of _stream_out (runs on the actor loop)."""
        import asyncio

        index = 0
        async for item in agen:
            status, payload, extra, contained = _result_payload(
                item, _item_oid(task_bin, index) if task_bin else None
            )
            _reply(("item", seq, index, status, payload, extra, contained))
            index += 1
            while True:
                with pend_cv:  # never await under this lock: aclose()/sleep
                    was_cancelled = seq in cancelled  # happen outside so the
                    cancelled.discard(seq)            # loop + reader can't freeze
                    window_open = (backpressure <= 0
                                   or index - gen_consumed.get(seq, 0) < backpressure)
                if was_cancelled:
                    await agen.aclose()
                    raise TaskCancelledError("stream cancelled")
                if window_open:
                    break
                await asyncio.sleep(0.02)
        _reply(("done", seq, "gen_end", index, None))

    # Dedicated-actor mode: ("actor_init", cls_blob, args_blob, renv)
    # instantiates the user class IN THIS PROCESS (runtime_env applied for the
    # actor's lifetime); subsequent calls invoke methods on the held instance
    # (reference: actors live in their own worker process, task_receiver.cc).
    # Async actor methods run CONCURRENTLY on a dedicated asyncio loop thread —
    # seq-tagged `actor_call2` replies arrive out of order as calls finish.
    actor_instance = None
    actor_env_stack = None  # noqa: F841 - held so the env outlives __init__
    actor_loop = None
    actor_pool = None  # sync-method thread pool when max_concurrency > 1
    # serializes compiled-graph loop steps with direct sync dispatch
    # (max_concurrency=1 actors keep sequential semantics while a graph
    # loop runs in this process; see dag/exec_loop.py step_lock)
    actor_step_mutex = threading.Lock()
    # graph_id -> channel objects installed loops hold (dag_close cascade)
    dag_channels_by_graph: dict = {}
    actor_group_pools: dict = {}  # named concurrency group -> its own pool
    # (reference: concurrency_group_manager.cc runs sync calls on a pool of
    # max_concurrency threads inside the worker; user code owns its locking)

    def _ensure_loop():
        import asyncio

        nonlocal actor_loop
        if actor_loop is None:
            actor_loop = asyncio.new_event_loop()
            threading.Thread(
                target=actor_loop.run_forever, daemon=True, name="actor-loop"
            ).start()
        return actor_loop

    exec_starts: dict = {}  # seq -> (wall start, id_bin) for profile events

    def _note_start(seq: int, id_bin) -> None:
        exec_starts[seq] = (time.time(), id_bin)

    def _profile_done(seq: int, status) -> None:
        started = exec_starts.pop(seq, None)
        if started is not None:
            _emit_profile_event(started[1], started[0], status)

    def _finish_call(seq: int, result, oid_bin) -> None:
        contained = None
        try:
            status, payload, extra, contained = _result_payload(result, oid_bin)
        except BaseException as e:  # noqa: BLE001
            status, payload, extra = _error_payload(e)
        _profile_done(seq, status)
        _reply(("done", seq, status, payload, extra, contained))
        _retire(seq)

    def _finish_err(seq: int, e: BaseException) -> None:
        status, payload, extra = _error_payload(e)
        _profile_done(seq, status)
        _reply(("done", seq, status, payload, extra))
        _retire(seq)

    while True:
        with pend_cv:
            while not pending:
                pend_cv.wait()
            req = pending.popleft()
            if req[0] in _SEQ_TAGGED:
                # mark executing atomically with the dequeue: a cancel frame
                # must find the seq in exactly one of {pending, active}
                active_seqs.add(req[1])
        kind = req[0]
        if kind == "exit":
            os._exit(0)
        if kind == "actor_init":
            try:
                cls = cloudpickle.loads(req[1])
                args, kwargs = _decode_call(req[2])
                renv = req[3] if len(req) > 3 else None
                mc = req[4] if len(req) > 4 else 1
                groups = req[5] if len(req) > 5 else None
                if mc > 1 or groups:
                    from concurrent.futures import ThreadPoolExecutor

                    actor_pool = ThreadPoolExecutor(
                        max_workers=max(mc, 1), thread_name_prefix="actor-sync")
                    # one pool per named concurrency group: a slow method in
                    # one group never exhausts another group's threads
                    # (reference: concurrency_group_manager.cc per-group pools)
                    for gname, limit in (groups or {}).items():
                        actor_group_pools[gname] = ThreadPoolExecutor(
                            max_workers=max(int(limit), 1),
                            thread_name_prefix=f"actor-{gname}")
                if renv:
                    import contextlib

                    from ray_tpu import runtime_env as renv_mod

                    actor_env_stack = contextlib.ExitStack()
                    actor_env_stack.enter_context(
                        renv_mod.apply_context(renv_mod.build_context(renv))
                    )
                actor_instance = cls(*args, **kwargs)
                _reply(("ok", None, None))
            except BaseException as e:  # noqa: BLE001
                _reply(_error_payload(e))
            continue
        if kind == "dag_close":
            # the head/agent cascading a graph abort: close THIS worker's
            # channel mappings so its resident loop wakes with
            # ChannelClosed — rings hosted by a DEAD node were already
            # unlinked, so only mapping holders can flip the closed flag
            for ch in dag_channels_by_graph.pop(req[1], ()):
                try:
                    ch.close_channel()
                except Exception as e:
                    print(f"worker: dag_close channel failed: {e!r}",
                          flush=True)
            continue
        if kind == "dag_install":
            # ("dag_install", seq, plan_blob, chan_names): attach the
            # compiled graph's shm channels and run the static schedule on a
            # resident thread — zero pipe/RPC traffic per step from here on.
            dag_seq = req[1]
            try:
                if actor_instance is None:
                    raise RuntimeError("dag_install before actor_init")
                from ray_tpu.core.shm_channel import ShmChannel
                from ray_tpu.dag import exec_loop

                plan = cloudpickle.loads(req[2])
                graph_id = req[4] if len(req) > 4 else b""
                # channel descriptors: a str is a node-local ring attached
                # by name; an ["addr", kind] pair is a CROSS-NODE edge
                # bridged through a pre-opened fabric peer (wire v9 —
                # dag/fabric.py; kind "read": this actor consumes a ring
                # hosted on the producer's node)
                chans = {}
                for cid, desc in req[3].items():
                    if isinstance(desc, str):
                        chans[cid] = ShmChannel(name=desc, create=False)
                    else:
                        from ray_tpu.dag import fabric

                        chans[cid] = fabric.build_edge(desc, graph_id, cid)
                dag_channels_by_graph.setdefault(graph_id, []).extend(
                    chans.values())
                threading.Thread(
                    target=exec_loop.run_plan,
                    args=(actor_instance, plan, chans),
                    # the step mutex is skipped for mc>1 actors — they
                    # opted into concurrent execution (pool path)
                    kwargs={"detach_on_exit": True,
                            "step_lock": (actor_step_mutex
                                          if actor_pool is None else None)},
                    daemon=True, name="actor-dag-loop",
                ).start()
                _reply(("dag", dag_seq, "ok", None))
            except BaseException as e:  # noqa: BLE001
                status, payload, extra = _error_payload(e)
                _reply(("dag", dag_seq, "err", payload, extra))
            continue
        if kind == "actor_call2":
            # ("actor_call2", seq, method, args_blob, oid_bin[, group])
            _, seq, method_name, args_blob, oid_bin = req[:5]
            call_group = req[5] if len(req) > 5 else None
            if _check_skip(seq):
                _reply(("skipped", seq))
                continue
            _reply(("start", seq))
            # return oid = task_id(24B) + index: record the TASK id so
            # profile events join against task state events
            _note_start(seq, oid_bin[:24] if oid_bin else None)
            try:
                if actor_instance is None:
                    raise RuntimeError("actor_call before actor_init")
                method = getattr(actor_instance, method_name)
                args, kwargs = _decode_call(args_blob)
                import inspect as _inspect

                if _inspect.iscoroutinefunction(method):
                    # concurrent: executor moves on; the loop replies on finish
                    async def _run_async(m=method, a=args, kw=kwargs, s=seq, ob=oid_bin):
                        try:
                            result = await m(*a, **kw)
                        except BaseException as e:  # noqa: BLE001
                            _finish_err(s, e)
                            return
                        _finish_call(s, result, ob)

                    import asyncio

                    asyncio.run_coroutine_threadsafe(_run_async(), _ensure_loop())
                elif actor_pool is not None or call_group is not None:
                    # sync method on the (group's) pool: the executor moves
                    # on, replies arrive out of order as calls finish (same
                    # contract as async methods — the parent matches by seq)
                    def _run_pooled(m=method, a=args, kw=kwargs, s=seq, ob=oid_bin):
                        try:
                            result = m(*a, **kw)
                        except BaseException as e:  # noqa: BLE001
                            _finish_err(s, e)
                            return
                        _finish_call(s, result, ob)

                    pool_for = actor_group_pools.get(call_group) or actor_pool
                    if pool_for is None:
                        _run_pooled()
                    else:
                        pool_for.submit(_run_pooled)
                else:
                    with actor_step_mutex:
                        result = method(*args, **kwargs)
                    _finish_call(seq, result, oid_bin)
            except BaseException as e:  # noqa: BLE001
                _finish_err(seq, e)
            continue
        if kind == "actor_gen":
            # ("actor_gen", seq, method, args_blob, task_bin, bp[, group])
            _, seq, method_name, args_blob, task_bin, bp = req[:6]
            gen_group = req[6] if len(req) > 6 else None
            if _check_skip(seq):
                _reply(("skipped", seq))
                continue
            _reply(("start", seq))
            _note_start(seq, task_bin)
            try:
                if actor_instance is None:
                    raise RuntimeError("actor_gen before actor_init")
                method = getattr(actor_instance, method_name)
                args, kwargs = _decode_call(args_blob)
                import inspect as _inspect

                if _inspect.isasyncgenfunction(method):
                    async def _run_agen(m=method, a=args, kw=kwargs, s=seq,
                                        tb=task_bin, b=bp):
                        gen_status = "gen_end"
                        try:
                            await _astream_out(s, tb, m(*a, **kw), b)
                        except BaseException as e:  # noqa: BLE001
                            status, payload, extra = _error_payload(e)
                            gen_status = status
                            _reply(("done", s, status, payload, extra))
                        finally:
                            _profile_done(s, gen_status)
                            # cleaned on the LOOP at stream end — the executor
                            # popping it early would reset live backpressure
                            # counts and leak re-added entries
                            with pend_cv:
                                gen_consumed.pop(s, None)
                            _retire(s)

                    import asyncio

                    asyncio.run_coroutine_threadsafe(_run_agen(), _ensure_loop())
                else:
                    def _run_sync_gen(m=method, a=args, kw=kwargs, s=seq,
                                      tb=task_bin, b=bp):
                        gen_status = "gen_end"
                        try:
                            try:
                                if actor_pool is None:
                                    # max_concurrency=1: generator iteration
                                    # mutates actor state — serialize with
                                    # any installed compiled-graph loop
                                    with actor_step_mutex:
                                        _stream_out(s, tb, m(*a, **kw), b)
                                else:
                                    _stream_out(s, tb, m(*a, **kw), b)
                            finally:
                                with pend_cv:
                                    gen_consumed.pop(s, None)
                                _retire(s)
                        except BaseException as e:  # noqa: BLE001
                            status, payload, extra = _error_payload(e)
                            gen_status = status
                            _reply(("done", s, status, payload, extra))
                            _retire(s)
                        finally:
                            _profile_done(s, gen_status)

                    # a GROUPED streaming method runs on its group's pool so
                    # a long-lived stream never wedges the executor loop that
                    # dispatches every other group (_stream_out only touches
                    # pend_cv-guarded state + the locked _reply — thread-safe)
                    gp = actor_group_pools.get(gen_group)
                    if gp is not None:
                        gp.submit(_run_sync_gen)
                    else:
                        _run_sync_gen()
            except BaseException as e:  # noqa: BLE001
                status, payload, extra = _error_payload(e)
                _reply(("done", seq, status, payload, extra))
                _retire(seq)
            continue
        if kind == "run_gen":
            # ("run_gen", seq, task_bin, fn_blob, args_blob, backpressure)
            _, seq, task_bin, fn_blob, args_blob, bp = req
            if _check_skip(seq):
                _reply(("skipped", seq))
                continue
            _reply(("start", seq))
            _set_current_task(task_bin)
            gen_t0 = time.time()
            gen_status = "gen_end"
            try:
                fn = cloudpickle.loads(fn_blob)
                args, kwargs = _decode_call(args_blob)
                _stream_out(seq, task_bin, fn(*args, **kwargs), bp)
            except BaseException as e:  # noqa: BLE001
                if not isinstance(e, TaskCancelledError):
                    _maybe_post_mortem(e)
                status, payload, extra = _error_payload(e)
                gen_status = status
                _reply(("done", seq, status, payload, extra))
            finally:
                _set_current_task(None)
                _emit_profile_event(task_bin, gen_t0, gen_status)
                with pend_cv:
                    gen_consumed.pop(seq, None)
                _retire(seq)
            continue
        # ("run", seq, oid_bin, fn_blob, args_blob, task_bin[, trace])
        _, seq, oid_bin, fn_blob, args_blob, task_bin = req[:6]
        trace_ctx = req[6] if len(req) > 6 else None
        if _check_skip(seq):
            _reply(("skipped", seq))
            continue
        _reply(("start", seq))
        _set_current_task(task_bin)
        contained = None
        exec_t0 = time.time()
        # Task phase clocks (ISSUE 13 timeline): received (dequeued) ->
        # args-deserialized -> exec -> outputs-stored. Monotonic reads here;
        # the wall-converted clocks ride the done reply (phase_reply, pinned
        # RPC- and instrument-free by check_phase_stamp_hot_path) and the
        # pool PARENT — head driver or node agent, both already metric
        # pushers — stamps them into its timeline ring.
        t_recv = t_args = t_exec1 = time.monotonic()
        try:
            fn = cloudpickle.loads(fn_blob)
            args, kwargs = _decode_call(args_blob)
            t_args = t_exec1 = time.monotonic()
            if trace_ctx:
                # worker-side execute span joins the driver's submit trace
                # (the propagated context IS the opt-in — recorded to this
                # process's buffer and OTLP sink when configured)
                from ray_tpu.util import tracing as _tracing

                with _tracing.span(
                        "worker_exec::" + (task_bin.hex()[:12]
                                           if task_bin else "task"),
                        {"worker_pid": os.getpid()},
                        parent_ctx=tuple(trace_ctx)):
                    result = fn(*args, **kwargs)
            else:
                result = fn(*args, **kwargs)
            t_exec1 = time.monotonic()
            status, payload, extra, contained = _result_payload(
                result, oid_bin)
        except BaseException as e:  # noqa: BLE001
            _maybe_post_mortem(e)
            status, payload, extra = _error_payload(e)
        finally:
            _set_current_task(None)
            _emit_profile_event(task_bin, exec_t0, status)
        _reply(("done", seq, status, payload, extra, contained,
                _timeline.phase_reply(t_recv, t_args, t_exec1,
                                      time.monotonic())))
        _retire(seq)


class _Inflight:
    """One submitted task: its future, the marshalled request (kept so a
    `skipped` reply can resubmit it verbatim elsewhere), and flags.

    kind: "run" (plain task) or "gen" (streaming generator — `item` replies
    stream through on_item before the terminal `done`)."""

    __slots__ = ("future", "oid_bin", "fn_blob", "args_blob", "task_bin",
                 "started", "cancel_sent", "cancel_reason", "worker",
                 "submit_ts", "user_cancelled", "kind", "on_item",
                 "backpressure", "seq", "trace")

    def __init__(self, fn_blob, args_blob, oid_bin, task_bin, kind="run",
                 on_item=None, backpressure=0, trace=None):
        self.future: Future = Future()
        self.fn_blob = fn_blob
        self.args_blob = args_blob
        self.oid_bin = oid_bin
        self.task_bin = task_bin
        self.started = False
        self.cancel_sent = False
        self.cancel_reason: str | None = None  # "migrate" | "user"
        self.worker: "_Worker | None" = None
        self.submit_ts = 0.0
        self.user_cancelled = False  # skipped -> cancelled, not resubmitted
        self.kind = kind
        self.on_item = on_item      # gen: callback(index, status, payload, extra)
        self.backpressure = backpressure
        self.seq: int | None = None
        self.trace = trace  # [trace_id, parent_span_id] from the submitter

    def ack(self, consumed: int) -> None:
        """Tell the producing worker the consumer has read `consumed` items
        (releases the generator's backpressure window)."""
        w, seq = self.worker, self.seq
        if w is not None and seq is not None and not self.future.done():
            try:
                w.send_frame(("ack", seq, consumed))
            except (BrokenPipeError, OSError):
                pass


@dataclass
class _Worker:
    proc: subprocess.Popen
    conn: Any
    next_seq: int = 0
    inflight: dict = field(default_factory=dict)  # seq -> _Inflight
    blocked: bool = False   # announced blocked-in-get; don't queue more
    dead: bool = False
    ready: bool = False     # boot handshake received
    last_done_ts: float = 0.0  # last completed/skipped task (progress signal)
    # Connection.send_bytes writes header+body as separate syscalls for big
    # frames; concurrent senders (dispatcher, monitor, control plane) would
    # interleave and desync the worker's stream without this.
    send_mu: threading.Lock = field(default_factory=threading.Lock)

    def send_frame(self, payload) -> None:
        blob = cloudpickle.dumps(payload)
        with self.send_mu:
            self.conn.send_bytes(blob)

    def send_frame_locked(self, payload) -> None:
        """Send with send_mu ALREADY HELD by the caller (ordered-handoff
        pattern: acquire send_mu under the pool lock, write after releasing
        it — frame order is pinned without blocking pipe I/O under the
        pool-global lock)."""
        self.conn.send_bytes(cloudpickle.dumps(payload))

    def is_alive(self) -> bool:
        """Authoritative liveness (monitor / slow paths): includes an OS
        poll to catch a process that died without its pipe EOF being seen."""
        return not self.dead and self.proc.poll() is None

    def is_alive_fast(self) -> bool:
        """Flag-only liveness for the SUBMISSION hot path. proc.poll() is a
        waitpid syscall — at per-task frequency it was ~75% of dispatch time
        (the round-4 microbench regression). The reply reader flips `dead`
        on pipe EOF within the same tick; the tiny race window (send to a
        just-died worker) is already covered by WorkerCrashedError
        migration/retry."""
        return not self.dead

    @property
    def load(self) -> int:
        return len(self.inflight)


def spawn_worker_process(shm_name, shm_size, head_addr, token, log_base=None):
    """Exec a fresh worker (default_worker.py analog); returns (Popen, Connection)."""
    parent_s, child_s = socket.socketpair()
    cmd = [
        sys.executable, "-m", "ray_tpu.core.worker_main",
        "--fd", str(child_s.fileno()),
    ]
    if shm_name:
        cmd += ["--shm-name", shm_name, "--shm-size", str(shm_size)]
    if head_addr:
        cmd += ["--head", head_addr]
        if token:
            cmd += ["--token", token]
    stdout = stderr = None
    if log_base:
        # per-worker log files tailed back to the driver (reference:
        # _private/log_monitor.py log_to_driver plumbing)
        os.makedirs(os.path.dirname(log_base), exist_ok=True)
        stdout = open(log_base + ".out", "ab", buffering=0)
        stderr = open(log_base + ".err", "ab", buffering=0)
    proc = subprocess.Popen(
        cmd, pass_fds=(child_s.fileno(),), close_fds=True, env=worker_env(),
        stdout=stdout, stderr=stderr,
    )
    if stdout is not None:
        stdout.close()
        stderr.close()
    child_s.close()
    return proc, Connection(parent_s.detach())


class _ActorCall:
    """One in-flight dedicated-actor call (seq-matched by the reader)."""

    __slots__ = ("future", "on_item", "worker", "seq")

    def __init__(self, on_item=None):
        self.future: Future = Future()
        self.on_item = on_item
        self.worker = None
        self.seq: int | None = None

    def ack(self, consumed: int) -> None:
        w = self.worker
        if w is not None and self.seq is not None and not self.future.done():
            try:
                w._send(("ack", self.seq, consumed))
            except (BrokenPipeError, OSError):
                pass


class DedicatedActorWorker:
    """One exec'd process hosting one actor instance (reference: every actor
    lives in its own worker process; task_receiver.cc execution).

    Calls are seq-tagged (`actor_call2`/`actor_gen`) with a parent reader
    matching replies — async actor methods execute CONCURRENTLY on the
    worker's asyncio loop and reply out of order; generator methods stream
    `item` replies with consumed-count backpressure."""

    def __init__(self, shm_name=None, shm_size=0, head_addr=None, token=None,
                 log_base=None):
        self.proc, self.conn = spawn_worker_process(
            shm_name, shm_size, head_addr, token, log_base
        )
        self._send_mu = threading.Lock()
        self._mu = threading.Lock()
        self._calls: dict[int, _ActorCall] = {}
        self._init_fut: Future | None = None
        self._dag_futs: dict[int, Future] = {}  # seq-tagged install acks
        self._seq = 0
        self._dead = False
        threading.Thread(target=self._reader, daemon=True,
                         name=f"actor-reader-{self.proc.pid}").start()

    @property
    def pid(self) -> int:
        return self.proc.pid

    def is_alive(self) -> bool:
        return self.proc.poll() is None

    def _send(self, payload) -> None:
        blob = cloudpickle.dumps(payload)
        with self._send_mu:
            self.conn.send_bytes(blob)

    def _fail_all(self, exc: BaseException) -> None:
        with self._mu:
            self._dead = True
            calls, self._calls = list(self._calls.values()), {}
            init_fut, self._init_fut = self._init_fut, None
            dag_futs, self._dag_futs = list(self._dag_futs.values()), {}
        for c in calls:
            if not c.future.done():
                c.future.set_exception(exc)
        for fut in [init_fut] + dag_futs:
            if fut is not None and not fut.done():
                fut.set_exception(exc)

    def _reader(self) -> None:
        while True:
            try:
                resp = cloudpickle.loads(self.conn.recv_bytes())
            except (EOFError, OSError, BrokenPipeError, TypeError, ValueError) as e:
                # TypeError/ValueError: connection closed under us (teardown)
                self._fail_all(WorkerCrashedError(
                    f"actor worker process died ({type(e).__name__})"))
                return
            except Exception:
                resp = ("badreq", None)
            tag = resp[0]
            if tag == "ready" or tag == "start":
                continue
            if tag == "badreq":
                # protocol desync: untrustworthy stream — kill so the
                # actor-restart machinery runs
                self.kill()
                self._fail_all(WorkerCrashedError(
                    "actor worker protocol desync (badreq)"))
                return
            if tag == "item":
                seq, index, status, payload, extra = resp[1:6]
                contained = resp[6] if len(resp) > 6 else None
                with self._mu:
                    call = self._calls.get(seq)
                if call is not None and call.on_item is not None:
                    try:
                        call.on_item(index, status, payload, extra, contained)
                    except Exception as e:
                        with self._mu:
                            self._calls.pop(seq, None)
                        try:
                            self._send(("cancel", seq))
                        except (BrokenPipeError, OSError):
                            pass
                        if not call.future.done():
                            call.future.set_exception(e)
                continue
            if tag == "done" or tag == "skipped":
                if tag == "skipped":
                    with self._mu:
                        call = self._calls.pop(resp[1], None)
                    if call is not None and not call.future.done():
                        call.future.set_exception(TaskCancelledError("cancelled"))
                    continue
                seq, status, payload, extra = resp[1], resp[2], resp[3], resp[4]
                contained = resp[5] if len(resp) > 5 else None
                with self._mu:
                    call = self._calls.pop(seq, None)
                if call is None:
                    continue
                if status == "err":
                    call.future.set_exception(
                        _RemoteTaskError(payload, exc_blob=extra))
                else:
                    call.future.set_result((status, payload, extra, contained))
                continue
            if tag == "dag":
                # compiled-graph install ack: ("dag", seq, "ok"/"err",
                # payload[, exc]) — seq-tagged so concurrent installs on
                # one actor pair each ack with ITS request
                with self._mu:
                    fut = self._dag_futs.pop(resp[1], None)
                if fut is not None and not fut.done():
                    if resp[2] == "err":
                        fut.set_exception(
                            _RemoteTaskError(resp[3], exc_blob=resp[4]
                                             if len(resp) > 4 else None))
                    else:
                        fut.set_result(None)
                continue
            # unnumbered 3-tuple: actor_init reply
            if self._init_fut is not None:
                status, payload, extra = resp
                fut, self._init_fut = self._init_fut, None
                if status == "err":
                    fut.set_exception(_RemoteTaskError(payload, exc_blob=extra))
                else:
                    fut.set_result(None)

    def init_actor(self, cls, args_blob: bytes, runtime_env: dict | None = None,
                   max_concurrency: int = 1,
                   concurrency_groups: dict | None = None) -> None:
        self.init_actor_blob(cloudpickle.dumps(cls), args_blob,
                             runtime_env=runtime_env,
                             max_concurrency=max_concurrency,
                             concurrency_groups=concurrency_groups)

    def init_actor_blob(self, cls_blob: bytes, args_blob: bytes,
                        runtime_env: dict | None = None,
                        max_concurrency: int = 1,
                        concurrency_groups: dict | None = None) -> None:
        """Init from an already-pickled class: a node agent relaying a
        head-shipped actor_spawn forwards the blob verbatim — user code
        deserializes only inside the worker, never in the agent."""
        with self._mu:
            if self._dead:
                raise WorkerCrashedError("actor worker process died")
            fut = self._init_fut = Future()
        try:
            self._send(("actor_init", cls_blob, args_blob,
                        runtime_env, max_concurrency, concurrency_groups))
        except (BrokenPipeError, OSError) as e:
            raise WorkerCrashedError("actor worker process died") from e
        fut.result()

    def dag_close(self, graph_id: bytes) -> None:
        """Cascade a graph abort into the worker: it closes its own channel
        mappings (no ack — the loop's ChannelClosed exit is the effect)."""
        try:
            self._send(("dag_close", graph_id))
        except (BrokenPipeError, OSError):
            pass  # worker already dead: nothing left to wake

    def dag_install(self, plan_blob: bytes, chan_names: dict,
                    graph_id: bytes = b"") -> None:
        """Install a compiled-graph resident loop in the worker process: it
        attaches the named shm channels (cross-node edges arrive as
        ["addr", kind] fabric descriptors instead of names) and drives the
        actor instance through the static plan until the channels close
        (dag/exec_loop.py). Blocks until the worker acks the attach (or
        reports the error)."""
        with self._mu:
            if self._dead:
                raise WorkerCrashedError("actor worker process died")
            seq = self._seq
            self._seq += 1
            fut = self._dag_futs[seq] = Future()
        try:
            self._send(("dag_install", seq, plan_blob, dict(chan_names),
                        graph_id))
        except (BrokenPipeError, OSError) as e:
            with self._mu:
                self._dag_futs.pop(seq, None)
            raise WorkerCrashedError("actor worker process died") from e
        try:
            fut.result(timeout=30)
        finally:
            with self._mu:
                self._dag_futs.pop(seq, None)

    def submit_call(self, method_name: str, args_blob: bytes,
                    oid_bin: bytes | None, on_item=None, task_bin: bytes | None = None,
                    backpressure: int = 0, group: str | None = None) -> _ActorCall:
        """Non-blocking seq-tagged call; generator methods pass on_item;
        `group` selects the worker-side concurrency-group pool."""
        call = _ActorCall(on_item=on_item)
        with self._mu:
            if self._dead:
                raise WorkerCrashedError("actor worker process died")
            seq = self._seq
            self._seq += 1
            self._calls[seq] = call
            call.worker = self
            call.seq = seq
        if on_item is not None:
            frame = ("actor_gen", seq, method_name, args_blob, task_bin,
                     backpressure, group)
        else:
            frame = ("actor_call2", seq, method_name, args_blob, oid_bin, group)
        try:
            self._send(frame)
        except (BrokenPipeError, OSError) as e:
            with self._mu:
                self._calls.pop(seq, None)
            raise WorkerCrashedError("actor worker process died") from e
        return call

    def call(self, method_name: str, args_blob: bytes, oid_bin: bytes | None,
             group: str | None = None):
        """Blocking form; raises the remote error / WorkerCrashedError."""
        return self.submit_call(method_name, args_blob, oid_bin,
                                group=group).future.result()

    def kill(self) -> None:
        try:
            os.kill(self.proc.pid, 9)
        except OSError:
            pass

    def shutdown(self) -> None:
        try:
            self._send(("exit",))
        except Exception:
            pass
        try:
            self.proc.wait(timeout=2)
        except subprocess.TimeoutExpired:
            self.proc.terminate()
        try:
            self.conn.close()
        except Exception:
            pass


class ProcessWorkerPool:
    """Parent-side pipelined pool (reference: raylet/worker_pool.cc lease
    semantics + the core worker's pipelined PushNormalTask submission).

    Submission never blocks on a worker roundtrip: tasks are seq-tagged and
    queued onto the least-loaded live worker; a per-worker reader thread
    matches replies to futures. Throughput scales with pipe bandwidth, not
    worker-spawn latency (the old checkout-or-spawn design paid a ~1s Python
    boot for every burst that momentarily saturated the pool)."""

    # Growth cap: demand overflow (tasks blocked in nested gets) spawns extra
    # workers instead of deadlocking — the reference similarly starts new
    # workers while existing ones are blocked (worker_pool.cc PopWorker +
    # blocked-task accounting).
    MAX_WORKERS = int(os.environ.get("RAY_TPU_MAX_PROCESS_WORKERS", "64"))

    def __init__(self, num_workers: int = 2, shm_name: str | None = None,
                 shm_size: int = 0, head_addr: str | None = None,
                 token: str | None = None, log_dir: str | None = None,
                 cgroup_manager=None):
        # Workers are exec'd fresh (python -m ray_tpu.core.worker_main), never
        # forked: the driver runs many threads (dispatcher, actor loops,
        # JAX/XLA) and fork-with-threads can copy locks mid-acquire; fork-based
        # mp start methods also re-prepare the parent's __main__ in the child,
        # which re-executes driver scripts (and breaks stdin drivers). The
        # reference execs default_worker.py for the same reasons
        # (python/ray/_private/workers/default_worker.py:203).
        self._num = num_workers
        self._shm_name = shm_name
        self._shm_size = shm_size
        self._head_addr = head_addr
        self._token = token
        self._log_dir = log_dir
        self._workers: list[_Worker] = []
        self._running_tasks: dict[int, tuple] = {}  # pid -> (task_bin, started)
        self._spawn_seq = 0
        self._shutdown = False
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # optional cgroup2 confinement (reference: cgroup_manager) — workers
        # land in per-worker cgroups with memory.max/cpu.max from config
        self._cgroups = cgroup_manager
        with self._cv:
            for _ in range(num_workers):
                self._spawn_locked()
        threading.Thread(
            target=self._monitor_loop, daemon=True, name="pool-monitor"
        ).start()

    # ---------------------------------------------------------------- monitor
    # Sustained-demand growth + work rebalancing. Short-task bursts pipeline
    # onto live workers (no spawn cost on the submit path); tasks that SIT —
    # every worker loaded for >100ms — indicate long-running work that deserves
    # true process parallelism, so the pool grows one worker per tick. Queued
    # tasks stuck behind a long runner get yanked (cancel protocol) whenever an
    # idle worker could take them.
    MONITOR_TICK_S = 0.05
    SUSTAINED_S = 0.1

    def _monitor_loop(self) -> None:
        while not self._shutdown:
            time.sleep(self.MONITOR_TICK_S)
            try:
                self._monitor_tick(time.monotonic())
            except Exception:  # e.g. Popen EAGAIN under fd pressure — the
                continue       # monitor must survive to try again next tick

    def _monitor_tick(self, now: float) -> None:
        to_cancel: list[tuple[_Worker, int]] = []
        with self._cv:
            live = [w for w in self._workers if w.is_alive()]
            if not live:
                # Total loss (e.g. every respawn failed under fd pressure):
                # rebuild toward the floor rather than staying dead forever.
                if not self._shutdown and self._num > 0:
                    self._spawn_locked()
                return

            def stalled(w: _Worker) -> bool:
                # No completion recently AND work is waiting on it: the
                # current task is long-running or blocked. A worker that is
                # completing tasks is never stalled, however deep its queue
                # — that keeps short-task floods pipelining instead of
                # tripping spawn/migrate churn under CPU contention.
                return (
                    (w.blocked or w.load >= 1)
                    and now - w.last_done_ts > self.SUSTAINED_S
                    and any(now - i.submit_ts > self.SUSTAINED_S
                            for i in w.inflight.values())
                ) or (w.blocked and w.load >= 1)

            idle = [w for w in live if w.ready and w.load == 0 and not w.blocked]
            booting = [w for w in live if not w.ready]
            # Restore the floor: _on_worker_death's respawn can fail under
            # fd/memory pressure (swallowed there so orphan futures still
            # fail) — the monitor re-tries here, one spawn per tick.
            if len(live) < self._num and not booting:
                self._spawn_locked()
            # Grow: every worker is stalled on aged work and nothing is
            # already booting (growth paced by worker boot time, so a
            # stall can never storm-spawn).
            elif (not idle and not booting and len(live) < self.MAX_WORKERS
                    and all(stalled(w) for w in live)):
                self._spawn_locked()
            # Rebalance: stale UNSTARTED tasks on stalled workers migrate
            # to ready idle workers (cancel wins only if unstarted).
            elif idle:
                budget = len(idle)
                for w in live:
                    if budget <= 0:
                        break
                    if w in idle or not stalled(w):
                        continue
                    for seq, i in w.inflight.items():
                        if (not i.started and not i.cancel_sent
                                and now - i.submit_ts > self.SUSTAINED_S):
                            i.cancel_sent = True
                            i.cancel_reason = "migrate"
                            to_cancel.append((w, seq))
                            budget -= 1
                            if budget <= 0:
                                break
        for w, seq in to_cancel:
            try:
                w.send_frame(("cancel", seq, "migrate"))
            except (BrokenPipeError, OSError):
                self._on_worker_death(w)

    # ---------------------------------------------------------------- spawn
    def _spawn_locked(self) -> "_Worker":
        self._spawn_seq += 1
        log_base = None
        if self._log_dir:
            log_base = os.path.join(
                self._log_dir, f"worker-{os.getpid()}-{self._spawn_seq}"
            )
        proc, conn = spawn_worker_process(
            self._shm_name, self._shm_size, self._head_addr, self._token, log_base
        )
        if self._cgroups is not None and self._cgroups.enabled:
            from ray_tpu._private.config import get_config

            cfg = get_config()
            self._cgroups.add_worker(
                f"worker-{proc.pid}", proc.pid,
                memory_bytes=cfg.worker_memory_limit_bytes or None,
                cpu_quota=cfg.worker_cpu_quota or None,
            )
        w = _Worker(proc, conn)
        self._workers.append(w)
        threading.Thread(
            target=self._reply_reader, args=(w,), daemon=True,
            name=f"pool-reader-{proc.pid}",
        ).start()
        return w

    # ---------------------------------------------------------- reply plumbing
    def _reply_reader(self, w: _Worker) -> None:
        """Parent-side reader for one worker: completes futures as replies
        arrive (PushNormalTask reply matching)."""
        while True:
            try:
                msg = w.conn.recv_bytes()
            except (EOFError, OSError, TypeError, ValueError):
                # TypeError/ValueError: connection closed under us (teardown)
                self._on_worker_death(w)
                return
            try:
                resp = cloudpickle.loads(msg)
            except Exception:
                resp = ("badreq", None)
            tag = resp[0]
            if tag == "badreq" or tag not in ("ready", "start", "done",
                                              "skipped", "item",
                                              "serve_phases"):
                # Protocol desync (undecodable frame on either side): this
                # worker's stream can no longer be trusted — kill it; the
                # EOF path fails its in-flight futures as WorkerCrashedError
                # so nothing hangs and the runtime's retries recover.
                try:
                    w.proc.kill()
                except Exception:
                    pass
                continue
            if tag == "ready":
                with self._cv:
                    w.ready = True
                    w.last_done_ts = time.monotonic()
                    self._cv.notify_all()
            elif tag == "start":
                with self._lock:
                    inf = w.inflight.get(resp[1])
                    if inf is not None:
                        inf.started = True
                        self._running_tasks[w.proc.pid] = (inf.task_bin, time.monotonic())
            elif tag == "item":
                # streaming generator item: deliver without completing
                seq, index, status, payload, extra = resp[1:6]
                contained = resp[6] if len(resp) > 6 else None
                with self._lock:
                    inf = w.inflight.get(seq)
                    if inf is not None:
                        w.last_done_ts = time.monotonic()  # progress signal
                if inf is not None and inf.on_item is not None:
                    try:
                        inf.on_item(index, status, payload, extra, contained)
                    except Exception as e:
                        # a dropped item would silently shift every later
                        # index — abort the stream instead (consumer sees the
                        # error; retries replay from the start)
                        with self._cv:
                            w.inflight.pop(seq, None)
                        try:
                            w.send_frame(("cancel", seq))
                        except (BrokenPipeError, OSError):
                            pass
                        if not inf.future.done():
                            inf.future.set_exception(e)
            elif tag == "serve_phases":
                # worker serve-anatomy beat (reply-pipe uplink, like the
                # phase_clocks piggyback): re-home the entries in THIS
                # process's ring — the pool parent (head driver or node
                # agent) runs a metrics push loop, its workers don't
                try:
                    from ray_tpu.serve import anatomy as _anatomy

                    _anatomy.adopt(resp[1])
                except Exception as e:
                    from ray_tpu.util import flight_recorder

                    flight_recorder.record("serve", "anatomy_adopt_error",
                                           error=str(e)[:200])
            elif tag == "done":
                seq, status, payload, extra = resp[1], resp[2], resp[3], resp[4]
                contained = resp[5] if len(resp) > 5 else None
                phase_clocks = resp[6] if len(resp) > 6 else None
                with self._cv:
                    inf = w.inflight.pop(seq, None)
                    cur = self._running_tasks.get(w.proc.pid)
                    if inf is not None and cur is not None and cur[0] == inf.task_bin:
                        self._running_tasks.pop(w.proc.pid, None)
                    # A finished task means the worker is making progress again
                    # (a blocked-in-get task only completes after unblocking).
                    w.blocked = False
                    w.last_done_ts = time.monotonic()
                    self._cv.notify_all()
                if inf is None:
                    continue
                if phase_clocks:
                    # worker phase clocks rode the reply pipe: stamp them
                    # into THIS (pushing) process's timeline ring
                    _timeline.stamp_task_phases(inf.task_bin, w.proc.pid,
                                                phase_clocks, status)
                if status == "err":
                    inf.future.set_exception(_RemoteTaskError(payload, exc_blob=extra))
                else:
                    inf.future.set_result((status, payload, extra, contained))
            elif tag == "skipped":
                with self._cv:
                    inf = w.inflight.pop(resp[1], None)
                    w.last_done_ts = time.monotonic()
                    self._cv.notify_all()
                if inf is not None and inf.user_cancelled:
                    if not inf.future.done():
                        inf.future.set_exception(TaskCancelledError("cancelled"))
                elif inf is not None:
                    # cancel won before the task started: run it elsewhere
                    try:
                        self._submit_inflight(inf)
                    except RuntimeError:  # pool shut down mid-migration
                        if not inf.future.done():
                            inf.future.set_exception(
                                WorkerCrashedError("pool shut down during task migration")
                            )
                        return

    def _on_worker_death(self, w: _Worker) -> None:
        with self._cv:
            if w.dead:
                return
            w.dead = True
            if w in self._workers:
                self._workers.remove(w)
            orphans = list(w.inflight.values())
            w.inflight.clear()
            self._running_tasks.pop(w.proc.pid, None)
            # Respawn to the floor — but never during shutdown. Futures are
            # failed below EITHER way: a blocking execute_blob caller must not
            # hang because teardown raced a worker EOF. Popen can raise
            # (EAGAIN/ENOMEM under pressure); w.dead is already True so this
            # function won't re-enter — swallow and let the monitor restore
            # the floor next tick rather than skip failing the orphans.
            try:
                while (not self._shutdown
                       and sum(1 for x in self._workers if x.is_alive()) < self._num):
                    self._spawn_locked()
            except Exception:
                pass
            self._cv.notify_all()
        err = WorkerCrashedError("worker process died while executing task")
        for inf in orphans:
            if not inf.future.done():
                inf.future.set_exception(err)
        try:
            w.conn.close()
        except Exception:
            pass

    # ------------------------------------------------------------- submission
    def _pick_worker_locked(self) -> _Worker:
        """Least-loaded live worker; blocked workers are a last resort (their
        current task is stalled in a nested get). Submission itself never
        spawns (short-task bursts pipeline onto live workers); SUSTAINED
        demand grows the pool via the monitor thread — the reference raylet
        similarly starts workers toward the granted lease count over time
        rather than per-request (worker_pool.cc PopWorker)."""
        candidates = [w for w in self._workers
                      if w.is_alive_fast() and not w.blocked]
        if not candidates:
            live = sum(1 for w in self._workers if w.is_alive())
            if live < self.MAX_WORKERS:
                return self._spawn_locked()
            candidates = [w for w in self._workers if w.is_alive()]
            if not candidates:
                return self._spawn_locked()
        return min(candidates, key=lambda w: w.load)

    def _submit_inflight(self, inf: _Inflight) -> None:
        dead: "_Worker | None" = None
        with self._cv:
            if self._shutdown:
                raise RuntimeError("pool is shut down")
            w = self._pick_worker_locked()
            seq = w.next_seq
            w.next_seq += 1
            w.inflight[seq] = inf
            inf.worker = w
            inf.started = False
            inf.cancel_sent = False
            inf.cancel_reason = None
            inf.submit_ts = time.monotonic()
            inf.seq = seq
            if inf.kind == "gen":
                frame = ("run_gen", seq, inf.task_bin, inf.fn_blob, inf.args_blob,
                         inf.backpressure)
            else:
                frame = ("run", seq, inf.oid_bin, inf.fn_blob, inf.args_blob,
                         inf.task_bin, inf.trace)
            # Ordered handoff: acquire the worker's send lock WHILE the
            # registration lock is held, but do the (blocking) pipe write
            # after releasing it. Every cancel sender discovers the inflight
            # under _cv and then queues on send_mu, so its cancel frame can
            # only follow this run frame — the ordering invariant the
            # worker's stale-cancel guard relies on — while reader threads
            # (which need _cv to resolve futures) never wait behind pipe
            # backpressure.
            w.send_mu.acquire()
        try:
            w.send_frame_locked(frame)
        except (BrokenPipeError, OSError):
            dead = w
        finally:
            w.send_mu.release()
        if dead is not None:
            self._on_worker_death(dead)

    def submit_blob(self, fn_blob: bytes, args_blob: bytes,
                    result_oid_bin: bytes | None = None,
                    task_bin: bytes | None = None,
                    trace=None) -> Future:
        """Pipelined submission; the future resolves to (status, payload, extra)
        or raises _RemoteTaskError / WorkerCrashedError."""
        inf = _Inflight(fn_blob, args_blob, result_oid_bin, task_bin,
                        trace=trace)
        self._submit_inflight(inf)
        return inf.future

    def submit_generator(self, fn_blob: bytes, args_blob: bytes,
                         task_bin: bytes, on_item,
                         backpressure: int = 0) -> _Inflight:
        """Run a streaming-generator task in a worker: on_item(index, status,
        payload, extra) fires per yield (reader thread); the returned handle's
        .future resolves to ("gen_end", count, None) at exhaustion, and
        .ack(consumed) releases the backpressure window (reference: streaming
        generators + generator_waiter.h consumed-count flow control)."""
        inf = _Inflight(fn_blob, args_blob, None, task_bin, kind="gen",
                        on_item=on_item, backpressure=backpressure)
        self._submit_inflight(inf)
        return inf

    def execute(self, fn: Callable, args: tuple, kwargs: dict,
                result_oid_bin: bytes | None = None, timeout: float | None = None,
                task_bin: bytes | None = None):
        """Run fn in a worker process; returns ('val', blob) | ('shm', oid_bin).

        Raises WorkerCrashedError if the worker dies mid-task; the caller's
        retry machinery treats it as a system failure.
        """
        from ray_tpu._private import serialization

        try:
            fn_blob = cloudpickle.dumps(fn)
            args_blob = serialization.serialize_to_bytes((args, kwargs))
        except Exception as e:
            raise ValueError(f"task not serializable for process isolation: {e}") from e
        return self.execute_blob(fn_blob, args_blob, result_oid_bin, timeout, task_bin)

    def execute_blob(self, fn_blob: bytes, args_blob: bytes,
                     result_oid_bin: bytes | None = None,
                     timeout: float | None = None,
                     task_bin: bytes | None = None,
                     trace=None):
        """Blocking form (head dispatcher and node agents): submit + wait."""
        import concurrent.futures as _cf

        inf = _Inflight(fn_blob, args_blob, result_oid_bin, task_bin,
                        trace=trace)
        self._submit_inflight(inf)
        try:
            return inf.future.result(timeout)
        except _cf.TimeoutError:
            # the worker is mid-task; its pipe is now desynced — kill it rather
            # than let it hand a later task this task's late response. Innocent
            # pipelined neighbors fail as WorkerCrashedError and retry.
            w = inf.worker
            if w is not None:
                try:
                    w.proc.terminate()
                except Exception:
                    pass
            raise TimeoutError(f"process task exceeded {timeout}s") from None

    # ------------------------------------------------------------ blocked flow
    def on_task_blocked(self, task_bin: bytes) -> None:
        """The head learned `task_bin` is blocked in a nested get/wait. Mark
        its worker blocked and yank that worker's queued (unstarted) tasks so
        they run elsewhere — the pipelined analog of the reference's
        NotifyDirectCallTaskBlocked worker-release."""
        to_cancel: list[tuple[_Worker, int]] = []
        with self._cv:
            for w in self._workers:
                if not w.is_alive():
                    continue
                for seq, inf in w.inflight.items():
                    if inf.started and inf.task_bin == task_bin:
                        w.blocked = True
                        for s2, inf2 in w.inflight.items():
                            if not inf2.started and not inf2.cancel_sent:
                                inf2.cancel_sent = True
                                inf2.cancel_reason = "migrate"
                                to_cancel.append((w, s2))
                        break
        for w, seq in to_cancel:
            try:
                w.send_frame(("cancel", seq, "migrate"))
            except (BrokenPipeError, OSError):
                self._on_worker_death(w)

    def cancel_task(self, task_bin: bytes, force: bool = False) -> bool:
        """User-requested cancel (ray.cancel). A queued (unstarted) task is
        yanked via the cancel protocol and its future resolves to
        TaskCancelledError; a RUNNING task is only interruptible with
        force=True, which kills its worker (pipelined neighbors fail as
        WorkerCrashedError and retry — CancelTask semantics,
        task_receiver.cc force_kill)."""
        target: _Worker | None = None
        seq_to_cancel: int | None = None
        with self._cv:
            for w in self._workers:
                for seq, inf in w.inflight.items():
                    if inf.task_bin == task_bin:
                        if inf.started:
                            if force:
                                try:
                                    os.kill(w.proc.pid, 9)
                                except OSError:
                                    return False
                                return True
                            if inf.kind == "gen":
                                # a RUNNING stream polls the cancelled set per
                                # item — a cancel frame aborts it cleanly. A
                                # prior MIGRATE cancel that lost (task started)
                                # was a worker-side no-op, so a user cancel
                                # must still send its own frame.
                                inf.user_cancelled = True
                                if not inf.cancel_sent or inf.cancel_reason == "migrate":
                                    inf.cancel_sent = True
                                    inf.cancel_reason = "user"
                                    target, seq_to_cancel = w, seq
                                break
                            return False
                        inf.user_cancelled = True
                        if not inf.cancel_sent or inf.cancel_reason == "migrate":
                            inf.cancel_sent = True
                            inf.cancel_reason = "user"
                            target, seq_to_cancel = w, seq
                        break
                if target is not None:
                    break
            # Ordered handoff (see _submit_inflight): grab the worker's send
            # lock under _cv so this cancel queues strictly after the task's
            # run frame, then write outside the pool lock.
            dead: "_Worker | None" = None
            if target is not None:
                target.send_mu.acquire()
        if target is None:
            return False
        try:
            target.send_frame_locked(("cancel", seq_to_cancel, "user"))
        except (BrokenPipeError, OSError):
            dead = target
        finally:
            target.send_mu.release()
        if dead is not None:
            # worker died under us — its inflight futures fail (task is
            # effectively cancelled from the caller's perspective)
            self._on_worker_death(dead)
        return True

    # ------------------------------------------------------------- inspection
    def worker_pids(self) -> list[int]:
        """Live worker pids (profile-capture target validation: a SIGUSR to
        a pid with no handler installed would TERMINATE it)."""
        with self._lock:
            return [w.proc.pid for w in self._workers if w.is_alive()]

    def running_tasks(self) -> dict:
        """pid -> (task_bin, start_ts) for in-flight tasks (OOM policy input)."""
        with self._lock:
            return dict(self._running_tasks)

    def kill_task(self, pid: int, task_bin) -> bool:
        """SIGKILL `pid` iff it is STILL running `task_bin` — re-verified under
        the pool lock so a policy decision made from a stale snapshot can't
        kill a worker that moved on to a different task."""
        with self._lock:
            cur = self._running_tasks.get(pid)
            if cur is None or cur[0] != task_bin:
                return False
            try:
                os.kill(pid, 9)
            except OSError:
                return False
            return True

    def kill_random_worker(self) -> int:
        """Chaos hook: SIGKILL one busy-or-idle worker (tests worker-death FT)."""
        with self._lock:
            for w in self._workers:
                if w.is_alive():
                    pid = w.proc.pid
                    os.kill(pid, 9)
                    return pid
        return -1

    def shutdown(self) -> None:
        self._shutdown = True
        with self._lock:
            workers, self._workers = self._workers, []
        for w in workers:
            try:
                w.send_frame(("exit",))
            except Exception:
                pass
            try:
                w.proc.wait(timeout=1)
            except subprocess.TimeoutExpired:
                w.proc.terminate()
            try:
                w.conn.close()
            except Exception:
                pass

    @property
    def num_alive(self) -> int:
        with self._lock:
            return sum(1 for w in self._workers if w.is_alive())


def _run_with_env(fn, runtime_env, *args, **kwargs):
    from ray_tpu import runtime_env as renv

    ctx = renv.build_context(runtime_env)
    with renv.apply_context(ctx):
        return fn(*args, **kwargs)


def _run_with_env_gen(fn, runtime_env, *args, **kwargs):
    # generator form: the context must stay LIVE across iteration — a plain
    # `return fn(...)` would tear the env down before the first yield runs
    from ray_tpu import runtime_env as renv

    ctx = renv.build_context(runtime_env)
    with renv.apply_context(ctx):
        yield from fn(*args, **kwargs)


def wrap_with_runtime_env(fn, runtime_env: dict, is_generator: bool = False):
    """Picklable wrapper: builds+applies the env inside the worker process."""
    import functools

    runner = _run_with_env_gen if is_generator else _run_with_env
    return functools.partial(runner, fn, runtime_env)


class _RemoteTaskError(Exception):
    """App-level failure inside the worker, carrying the remote traceback and
    (when picklable) the original exception object for retry matching."""

    def __init__(self, remote_tb: str, exc_blob: bytes | None = None):
        self.remote_tb = remote_tb
        self.exc_blob = exc_blob
        super().__init__(remote_tb)

    def original_exception(self):
        if self.exc_blob is not None:
            try:
                return cloudpickle.loads(self.exc_blob)
            except Exception:
                pass
        return None
