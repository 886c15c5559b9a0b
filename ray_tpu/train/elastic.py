"""Elastic gang runtime: preemption-tolerant training over restartable fleets.

The Podracer pattern (PAPERS.md, arxiv 2104.06272) on this runtime's own
substrates: a gang of rank processes that

1. DETECTS capacity loss through the head's existing liveness machinery —
   agent-expiry / node-death events arrive on the control plane's "nodes"
   pub/sub channel (core/cluster.py heartbeat monitor -> Runtime.on_node_death
   -> publish), and GCE preemption NOTICES arrive either from a node agent's
   metadata watcher (wire v6 ``preempt_notice``) or the driver-local
   ``GcePreemptionWatcher`` — no polling anywhere in the detection path;

2. CHECKPOINTS sharded train state into the OBJECT PLANE
   (``train/checkpoint.py::PlaneCheckpoint``): each rank ``put``s its shard
   (sealed into its node's store), the manager re-holds the refs driver-side
   and replicates every shard across >= 2 holders
   (``Runtime.ensure_plane_replicas`` — other agents' stores via the v6
   ``plane_replicate`` op, the head's spill-backed store as fallback), so a
   preempted holder doesn't take the only copy with it; restore rides the
   PR-5 ``pull_into`` zero-copy path;

3. RE-FORMS at whatever world size the cluster can deliver: fresh membership
   epoch (monotonic — stale members' reports are ignored), fresh coordinator
   address, fresh ``jax.distributed`` init, state re-sharded from the
   surviving checkpoint shards, and the epoch resumes.

State machine (``GangPhase``)::

    FORMING -> RUNNING -> DRAINING -> REFORMING -> RESUMED -> RUNNING -> ...
                  |                                              |
                  +------------> FINISHED / FAILED <-------------+

Every transition is stamped into the flight recorder (subsystem "gang") and
exported as ``gang_*`` metrics on the /metrics scrape.

The older per-attempt surface (``ElasticScalingPolicy`` + ``run_elastic``
over the TrainController) remains for fixed-shape retry loops; the
``GangManager`` below is the real elastic subsystem.
"""

from __future__ import annotations

import collections
import itertools
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Optional

import ray_tpu
from ray_tpu.util import flight_recorder
from ray_tpu.util.metrics import Counter, Gauge, Histogram

# ---------------------------------------------------------------- metrics
# Instruments bound once at import (util/metrics.py bind contract). These
# are the ``gang_*`` series the /metrics scrape serves.
_M_TRANSITIONS = Counter(
    "ray_tpu_gang_transitions_total",
    "elastic-gang lifecycle transitions", tag_keys=("phase",))
_M_WORKERS_LOST = Counter(
    "ray_tpu_gang_workers_lost_total",
    "gang members lost to node death / agent expiry").bind()
_M_PREEMPT_NOTICES = Counter(
    "ray_tpu_gang_preempt_notices_total",
    "provider preemption notices observed by gang managers").bind()
_M_REFORMS = Counter(
    "ray_tpu_gang_reforms_total",
    "gang re-formations (new membership epoch after a loss)").bind()
_M_REFORM_SECONDS = Histogram(
    "ray_tpu_gang_reform_seconds",
    "wall-clock from loss detection to the re-formed gang's launch",
    boundaries=[0.1, 0.5, 1, 2, 5, 10, 30, 60, 120]).bind()
_M_CKPTS = Counter(
    "ray_tpu_gang_checkpoints_total",
    "complete plane-backed gang checkpoints (all ranks, one step)").bind()
_M_CKPT_BYTES = Counter(
    "ray_tpu_gang_checkpoint_bytes_total",
    "bytes of checkpoint shards put into the object plane").bind()

# Live managers, sampled by producer gauges + util.state.gang_view().
_GANGS: "set[GangManager]" = set()
_GANGS_LOCK = threading.Lock()
_GANG_SEQ = itertools.count(1)


def _gang_gauge_producer(attr):
    def produce():
        with _GANGS_LOCK:
            gangs = list(_GANGS)
        return [({"gang": g.name}, float(getattr(g, attr)))
                for g in gangs]
    return produce


Gauge("ray_tpu_gang_world_size", "current world size per live gang",
      tag_keys=("gang",)).attach_producer(_gang_gauge_producer("world_size"))
Gauge("ray_tpu_gang_membership_epoch",
      "monotonic membership epoch per live gang",
      tag_keys=("gang",)).attach_producer(
          _gang_gauge_producer("membership_epoch"))


def gang_view() -> list:
    """Dashboard/state-API view of live gang managers (util.state.gang_view
    and GET /api/v0/gang serve this)."""
    with _GANGS_LOCK:
        gangs = list(_GANGS)
    out = []
    for g in sorted(gangs, key=lambda g: g.name):
        ckpt = g.last_checkpoint()
        out.append({
            "name": g.name,
            "phase": g.phase.value,
            "membership_epoch": g.membership_epoch,
            "world_size": g.world_size,
            "last_checkpoint_step": ckpt.step if ckpt else None,
            "members": {r: m["node"].hex() if m["node"] else None
                        for r, m in g.members().items()},
        })
    return out


# ----------------------------------------------------------------- config
@dataclass
class ElasticConfig:
    min_workers: int = 1
    max_workers: int = 8
    resources_per_worker: dict | None = None
    # plane-backed checkpointing: holders per shard (2 = survive one loss)
    checkpoint_replicas: int = 2
    # after a loss/notice, how long survivors get to save + exit cleanly
    drain_grace_s: float = 10.0
    # how long REFORMING waits for >= min_workers of capacity
    reform_timeout_s: float = 120.0
    # members initialize a fresh jax.distributed world per membership epoch
    jax_distributed: bool = False
    # run members in dedicated processes (required for jax_distributed)
    isolate_members: bool = False

    def __post_init__(self) -> None:
        if not isinstance(self.min_workers, int) or self.min_workers < 1:
            raise ValueError(
                f"ElasticConfig.min_workers must be an int >= 1, got "
                f"{self.min_workers!r} — a gang needs at least one rank")
        if not isinstance(self.max_workers, int) or self.max_workers < 1:
            raise ValueError(
                f"ElasticConfig.max_workers must be an int >= 1, got "
                f"{self.max_workers!r}")
        if self.min_workers > self.max_workers:
            raise ValueError(
                f"ElasticConfig.min_workers ({self.min_workers}) exceeds "
                f"max_workers ({self.max_workers}) — the gang could never "
                "form; swap or widen the bounds")
        if self.checkpoint_replicas < 1:
            raise ValueError(
                f"ElasticConfig.checkpoint_replicas must be >= 1, got "
                f"{self.checkpoint_replicas} (1 = primary only, no "
                "durability against holder loss)")
        if self.drain_grace_s < 0:
            raise ValueError("ElasticConfig.drain_grace_s must be >= 0")
        if self.reform_timeout_s <= 0:
            raise ValueError("ElasticConfig.reform_timeout_s must be > 0")


class ElasticScalingPolicy:
    """Decide the worker count for the next run attempt from live capacity."""

    def __init__(self, config: ElasticConfig):
        self.config = config

    def workers_for_next_attempt(self) -> int:
        res = self.config.resources_per_worker or {"CPU": 1.0}
        avail = ray_tpu.available_resources()
        fits = min(
            (avail.get(k, 0.0) // v) for k, v in res.items() if v > 0
        )
        n = int(max(self.config.min_workers, min(self.config.max_workers, fits)))
        return n

    def validate(self) -> None:
        if self.workers_for_next_attempt() < self.config.min_workers:
            raise RuntimeError(
                f"Cluster cannot satisfy min_workers={self.config.min_workers}"
            )


# ------------------------------------------------------------- preemption
class PreemptionHandler:
    """Drain hook: when a preemption notice arrives, workers see
    ``should_checkpoint_and_exit()`` truthy and exit cleanly at the next step
    boundary (reference: preemption.py drain + MEGASCALE stale-env trap —
    the restart must rebuild coordination env from scratch, which a fresh
    gang per membership epoch guarantees).

    Thread-safe: watcher threads (GCE metadata pollers) call
    ``notify_preemption`` while train/controller threads read — all state
    mutations happen under one lock, and listeners fire outside it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._preempted = threading.Event()
        self._notice_time: float | None = None
        self._listeners: list[Callable[[], None]] = []

    def notify_preemption(self) -> None:
        """Wired to the cloud provider's preemption signal (e.g. GCE metadata
        server 'preempted' event on TPU-VMs). Idempotent: listeners fire on
        the FIRST notice only."""
        with self._lock:
            if self._preempted.is_set():
                return
            self._notice_time = time.monotonic()
            self._preempted.set()
            listeners = list(self._listeners)
        for cb in listeners:  # outside the lock: a listener may re-enter
            try:
                cb()
            except Exception:
                pass

    def should_checkpoint_and_exit(self) -> bool:
        return self._preempted.is_set()

    def clear(self) -> None:
        with self._lock:
            self._preempted.clear()
            self._notice_time = None

    def seconds_since_notice(self) -> Optional[float]:
        with self._lock:
            if self._notice_time is None:
                return None
            return time.monotonic() - self._notice_time

    def add_listener(self, cb: Callable[[], None]) -> None:
        """Event-driven consumers (GangManager) register here instead of
        polling ``should_checkpoint_and_exit``."""
        with self._lock:
            self._listeners.append(cb)

    def remove_listener(self, cb: Callable[[], None]) -> None:
        with self._lock:
            try:
                self._listeners.remove(cb)
            except ValueError:
                pass


_global_handler = PreemptionHandler()


def get_preemption_handler() -> PreemptionHandler:
    return _global_handler


class GcePreemptionWatcher:
    """Driver-side GCE preemption watcher: polls the VM-local metadata
    endpoint and fires the PreemptionHandler once it flips (node agents run
    the same watch in-process — node_agent.py — and notify the head over
    wire v6; this covers the DRIVER's own VM)."""

    def __init__(self, url: str | None = None, period_s: float = 1.0,
                 handler: PreemptionHandler | None = None):
        from ray_tpu.autoscaler import gce

        self.url = url or gce.PREEMPTED_METADATA_URL
        self.period_s = period_s
        self.handler = handler or get_preemption_handler()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "GcePreemptionWatcher":
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="gce-preempt-watch")
        self._thread.start()
        return self

    def _loop(self) -> None:
        from ray_tpu.autoscaler import gce

        while not self._stop.is_set():
            if gce.poll_preempted(self.url, timeout=self.period_s + 4):
                flight_recorder.record("gang", "preempt_notice",
                                       source="driver_metadata")
                _M_PREEMPT_NOTICES.inc()
                self.handler.notify_preemption()
                return
            self._stop.wait(self.period_s)

    def stop(self) -> None:
        self._stop.set()


# ---------------------------------------------------------- gang protocol
class GangPhase(Enum):
    FORMING = "FORMING"
    RUNNING = "RUNNING"
    DRAINING = "DRAINING"
    REFORMING = "REFORMING"
    RESUMED = "RESUMED"
    FINISHED = "FINISHED"
    FAILED = "FAILED"


def _gang_channel(name: str) -> str:
    return f"elastic:{name}"


def shard_bounds(total: int, rank: int, world: int) -> "tuple[int, int]":
    """[lo, hi) of a length-``total`` axis owned by ``rank`` of ``world``
    (contiguous, remainder spread over the first ranks)."""
    base, rem = divmod(total, world)
    lo = rank * base + min(rank, rem)
    return lo, lo + base + (1 if rank < rem else 0)


def reshard_arrays(shards: list, world: int) -> list:
    """Re-split checkpoint shards for a NEW world size: concatenate the
    surviving shards' leading axes and slice per the new bounds — the
    resharding step of gang re-formation (works for any same-dtype arrays
    sharded on axis 0)."""
    import numpy as np

    full = np.concatenate([np.asarray(s) for s in shards], axis=0)
    n = full.shape[0]
    return [full[slice(*shard_bounds(n, r, world))] for r in range(world)]


class GangContext:
    """Worker-side face of the elastic gang: restore, save, should_stop.

    Created inside the member task from the manager's spec; the user train
    fn receives it as its only argument."""

    def __init__(self, spec: dict):
        self.name = spec["name"]
        self.rank = spec["rank"]
        self.world_size = spec["world_size"]
        self.membership_epoch = spec["epoch"]
        self.start_step = spec.get("start_step", 0)
        self.user_config = spec.get("user_config") or {}
        self.coordinator = spec.get("coordinator")
        self._shard_refs = spec.get("shards")  # prior epoch's ckpt, or None
        self._chan = _gang_channel(self.name)
        from ray_tpu.experimental import pubsub

        self._pubsub = pubsub
        self._sub = pubsub.subscribe(self._chan)
        self._drained = False
        self._live_refs: list = []  # shard refs kept until the member exits
        self._initial_ppid = os.getppid()
        self.last_saved_step: int | None = None

    # -- lifecycle --------------------------------------------------------
    def _announce(self, kind: str, **fields) -> None:
        msg = {"kind": kind, "epoch": self.membership_epoch,
               "rank": self.rank, "pid": os.getpid()}
        msg.update(fields)
        self._pubsub.publish(self._chan, msg)

    def _init_jax_distributed(self) -> None:
        """Fresh jax.distributed world for THIS membership epoch: new
        coordinator address every re-formation, so no member ever reuses a
        dead epoch's coordination env (the MEGASCALE stale-env trap)."""
        import jax

        if os.environ.get("RAY_TPU_WORKER_TPU") != "1":
            jax.config.update("jax_platforms", "cpu")
            # multi-process CPU collectives need the Gloo backend
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            self.coordinator, num_processes=self.world_size,
            process_id=self.rank)

    # -- checkpointing ----------------------------------------------------
    def restore_shards(self, timeout: float = 120.0) -> "list | None":
        """The previous epoch's complete checkpoint — every rank's shard,
        rank-ordered by the OLD world size — or None on a cold start. The
        transfer rides the zero-copy pull path; re-split for the new world
        with ``reshard_arrays`` (or your own scheme)."""
        if not self._shard_refs:
            return None
        from ray_tpu.train.checkpoint import PlaneCheckpoint

        return PlaneCheckpoint(self._shard_refs,
                               step=self.start_step).to_state(timeout=timeout)

    def save(self, shard: Any, step: int, metrics: dict | None = None) -> None:
        """Put THIS rank's shard into the object plane and report it to the
        manager, which re-holds the ref (so the shard outlives this worker)
        and replicates it across holders once all ranks reported ``step``."""
        from ray_tpu.train.checkpoint import PlaneCheckpoint

        ref, nbytes = PlaneCheckpoint.save_shard(shard)
        # keep only the most recent refs alive worker-side: the manager
        # re-holds every reported shard driver-side, so pinning the whole
        # history here would keep superseded shards in the stores forever
        self._live_refs.append(ref)
        del self._live_refs[:-2]
        self.last_saved_step = step
        self._announce("shard", step=step, oid=ref.object_id().binary(),
                       nbytes=nbytes, metrics=dict(metrics or {}))

    # -- drain ------------------------------------------------------------
    def should_stop(self) -> bool:
        """Check at step boundaries: True once the manager drained this
        epoch (loss elsewhere in the gang / preemption notice), the local
        preemption handler fired, or this worker got orphaned (its agent
        died under it) — save and return promptly when it flips."""
        if self._drained:
            return True
        while True:
            msg = self._sub.poll(timeout=0)
            if msg is None:
                break
            if (isinstance(msg, dict) and msg.get("kind") == "drain"
                    and msg.get("epoch", 0) >= self.membership_epoch):
                self._drained = True
                return True
        if get_preemption_handler().should_checkpoint_and_exit():
            # mark drained too: the member must report status "stopped" —
            # a preemption-truncated run is a capacity event, not a clean
            # completion the manager may mistake for FINISHED
            self._drained = True
            return True
        if os.getppid() != self._initial_ppid:
            # reparented: the supervising agent/pool died — our node is on
            # its way out, stop burning cycles on a stale epoch
            self._drained = True
            return True
        return False


def _elastic_member(spec_blob: bytes) -> bytes:
    """Runtime task hosting one elastic-gang rank (max_retries=0: a lost
    member is the MANAGER's business — an automatic runtime retry would
    silently fork a stale epoch)."""
    import cloudpickle

    spec = cloudpickle.loads(spec_blob)
    ctx = GangContext(spec)
    ctx._announce("member_up", node=os.environ.get("RAY_TPU_NODE_ID"))
    jax_up = False
    try:
        if spec.get("coordinator"):
            ctx._init_jax_distributed()
            jax_up = True
        result = spec["fn"](ctx)
        status = "stopped" if ctx._drained else "done"
        ctx._announce("member_done", status=status,
                      step=ctx.last_saved_step)
        return cloudpickle.dumps({"status": status, "result": result,
                                  "rank": ctx.rank,
                                  "last_saved_step": ctx.last_saved_step})
    finally:
        try:  # drop the gang-channel subscription — thread-mode members
            ctx._sub.close()  # share the head Publisher, which otherwise
        except Exception:     # copies every later publish into a dead queue
            pass
        if jax_up:
            try:
                import jax

                jax.distributed.shutdown()
            except Exception:
                pass


# ------------------------------------------------------------ the manager
@dataclass
class GangResult:
    results: list            # per-rank user return values (final epoch)
    membership_epochs: int
    world_size: int
    checkpoint: "Any | None"  # last complete PlaneCheckpoint
    history: list            # [(phase, detail, wall_ts)]
    error: "BaseException | None" = None


class _Stop(Exception):
    """Internal: shutdown() was called — unwind the driver thread."""


class _Loss(Exception):
    """Internal: the running epoch lost capacity (node death, member system
    failure, preemption notice); carries the failure kind for the policy."""

    def __init__(self, kind, detail: str, proactive: bool = False,
                 driver_preempt: bool = False):
        super().__init__(detail)
        self.kind = kind
        self.detail = detail
        self.proactive = proactive  # notice BEFORE loss: drain can save
        self.driver_preempt = driver_preempt  # from the DRIVER's handler


class GangManager:
    """The elastic gang state machine (see module docstring). Runs on the
    head driver; members are runtime tasks spread across live nodes."""

    def __init__(self, train_fn: Callable, config: ElasticConfig | None = None,
                 *, name: str | None = None, user_config: dict | None = None,
                 failure_config=None):
        from ray_tpu.core.runtime import get_runtime
        from ray_tpu.train.config import FailureConfig
        from ray_tpu.train.failure_policy import FailurePolicy

        self._rt = get_runtime()
        if not hasattr(self._rt, "publisher"):
            raise RuntimeError(
                "GangManager needs the head runtime (its loss detection "
                "subscribes to the head's node-event channel); run it on "
                "the driver that called ray_tpu.init()")
        self.train_fn = train_fn
        self.config = config or ElasticConfig()
        self.name = name or f"gang-{next(_GANG_SEQ)}"
        self.user_config = dict(user_config or {})
        # losses are capacity events by default (PREEMPTED budget:
        # unlimited); member USER errors draw max_failures
        self.failure_policy = FailurePolicy(
            failure_config or FailureConfig(max_failures=0))

        self.phase = GangPhase.FORMING
        self.membership_epoch = 0
        self.world_size = 0
        self.history: "list[tuple[str, str, float]]" = []
        self.metrics_history: list[dict] = []
        self._visited: set = set()
        self._cv = threading.Condition()
        self._events: "queue.Queue[tuple]" = queue.Queue()
        self._members: dict[int, dict] = {}   # rank -> {ref,node,lost,done}
        self._staging: dict[int, dict] = {}   # step -> rank -> (ref,nbytes)
        self._ckpt = None        # newest COMPLETE PlaneCheckpoint
        self._safe_ckpt = None   # newest complete AND replicated
        # recent complete checkpoints (refs pinned): restore falls back past
        # a checkpoint whose shard died with its (unreplicated) holder
        self._ckpts: "collections.deque" = collections.deque(maxlen=4)
        self._excluded: set = set()  # nodes with preemption notices
        # one-shot events stashed by _form's capacity wait for _run_epoch
        # (e.g. a driver preempt_local that fired while REFORMING)
        self._pending_events: list = []
        self._stopped = threading.Event()
        self._result: GangResult | None = None
        self._threads: list[threading.Thread] = []
        self._driver: threading.Thread | None = None
        self._preempt_cb = lambda: self._events.put(("preempt_local", None))

    # -- public surface ---------------------------------------------------
    def start(self) -> "GangManager":
        with _GANGS_LOCK:
            _GANGS.add(self)
        self._transition(GangPhase.FORMING)
        self._nodes_sub = self._rt.publisher.subscribe("nodes")
        self._gang_sub = self._rt.publisher.subscribe(
            _gang_channel(self.name))
        self._spawn(self._forward, self._nodes_sub, "nodes")
        self._spawn(self._forward, self._gang_sub, "gang")
        get_preemption_handler().add_listener(self._preempt_cb)
        self._driver = threading.Thread(
            target=self._drive, daemon=True, name=f"gang-{self.name}")
        self._driver.start()
        return self

    def run(self, timeout: float | None = None) -> GangResult:
        self.start()
        return self.result(timeout=timeout)

    def result(self, timeout: float | None = None) -> GangResult:
        if not self.wait_for_phase(
                (GangPhase.FINISHED, GangPhase.FAILED), timeout=timeout):
            raise TimeoutError(
                f"gang {self.name} not terminal after {timeout}s "
                f"(phase={self.phase.value})")
        assert self._result is not None
        if self._result.error is not None:
            raise self._result.error
        return self._result

    def wait_for_phase(self, phase, timeout: float | None = None) -> bool:
        """Block until the gang has ENTERED (possibly already passed
        through) any of the given phases. Condition-variable wait — no
        sleep polling."""
        wanted = set(phase) if isinstance(phase, (tuple, list, set)) \
            else {phase}
        with self._cv:
            return self._cv.wait_for(
                lambda: bool(wanted & self._visited), timeout=timeout)

    def last_checkpoint(self, safe: bool = False):
        """Newest complete checkpoint; ``safe=True`` = newest whose shard
        replication also finished (survives any single holder's death)."""
        return self._safe_ckpt if safe else (self._ckpt or self._safe_ckpt)

    def wait_for_checkpoint(self, min_step: int = 0, safe: bool = False,
                            timeout: float | None = None) -> bool:
        """Block until a complete (``safe=True``: replicated) checkpoint at
        step >= ``min_step`` exists. Condition-variable wait."""
        def ready():
            ck = self._safe_ckpt if safe else self._ckpt
            return ck is not None and ck.step >= min_step

        with self._cv:
            return self._cv.wait_for(ready, timeout=timeout)

    def members(self) -> dict:
        return dict(self._members)

    def shutdown(self) -> None:
        self._stopped.set()
        self._events.put(("stop", None))
        try:
            self._rt.publisher.publish(
                _gang_channel(self.name),
                {"kind": "drain", "epoch": self.membership_epoch,
                 "reason": "shutdown"})
        except Exception:
            pass
        self._cancel_members()
        self._teardown()

    # -- internals --------------------------------------------------------
    def _spawn(self, target, *args) -> None:
        # prune finished threads: one waiter per member per epoch plus one
        # replicator per checkpoint would otherwise grow forever on a
        # long-lived manager
        self._threads = [t for t in self._threads if t.is_alive()]
        t = threading.Thread(target=target, args=args, daemon=True)
        t.start()
        self._threads.append(t)

    def _forward(self, sub, tag: str) -> None:
        """Pub/sub -> the manager's single merged event queue."""
        while not self._stopped.is_set():
            msg = sub.poll(timeout=1.0)
            if msg is not None:
                self._events.put((tag, msg))

    def _transition(self, phase: GangPhase, detail: str = "") -> None:
        with self._cv:
            self.phase = phase
            self._visited.add(phase)
            self.history.append((phase.value, detail, time.time()))
            self._cv.notify_all()
        _M_TRANSITIONS.inc(tags={"phase": phase.value})
        flight_recorder.record("gang", "transition", gang=self.name,
                               phase=phase.value, detail=detail,
                               epoch=self.membership_epoch,
                               world_size=self.world_size)

    def _drive(self) -> None:
        from ray_tpu.train.failure_policy import FailureDecision

        try:
            while not self._stopped.is_set():
                try:
                    self._form()
                except Exception as e:
                    self._finish(GangPhase.FAILED, error=e)
                    return
                try:
                    results = self._run_epoch()
                    self._finish(GangPhase.FINISHED, results=results)
                    return
                except _Stop:
                    self._finish(GangPhase.FAILED,
                                 error=RuntimeError("gang shut down"))
                    return
                except _Loss as loss:
                    decision = self.failure_policy.decide(loss.kind)
                    try:
                        self._drain(loss)
                    except _Stop:
                        self._finish(GangPhase.FAILED,
                                     error=RuntimeError("gang shut down"))
                        return
                    if loss.driver_preempt:
                        # notice consumed: the drain took its checkpoint.
                        # Without this, thread-mode members of every NEW
                        # epoch would see the latched handler and stop
                        # immediately — an infinite drain/reform livelock
                        get_preemption_handler().clear()
                    if decision == FailureDecision.RAISE:
                        self._finish(GangPhase.FAILED, error=RuntimeError(
                            f"gang {self.name} failure budget exhausted: "
                            f"{loss.detail}"))
                        return
                    _M_REFORMS.inc()
                    self._transition(GangPhase.REFORMING, loss.detail)
            # stopped flag flipped between phases: still end at a terminal
            # phase, or a concurrent result() would block forever
            if self._result is None:
                self._finish(GangPhase.FAILED,
                             error=RuntimeError("gang shut down"))
        except Exception as e:  # pragma: no cover — driver must not die mute
            self._finish(GangPhase.FAILED, error=e)

    def _finish(self, phase: GangPhase, results: list | None = None,
                error: BaseException | None = None) -> None:
        flight_recorder.record(
            "gang", "finished" if phase == GangPhase.FINISHED else "failed",
            gang=self.name, epochs=self.membership_epoch,
            error=str(error)[:200] if error else None)
        # the result snapshot must exist before waiters wake, and must
        # already carry the terminal history entry — set both in one step
        with self._cv:
            self.phase = phase
            self._visited.add(phase)
            self.history.append(
                (phase.value, str(error) if error else "", time.time()))
            self._result = GangResult(
                results=results or [],
                membership_epochs=self.membership_epoch,
                world_size=self.world_size,
                checkpoint=self.last_checkpoint(),
                history=list(self.history), error=error)
            self._cv.notify_all()
        _M_TRANSITIONS.inc(tags={"phase": phase.value})
        flight_recorder.record("gang", "transition", gang=self.name,
                               phase=phase.value,
                               epoch=self.membership_epoch,
                               world_size=self.world_size)
        self._teardown()

    def _teardown(self) -> None:
        self._stopped.set()
        get_preemption_handler().remove_listener(self._preempt_cb)
        from ray_tpu.autoscaler.autoscaler import clear_standing_demand

        clear_standing_demand(self.name)
        for sub in (getattr(self, "_nodes_sub", None),
                    getattr(self, "_gang_sub", None)):
            if sub is not None:
                try:
                    sub.close()
                except Exception:
                    pass
        with _GANGS_LOCK:
            _GANGS.discard(self)

    # -- formation --------------------------------------------------------
    def _placement_plan(self) -> list:
        """One entry per launchable member: the node to pin it to, spread
        round-robin across live, non-draining, non-excluded nodes.

        Fit is computed from AVAILABLE resources: members are pinned with
        hard NodeAffinity, so planning against totals would queue ranks
        behind foreign workloads forever (rank 0 then blocks the whole
        world in jax.distributed.initialize). The capacity-wait loop in
        _form re-plans periodically, which also absorbs the short window
        where a drained epoch's resources are still being released."""
        res = self.config.resources_per_worker or {"CPU": 1.0}
        per_node: list[list] = []
        for node in self._rt.scheduler.nodes():
            if not node.alive or getattr(node, "draining", False):
                continue
            if node.node_id in self._excluded:
                continue
            avail = getattr(node, "available", None) or node.total
            fit = min((int(avail.get(k, 0.0) // v)
                       for k, v in res.items() if v > 0), default=0)
            if fit > 0:
                per_node.append([node.node_id] * fit)
        plan = [nid for group in itertools.zip_longest(*per_node)
                for nid in group if nid is not None] if per_node else []
        return plan[:self.config.max_workers]

    def _form(self) -> None:
        """FORMING/REFORMING -> a launched gang at current capacity."""
        from ray_tpu.autoscaler.autoscaler import (
            clear_standing_demand,
            register_standing_demand,
        )

        t0 = time.monotonic()
        cfg = self.config
        res = dict(cfg.resources_per_worker or {"CPU": 1.0})
        # standing demand: the autoscaler sees the gang's floor even while
        # no member tasks are queued (REFORMING submits nothing until
        # capacity exists — without this the reconciler would see zero
        # demand and never launch the replacement node)
        register_standing_demand(self.name, [dict(res)] * cfg.min_workers)
        deadline = time.monotonic() + cfg.reform_timeout_s
        while True:
            plan = self._placement_plan()
            if len(plan) >= cfg.min_workers:
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise RuntimeError(
                    f"gang {self.name} could not reach min_workers="
                    f"{cfg.min_workers} within {cfg.reform_timeout_s}s "
                    f"(capacity: {len(plan)})")
            try:
                # woken by node registered/dead events; the cap on the wait
                # also re-plans periodically, because resource RELEASE (a
                # drained epoch's members letting go) publishes no event
                tag, _msg = self._events.get(timeout=min(remaining, 0.5))
                if tag == "stop":
                    raise RuntimeError("gang shut down while forming")
                if tag == "preempt_local":
                    # one-shot driver-preemption notice: must not be
                    # swallowed here — _run_epoch consumes it first thing
                    self._pending_events.append((tag, _msg))
            except queue.Empty:
                continue
        self.membership_epoch += 1
        self.world_size = len(plan)
        epoch = self.membership_epoch
        coordinator = None
        reserved = None
        if cfg.jax_distributed:
            from ray_tpu.train.gang import _local_ip, _reserve_port

            reserved, port = _reserve_port()
            coordinator = f"{_local_ip()}:{port}"
        ckpt = self._pick_restore_ckpt()
        restore_refs = list(ckpt.shard_refs) if ckpt else None
        start_step = (ckpt.step + 1) if ckpt else 0
        import cloudpickle

        opts: dict = {"max_retries": 0, "name": f"{self.name}-member"}
        opts["num_cpus"] = float(res.pop("CPU", 1.0))
        if "TPU" in res:
            opts["num_tpus"] = float(res.pop("TPU"))
        if res:
            opts["resources"] = res
        if cfg.isolate_members or cfg.jax_distributed:
            opts["isolate_process"] = True
        member = ray_tpu.remote(**opts)(_elastic_member)
        self._members = {}
        self._staging = {}
        if reserved is not None:
            # release the held coordinator port at the last moment (see
            # gang.py _reserve_port: the bind is held, not re-found)
            reserved.close()
        for rank, nid in enumerate(plan):
            spec = {
                "name": self.name, "epoch": epoch, "rank": rank,
                "world_size": self.world_size, "coordinator": coordinator,
                "start_step": start_step, "shards": restore_refs,
                "user_config": self.user_config, "fn": self.train_fn,
            }
            ref = member.options(
                scheduling_strategy=ray_tpu.NodeAffinitySchedulingStrategy(
                    node_id=nid.hex())
            ).remote(cloudpickle.dumps(spec))
            self._members[rank] = {"ref": ref, "node": nid, "lost": False,
                                   "done": False, "up": False,
                                   "result": None}
            self._spawn(self._await_member, epoch, rank, ref)
        clear_standing_demand(self.name)
        if epoch == 1:
            self._transition(GangPhase.RUNNING,
                             f"{self.world_size} workers")
        else:
            _M_REFORM_SECONDS.observe(time.monotonic() - t0)
            flight_recorder.record(
                "gang", "reform", gang=self.name, epoch=epoch,
                world_size=self.world_size, start_step=start_step)
            self._transition(GangPhase.RESUMED,
                             f"epoch {epoch}: {self.world_size} workers "
                             f"from step {start_step}")
            flight_recorder.record("gang", "resume", gang=self.name,
                                   epoch=epoch, start_step=start_step)
            self._transition(GangPhase.RUNNING,
                             f"{self.world_size} workers")

    def _await_member(self, epoch: int, rank: int, ref) -> None:
        try:
            import cloudpickle

            blob = ray_tpu.get(ref, timeout=None)
            self._events.put(("member_result",
                              (epoch, rank, cloudpickle.loads(blob), None)))
        except BaseException as e:  # noqa: BLE001
            self._events.put(("member_result", (epoch, rank, None, e)))

    # -- the running epoch ------------------------------------------------
    def _run_epoch(self) -> list:
        """Consume events until every rank finished ("done") or a loss is
        detected; raises _Loss on capacity events."""
        from ray_tpu.train.failure_policy import FailureKind, classify_failure

        while True:
            if self._pending_events:
                tag, payload = self._pending_events.pop(0)
            else:
                tag, payload = self._events.get()
            if tag == "stop":
                raise _Stop
            if tag == "gang":
                self._on_gang_msg(payload)
            elif tag == "member_result":
                epoch, rank, value, err = payload
                if epoch != self.membership_epoch:
                    continue  # a stale epoch's straggler
                m = self._members.get(rank)
                if m is None or m["lost"]:
                    continue
                m["done"] = True
                if err is not None:
                    from ray_tpu.exceptions import ObjectLostError
                    from ray_tpu.train.failure_policy import _exception_chain

                    kind = classify_failure(err)
                    shard_lost = any(isinstance(e, ObjectLostError)
                                     for e in _exception_chain(err))
                    if kind == FailureKind.USER_ERROR and not shard_lost:
                        # a lost checkpoint shard is a capacity symptom
                        # (holder died), not a train_fn bug — reform; the
                        # chain walk matters: it arrives WRAPPED
                        # (TaskError(ObjectLostError)) at get()
                        raise _Loss(FailureKind.USER_ERROR,
                                    f"rank {rank} raised: {err}")
                    self._note_worker_lost(rank, m, f"{type(err).__name__}")
                    raise _Loss(FailureKind.PREEMPTED,
                                f"rank {rank} died: {err}")
                m["result"] = value
                if value.get("status") != "done":
                    # drained/stopped without a drain from us: treat as a
                    # preemption-style capacity event
                    raise _Loss(FailureKind.PREEMPTED,
                                f"rank {rank} stopped early")
                if all(mm["done"] for mm in self._members.values()):
                    return [self._members[r]["result"]["result"]
                            for r in sorted(self._members)]
            elif tag == "nodes":
                self._on_node_event(payload)
            elif tag == "preempt_local":
                # no counter bump here: the notice's SOURCE (watcher / node
                # event) already counted it — incrementing again would
                # double-count every driver notice on the scrape
                flight_recorder.record("gang", "preempt_notice",
                                       gang=self.name, source="driver")
                raise _Loss(FailureKind.PREEMPTED,
                            "driver preemption notice", proactive=True,
                            driver_preempt=True)

    def _on_gang_msg(self, msg) -> None:
        if not isinstance(msg, dict):
            return
        if msg.get("epoch") != self.membership_epoch:
            return  # stale epoch: monotonic membership makes this safe
        kind = msg.get("kind")
        rank = msg.get("rank")
        m = self._members.get(rank) if rank is not None else None
        if kind == "member_up" and m is not None:
            m["up"] = True
        elif kind == "shard" and m is not None and not m["lost"]:
            from ray_tpu._private.ids import ObjectID
            from ray_tpu.core.object_ref import ObjectRef

            step = msg["step"]
            # re-hold the shard driver-side: it must outlive the worker
            ref = ObjectRef(ObjectID(msg["oid"]), self._rt)
            stage = self._staging.setdefault(step, {})
            stage[rank] = (ref, msg.get("nbytes", 0))
            _M_CKPT_BYTES.inc(msg.get("nbytes", 0))
            if msg.get("metrics"):
                self.metrics_history.append(
                    {"step": step, "rank": rank, **msg["metrics"]})
            if len(stage) == self.world_size:
                self._complete_checkpoint(step, stage)

    def _complete_checkpoint(self, step: int, stage: dict) -> None:
        from ray_tpu.train.checkpoint import PlaneCheckpoint

        refs = [stage[r][0] for r in sorted(stage)]
        ckpt = PlaneCheckpoint(refs, step=step,
                               epoch=self.membership_epoch,
                               world_size=self.world_size)
        with self._cv:
            if self._ckpt is None or step >= self._ckpt.step:
                self._ckpt = ckpt
            self._ckpts.append(ckpt)
            self._cv.notify_all()
        _M_CKPTS.inc()
        flight_recorder.record(
            "gang", "checkpoint", gang=self.name, step=step,
            epoch=self.membership_epoch,
            bytes=sum(n for _, n in stage.values()))
        for old in [s for s in self._staging if s < step]:
            del self._staging[old]  # old shards: refs drop -> plane frees
        self._spawn(self._replicate_ckpt, ckpt)

    def _replicate_ckpt(self, ckpt) -> None:
        """Replication runs OFF the event loop: a dying holder mid-call
        must not delay loss detection."""
        try:
            ckpt.replicate(self.config.checkpoint_replicas)
            with self._cv:
                if (self._safe_ckpt is None
                        or ckpt.step >= self._safe_ckpt.step):
                    self._safe_ckpt = ckpt
                self._cv.notify_all()
        except Exception as e:
            flight_recorder.record("gang", "replicate_failed",
                                   gang=self.name, step=ckpt.step,
                                   error=str(e)[:200])

    def _shard_available(self, ref) -> bool:
        """Does this shard still have at least one live backing copy?"""
        rt = self._rt
        oid = ref.object_id()
        if rt.has_plane_copy(oid):
            return True
        if rt.shm_store is not None and rt.shm_store.contains(oid):
            return True
        if rt.spill is not None and rt.spill.is_spilled(oid):
            return True
        obj = rt.memory_store.get_if_exists(oid)
        # value resident in the head memory store (thread-mode puts)
        return obj is not None and not getattr(obj, "in_shm", False) \
            and obj.error is None

    def _pick_restore_ckpt(self):
        """Newest complete checkpoint whose EVERY shard still has a live
        holder — a checkpoint whose unreplicated shard died with its node
        is skipped for an older restorable one (this is what bounded-lag
        replication buys: the fallback is never more than a few steps
        behind)."""
        cands = [c for c in list(self._ckpts) + [self._safe_ckpt]
                 if c is not None]
        seen: set = set()
        for ckpt in sorted(cands, key=lambda c: c.step, reverse=True):
            if id(ckpt) in seen:
                continue
            seen.add(id(ckpt))
            if all(self._shard_available(r) for r in ckpt.shard_refs):
                return ckpt
            flight_recorder.record(
                "gang", "ckpt_unrestorable", gang=self.name, step=ckpt.step,
                detail="a shard lost its last holder; falling back")
        return None

    def _on_node_event(self, msg) -> None:
        if not isinstance(msg, dict):
            return
        event = msg.get("event")
        node_hex = msg.get("node_id", "")
        hosting = [r for r, m in self._members.items()
                   if m["node"] is not None and m["node"].hex() == node_hex
                   and not m["lost"] and not m["done"]]
        if event == "dead":
            from ray_tpu._private.ids import NodeID

            try:
                self._excluded.add(NodeID(bytes.fromhex(node_hex)))
            except ValueError:
                pass
            if hosting:
                from ray_tpu.train.failure_policy import FailureKind

                for r in hosting:
                    self._note_worker_lost(r, self._members[r],
                                           "agent_expiry")
                raise _Loss(FailureKind.PREEMPTED,
                            f"node {node_hex[:12]} died with rank(s) "
                            f"{hosting}")
        elif event == "preempt_notice":
            from ray_tpu._private.ids import NodeID

            try:
                self._excluded.add(NodeID(bytes.fromhex(node_hex)))
            except ValueError:
                pass
            if hosting:
                from ray_tpu.train.failure_policy import FailureKind

                _M_PREEMPT_NOTICES.inc()
                flight_recorder.record(
                    "gang", "preempt_notice", gang=self.name,
                    node_id=node_hex, ranks=hosting)
                raise _Loss(FailureKind.PREEMPTED,
                            f"preemption notice for node {node_hex[:12]} "
                            f"(rank(s) {hosting})", proactive=True)
        # "registered": capacity arrival — _form's wait loop consumes it

    def _note_worker_lost(self, rank: int, m: dict, how: str) -> None:
        m["lost"] = True
        _M_WORKERS_LOST.inc()
        flight_recorder.record(
            "gang", "worker_lost", gang=self.name, rank=rank,
            epoch=self.membership_epoch, how=how,
            node_id=m["node"].hex() if m["node"] else None)

    # -- drain ------------------------------------------------------------
    def _drain(self, loss: "_Loss") -> None:
        """Tell survivors to save + exit at the next step boundary, give
        them the grace window (their final saves may still complete a newer
        checkpoint), then cancel stragglers."""
        self._transition(GangPhase.DRAINING, loss.detail)
        flight_recorder.record("gang", "drain", gang=self.name,
                               epoch=self.membership_epoch,
                               reason=loss.detail[:200])
        try:
            self._rt.publisher.publish(
                _gang_channel(self.name),
                {"kind": "drain", "epoch": self.membership_epoch,
                 "reason": loss.detail[:200]})
        except Exception:
            pass
        deadline = time.monotonic() + self.config.drain_grace_s

        def all_settled() -> bool:
            return all(m["done"] or m["lost"]
                       for m in self._members.values())

        while not all_settled():
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                tag, payload = self._events.get(timeout=remaining)
            except queue.Empty:
                break
            if tag == "stop":
                raise _Stop  # shutdown mid-drain: unwind to a terminal phase
            if tag == "preempt_local":
                # one-shot driver notice landing mid-drain: preserve it for
                # the next epoch's _run_epoch (it never re-fires)
                self._pending_events.append((tag, payload))
            elif tag == "gang":
                self._on_gang_msg(payload)  # late saves still count
            elif tag == "member_result":
                epoch, rank, value, err = payload
                if epoch == self.membership_epoch and rank in self._members:
                    self._members[rank]["done"] = True
                    if value is not None:
                        self._members[rank]["result"] = value
            elif tag == "nodes" and isinstance(payload, dict) \
                    and payload.get("event") == "dead":
                # another node died while draining: mark its ranks lost
                for r, m in self._members.items():
                    if (m["node"] is not None
                            and m["node"].hex() == payload.get("node_id")):
                        m["lost"] = True
        self._cancel_members()

    def _cancel_members(self) -> None:
        for m in self._members.values():
            if not (m["done"] or m["lost"]):
                try:
                    ray_tpu.cancel(m["ref"], force=True)
                except Exception:
                    pass


def run_elastic(
    train_fn,
    *,
    config: dict | None = None,
    elastic: ElasticConfig | None = None,
    run_config=None,
    max_attempts: int = 3,
):
    """Train with per-attempt elastic sizing: each attempt sizes the gang to
    current capacity; worker failure or preemption triggers a resized retry.
    (The fixed-shape retry surface — for the event-driven, checkpointing
    runtime use ``GangManager``.)"""
    from ray_tpu.train.config import RunConfig, ScalingConfig
    from ray_tpu.train.controller import TrainController

    elastic = elastic or ElasticConfig()
    policy = ElasticScalingPolicy(elastic)
    policy.validate()
    last = None
    for attempt in range(max_attempts):
        n = policy.workers_for_next_attempt()
        scaling = ScalingConfig(
            num_workers=n, resources_per_worker=elastic.resources_per_worker
        )
        controller = TrainController(
            train_fn, dict(config or {}, _elastic_attempt=attempt, _num_workers=n),
            scaling, run_config or RunConfig(name="elastic"),
        )
        last, _kind = controller._run_attempt(n)
        if last.error is None:
            return last
        get_preemption_handler().clear()
    return last
