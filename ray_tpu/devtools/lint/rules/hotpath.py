"""``hot-path-purity``: ONE declarative registry for every per-event hot
path, replacing the four bespoke checks the old script had grown
(``check_hot_path_instruments``, ``check_kv_transport``'s purity half,
``check_data_streaming_hot_path``, ``check_phase_stamp_hot_path``).

A hot path is declared once in ``HOT_PATHS`` with the contracts it must
keep; adding a new per-event path to the system means appending a
declaration here, not writing a new checker. Contracts available:

- *metric-bind-only*: instruments bind at import/install time; the path
  never constructs or looks one up per event (the PR-8 telemetry
  contract). Optionally no metric RECORDING at all (the BLOB frame
  paths, where a lock per frame is a measured regression).
- *rpc-free*: the path never speaks the wire (call/notify/remote/task
  submission) — data moves over channels/plane pulls.
- *import bans*: the module must not link the control plane.
- *required calls*: load-bearing plumbing that must stay wired (e.g. the
  KV pull must ride ``pull_into``; the worker main must ship phase
  clocks on the done reply).
- *module-level bind*: at least one ``bind()`` assignment at module top
  level (instruments exist before the first event).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from ray_tpu.devtools.lint.core import (
    ProjectCtx, calls_in, callee_name, find_funcs, project_rule)

RULE = "hot-path-purity"

# Metric construction / registry-touching call names that must never run
# per-event on a hot path — instruments bind at import/install time
# (util/metrics.py bind contract, ISSUE-8 telemetry plane).
METRIC_CONSTRUCT_CALLS = {
    "Counter", "Gauge", "Histogram", "bind", "get_metric",
    "registry_snapshot", "wire_snapshot", "prometheus_text",
    "attach_producer",
}
# Any metric recording at all is banned inside the raw BLOB frame paths —
# a lock per frame there is a measured regression (pull metrics live at
# whole-pull granularity in object_plane instead).
METRIC_RECORD_CALLS = {"inc", "observe", "record"}

RPC_CALLS = {"call", "call_async", "notify"}
SUBMIT_CALLS = {"remote", "submit_task"}

CONTROL_PLANE_IMPORTS = (
    "ray_tpu.core.rpc", "ray_tpu.core.runtime", "ray_tpu.core.cluster",
    "ray_tpu.core.client_runtime", "ray_tpu.core.api",
)


@dataclass(frozen=True)
class HotPath:
    file: str
    funcs: tuple = ()            # () = every function in the module
    reason: str = ""             # one line: why this path is hot
    ban_metric_construct: bool = True
    ban_metric_record: bool = False
    ban_rpc: bool = False
    ban_submit: bool = False
    forbid_imports: tuple = ()   # module-level import prefixes
    require_module_bind: bool = False
    # ((func, (one-of-callees...), message), ...) — plumbing that must stay
    require_calls: tuple = ()
    missing_hint: str = ""       # shown when a declared func disappears


HOT_PATHS = (
    # ISSUE-7/8: the compiled-graph actor-resident exec loop. RPC-freedom
    # is dag-loop-rpc-free's job; purity here is bind-at-import metrics.
    HotPath(
        file="ray_tpu/dag/exec_loop.py",
        reason="runs every compiled-graph step; sampled metrics only",
        require_module_bind=True,
        missing_hint="compiled-graph loop renamed?",
    ),
    # ISSUE-5/8: the raw BLOB frame paths — per-FRAME, so even recording
    # through a bound handle (one lock) is a measured regression.
    HotPath(
        file="ray_tpu/core/rpc/peer.py",
        funcs=("_send_blob", "_read_blob"),
        reason="per-frame BLOB send/recv; account at pull granularity",
        ban_metric_record=True,
        missing_hint="BLOB path gone?",
    ),
    HotPath(
        file="ray_tpu/core/object_plane.py",
        funcs=("_h_chunk_raw",),
        reason="per-frame raw-chunk reply; account at pull granularity",
        ban_metric_record=True,
        missing_hint="BLOB path gone?",
    ),
    # ISSUE-11: the KV handoff publish/pull pair (declared since PR 8's
    # contract but previously enforced by a bespoke check).
    HotPath(
        file="ray_tpu/serve/kv_transport.py",
        funcs=("publish", "pull"),
        reason="per-handoff KV page movement",
        require_calls=(
            ("pull", ("pull_into", "pull_into_or_pull"),
             "pull no longer rides pull_into — KV pages must land "
             "zero-copy in the local store"),
            ("publish", ("kv_window",),
             "publish no longer stamps its anatomy KV window — request "
             "ledgers lose the kv_publish phase (serve/anatomy.py)"),
            ("pull", ("kv_window",),
             "pull no longer stamps its anatomy KV window — request "
             "ledgers lose the kv_pull phase (serve/anatomy.py)"),
        ),
        missing_hint="handoff path gone?",
    ),
    # ISSUE-16: serve anatomy stamping — every stamp is ONE bounded-ring
    # append: no instruments (bind or record), no RPC, no task submission
    # on the request path. Recording happens head-side at fold/settle time.
    HotPath(
        file="ray_tpu/serve/anatomy.py",
        funcs=("stamp", "kv_window", "link_kv", "complete", "admit",
               "rid_of", "router_stamp", "replica_dequeue", "drain_since"),
        reason="per-request phase stamps on the serve hot path",
        ban_metric_record=True,
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=CONTROL_PLANE_IMPORTS,
        missing_hint="anatomy stamping API renamed? (update HOT_PATHS)",
    ),
    # ISSUE-16: the stamping sites stay wired — the router marks its
    # decision (compiled dispatch stays ONE channel frame: the stamp is a
    # ring append, not a wire op), the paged engine stamps the first
    # decoded token.
    HotPath(
        file="ray_tpu/serve/controller.py",
        funcs=("_submit_compiled", "submit", "submit_stream"),
        reason="per-request dispatch; anatomy stamps must stay ring-only",
        ban_metric_construct=False,
        require_calls=(
            ("_submit_compiled", ("router_stamp",),
             "compiled dispatch no longer stamps router_decision — "
             "ledgers lose the routing phase on the zero-RPC path"),
            ("submit", ("router_stamp",),
             "per-call dispatch no longer stamps router_decision"),
            ("submit_stream", ("router_stamp",),
             "streaming dispatch no longer stamps router_decision"),
        ),
        missing_hint="router dispatch renamed? (update HOT_PATHS)",
    ),
    # ISSUE-24: the step also clocks itself — one PhaseClock record per
    # decode step and per admission (util/timeline.py). Nothing else may
    # creep into the step: no instrument lookup, no RPC, no task submission.
    HotPath(
        file="ray_tpu/serve/llm_paged.py",
        funcs=("_step_decode", "_enqueue", "_emit", "_admit_one", "_decode_clock"),
        reason="per-step decode loop and per-request admission; the "
               "first-token stamp and the phase record are ring appends",
        ban_rpc=True,
        ban_submit=True,
        require_calls=(
            ("_emit", ("stamp",),
             "_emit no longer stamps decode_first_token — PD "
             "ledgers lose the first-token phase and TTFT degrades to "
             "completion time"),
            ("_step_decode", ("_decode_clock",),
             "_step_decode no longer clocks its phases — the engine/decode "
             "timeline records and the engine:decode.* annotations go dark"),
            ("_admit_one", ("clock",),
             "_admit_one no longer clocks its phases — the engine/admit "
             "timeline records and the engine:admit.* annotations go dark"),
        ),
        missing_hint="paged engine step renamed? (update HOT_PATHS)",
    ),
    # ISSUE-12: streaming data plane pump / fetch / task bodies. May submit
    # tasks and get objects through the public API (which owns
    # retry/failover) but never speaks the wire directly.
    HotPath(
        file="ray_tpu/data/streaming.py",
        funcs=("_drive_op", "fetch_block", "_prefetch_pump", "__next__",
               "_transform_to_plane", "_slice_to_plane"),
        reason="per-block streaming pump/fetch loops",
        ban_rpc=True,
        forbid_imports=("ray_tpu.core.rpc",),
        missing_hint="streaming pump/pull loop renamed? (update HOT_PATHS)",
    ),
    HotPath(
        file="ray_tpu/data/exchange.py",
        funcs=("_reduce_partition", "_map_partition", "_pull_slices"),
        reason="per-partition shuffle task bodies",
        ban_rpc=True,
        forbid_imports=("ray_tpu.core.rpc",),
        require_calls=(
            ("_map_partition", ("put",),
             "_map_partition no longer seals slices via ray_tpu.put — "
             "slices must stay in the mapper's node store"),
            ("_reduce_partition", ("get", "_pull_slices"),
             "_reduce_partition no longer pulls its own slices — reducers "
             "must resolve slices through the plane failover path "
             "themselves"),
        ),
        missing_hint="shuffle task body renamed? (update HOT_PATHS)",
    ),
    # ISSUE-15: the cross-node compiled-graph edge bridge. Per-FRAME path:
    # metric-free entirely (bind-only would still take a lock per frame),
    # no task submission, no control-plane linkage — its ONLY wire traffic
    # is the persistent dag_ch_* ops on count_ops=False data peers, and
    # host-side reads must leave as raw BLOB frames (the PR-5 sendmsg
    # path). The zero-control-plane steady-state assert rests on this.
    HotPath(
        file="ray_tpu/dag/fabric.py",
        funcs=("read_view", "write", "_h_read", "_h_write", "_poll"),
        reason="per-frame cross-node compiled-graph edge traffic",
        ban_metric_record=True,
        ban_submit=True,
        forbid_imports=("ray_tpu.core.runtime", "ray_tpu.core.cluster",
                        "ray_tpu.core.client_runtime", "ray_tpu.core.api"),
        require_calls=(
            ("_h_read", ("RawReply",),
             "fabric reads no longer answer with raw BLOB frames — the "
             "zero-copy sendmsg reply path is the bridge's contract"),
            ("_poll", ("call_async",),
             "the reader no longer pipelines its long-polls (prefetch) — "
             "each hop would pay exec + RTT + producer instead of max()"),
        ),
        missing_hint="cross-node edge bridge renamed? (update HOT_PATHS)",
    ),
    # ISSUE-13: worker phase stamping — ring append under one lock; no
    # instruments, no RPC. export() may link the runtime; the recording
    # half may not.
    HotPath(
        file="ray_tpu/util/timeline.py",
        funcs=("phase_reply", "stamp_task_phases", "record_span",
               "drain_since",
               # PhaseClock (ISSUE-24): runs inside every decode step;
               # PhaseLoop (ISSUE-35) chains the engine thread's records
               "mark", "stop", "close", "_open", "_end_phase", "clock",
               "_closed", "rest", "profiling"),
        reason="per-task phase stamp on the worker exec path; the serving "
               "engine's per-step phase clock",
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=tuple(m for m in CONTROL_PLANE_IMPORTS
                             if m != "ray_tpu.core.runtime"),
        missing_hint="phase recording path renamed? (update HOT_PATHS)",
    ),
    # ISSUE-35: a stream's cell is written once a token by every stage of
    # the token's way to the socket and scanned once a decode step.
    HotPath(
        file="ray_tpu/serve/stream_cell.py",
        funcs=("stream_cell", "counts"),
        reason="per-token counts of the streams' stages; the engine thread's "
               "per-step scan of them",
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=CONTROL_PLANE_IMPORTS,
        missing_hint="stream cell API renamed? (update HOT_PATHS)",
    ),
    # ISSUE-17: the front door's ingress dispatch fast path. Per-REQUEST:
    # route lookup, replica pick, and the admission predictor read ONLY the
    # local routing-epoch cache — zero control-plane RPCs, no task
    # submission, no per-request instruments. Fleet management (_spawn,
    # _ensure, _drop) is deliberately NOT declared: it may submit actors.
    # Note: this PR added NO new wire ops — the retained-epoch replay rides
    # the existing pubsub_msg notify frame, so the rpc/schema baseline and
    # version gate are untouched by design.
    HotPath(
        file="ray_tpu/serve/front_door.py",
        funcs=("_refresh", "pick", "_lookup", "_predict", "_admit"),
        reason="per-request ingress dispatch; local epoch cache only",
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=CONTROL_PLANE_IMPORTS,
        require_calls=(
            ("_refresh", ("snapshot",),
             "_refresh no longer reads the local epoch cache — routing "
             "state must come from the last applied epoch, not a "
             "controller poll"),
            ("pick", ("wait_newer",),
             "pick no longer waits on the epoch condition variable — "
             "empty replica sets must block on the NEXT epoch, not "
             "sleep-poll the controller"),
            ("_admit", ("try_admit",),
             "_admit no longer consults the admission gate — requests "
             "reach anatomy.admit ungated and SLO breaches stop shedding"),
        ),
        missing_hint="ingress fast path renamed? (update HOT_PATHS)",
    ),
    # ISSUE-17: admission decisions stay pure + accounted. The gate runs
    # per request BEFORE anatomy.admit; it must never speak the wire, and
    # every shed must land on the shed counter + flight ring.
    HotPath(
        file="ray_tpu/serve/admission.py",
        funcs=("decide", "try_admit", "_shed"),
        reason="per-request admission gate ahead of anatomy.admit",
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=CONTROL_PLANE_IMPORTS,
        require_calls=(
            ("try_admit", ("decide",),
             "try_admit no longer routes through the pure decide() table — "
             "the policy must stay one tested function"),
            ("_shed", ("record_shed",),
             "_shed no longer records through anatomy.record_shed — "
             "ray_tpu_serve_shed_total and the flight-ring shed events "
             "go dark"),
        ),
        missing_hint="admission gate renamed? (update HOT_PATHS)",
    ),
    # ISSUE-18: the plane-store accounting ledger. Every _led_* update is
    # ONE dict operation under the ledger lock on the put/seal/pull path:
    # no instruments (the store gauges are producer-attached at import),
    # no RPC (reports ride the existing metrics_push beat), no control-
    # plane linkage — and the lifecycle hooks must STAY wired, or the
    # cluster memory view silently goes blind.
    HotPath(
        file="ray_tpu/core/shm_store.py",
        funcs=("_led_seal", "_led_pin", "_led_release", "_led_drop",
               "_led_access", "_led_mark_secondary", "_led_finish_seal",
               "put_bytes", "put_parts", "seal", "pin", "release",
               "delete", "get_bytes"),
        reason="per-object plane-store ledger on the put/seal/pull path",
        ban_metric_record=True,
        ban_rpc=True,
        ban_submit=True,
        forbid_imports=CONTROL_PLANE_IMPORTS,
        require_calls=(
            ("put_bytes", ("_led_seal",),
             "put_bytes no longer ledgers its seal — sealed objects "
             "vanish from cluster_memory_view"),
            ("put_parts", ("_led_seal",),
             "put_parts no longer ledgers its seal — vectored puts "
             "vanish from cluster_memory_view"),
            ("seal", ("_led_finish_seal",),
             "seal no longer finishes its pending ledger row — pulled "
             "copies vanish from cluster_memory_view"),
            ("pin", ("_led_pin",),
             "pin no longer ledgers — pinned bytes read as evictable in "
             "the memory view and the pinned gauge goes dark"),
            ("get_bytes", ("_led_access",),
             "get_bytes no longer stamps last-access — idle-age leak "
             "triage goes blind"),
        ),
        missing_hint="store ledger renamed? (update HOT_PATHS)",
    ),
    # ISSUE-13: both halves of the stamping pipeline stay wired — the
    # worker ships clocks on the done reply, the pool parent stamps them.
    HotPath(
        file="ray_tpu/core/process_pool.py",
        funcs=("_worker_main", "_reply_reader"),
        reason="phase-clock transport across the pool pipe",
        ban_metric_construct=False,
        require_calls=(
            ("_worker_main", ("phase_reply",),
             "_worker_main no longer ships phase clocks on the done "
             "reply — worker timeline lanes go dark"),
            ("_reply_reader", ("stamp_task_phases",),
             "_reply_reader no longer stamps worker phase clocks into the "
             "parent's timeline ring"),
        ),
        missing_hint="pool pipe path renamed? (update HOT_PATHS)",
    ),
)


def evaluate_hot_path(ctx, spec: HotPath) -> list:
    out = []
    rel = spec.file
    fctx = ctx.get(rel)
    if fctx is None:
        hint = spec.missing_hint or "hot path gone?"
        return [ctx.finding(RULE, rel, 0, f"{rel} missing — {hint}",
                            "missing-module")]
    tree = fctx.tree

    # module-level import bans
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names]
            mods.append(getattr(node, "module", "") or "")
            for m in mods:
                if any(m == f or m.startswith(f + ".")
                       for f in spec.forbid_imports):
                    out.append(ctx.finding(
                        RULE, rel, node.lineno,
                        f"imports {m} — this hot-path module must not link "
                        "the wire/control plane", f"import:{m}"))

    # module-level bind requirement
    if spec.require_module_bind:
        top_binds = 0
        for node in tree.body:
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    callee_name(node.value) == "bind":
                top_binds += 1
        if top_binds == 0:
            out.append(ctx.finding(
                RULE, rel, 0,
                "no module-level instrument bind() — hot-loop metrics must "
                "be bound at import time, not per event", "no-module-bind"))

    # per-function bans
    banned = set()
    if spec.ban_metric_construct:
        banned |= METRIC_CONSTRUCT_CALLS
    if spec.ban_metric_record:
        banned |= METRIC_RECORD_CALLS
    if spec.ban_rpc:
        banned |= RPC_CALLS
    if spec.ban_submit:
        banned |= SUBMIT_CALLS

    if spec.funcs:
        fns = find_funcs(tree, set(spec.funcs))
    else:
        fns = {n.name: n for n in ast.walk(tree)
               if isinstance(n, ast.FunctionDef)}
    for fname in sorted(spec.funcs or fns):
        fn = fns.get(fname)
        if fn is None:
            out.append(ctx.finding(
                RULE, rel, 0,
                f"hot function {fname} missing — "
                f"{spec.missing_hint or 'update HOT_PATHS'}",
                f"missing:{fname}"))
            continue
        for lineno, callee in calls_in(fn, banned):
            if callee in METRIC_CONSTRUCT_CALLS:
                why = ("instruments bind at import/install time, never "
                       "per event")
            elif callee in METRIC_RECORD_CALLS:
                why = ("this per-frame path must stay metric-free — a "
                       "lock per frame is a measured regression; account "
                       "at coarser granularity")
            else:
                why = ("this hot path is RPC-free — data moves over "
                       "channels/plane pulls; control traffic goes "
                       "through the public API")
            out.append(ctx.finding(
                RULE, rel, lineno,
                f"{fname} calls {callee}() — {why}",
                f"{fname}:calls:{callee}"))
    # load-bearing plumbing that must stay
    for fname, one_of, msg in spec.require_calls:
        fn = fns.get(fname)
        if fn is not None and not calls_in(fn, set(one_of)):
            out.append(ctx.finding(RULE, rel, fn.lineno, msg,
                                   f"{fname}:requires:{'|'.join(one_of)}"))
    return out


def hot_path_findings(ctx, files=None) -> list:
    out = []
    for spec in HOT_PATHS:
        if files is not None and spec.file not in files:
            continue
        out.extend(evaluate_hot_path(ctx, spec))
    return out


@project_rule(RULE,
              doc="declared hot paths keep their purity contracts: "
                  "bind-only metrics, RPC-free bodies, required plumbing "
                  "(see HOT_PATHS — add new per-event paths there)")
def _hot_path_rule(ctx: ProjectCtx) -> list:
    return hot_path_findings(ctx)
