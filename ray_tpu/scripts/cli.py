"""ray-tpu CLI.

Parity: python/ray/scripts/scripts.py (`ray status/summary/timeline/list/
job submit`) — argparse instead of click (not in the base image's guarantees).
"""

from __future__ import annotations

import argparse
import json
import sys


def _init_session(args):
    import ray_tpu

    # --address attaches to a RUNNING head as a client (the only way CLI
    # commands can see that head's live state — a bare init() would start a
    # fresh in-process runtime with empty tables)
    addr = getattr(args, "address", None)
    if addr:
        ray_tpu.init(address=addr, token=getattr(args, "token", None),
                     ignore_reinit_error=True)
    else:
        ray_tpu.init(num_cpus=args.num_cpus, ignore_reinit_error=True)


def cmd_status(args) -> int:
    import ray_tpu

    _init_session(args)
    total = ray_tpu.cluster_resources()
    avail = ray_tpu.available_resources()
    print("== ray_tpu status ==")
    print(f"nodes: {len(ray_tpu.nodes())}")
    for k in sorted(total):
        print(f"  {k}: {avail.get(k, 0):.1f}/{total[k]:.1f} available")
    # `ray status` parity: pending demand with an infeasible-vs-waiting
    # verdict per shape (head-local tables; a client attach skips it)
    try:
        from ray_tpu.util import state

        asv = state.autoscaler_status_view()
    except Exception:
        return 0
    print("\nDemand:")
    if not asv["pending_shapes"]:
        print("  (no pending resource demand)")
    for g in asv["pending_shapes"]:
        shape = ", ".join(f"{k}: {v:g}" for k, v in sorted(g["shape"].items()))
        print(f"  {{{shape}}} x {g['count']}  [{g['source']}]  "
              f"{g['status'].upper()}")
        print(f"    {g['reason']}")
    if asv["standing_demand"]:
        print(f"  standing demand entries: {len(asv['standing_demand'])}")
    return 0


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TiB"


def cmd_memory(args) -> int:
    """Cluster memory anatomy (`ray memory` parity): where the bytes live,
    who made them, what still references them, what looks leaked."""
    from ray_tpu.util import state

    _init_session(args)
    try:
        view = state.cluster_memory_view(limit=args.limit)
    except RuntimeError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    rows = view["objects"]
    sort_key = {"size": lambda r: -r["size_bytes"],
                "age": lambda r: -r["age_s"],
                "copies": lambda r: -r["copies"]}[args.sort_by]
    rows = sorted(rows, key=sort_key)
    print("== cluster memory ==")
    total_bytes = sum(r["size_bytes"] for r in rows)
    print(f"objects: {len(rows)}  bytes: {_fmt_bytes(total_bytes)}")
    if args.group_by:
        group_key = {
            "creator": lambda r: f"{r['creator_kind']}:{r['creator']}",
            "node": lambda r: ",".join(r["nodes"]) or "?",
            "state": lambda r: r["ref_state"],
        }[args.group_by]
        groups: dict = {}
        for r in rows:
            g = groups.setdefault(group_key(r),
                                  {"objects": 0, "bytes": 0, "pinned": 0})
            g["objects"] += 1
            g["bytes"] += r["size_bytes"]
            g["pinned"] += 1 if r["pinned"] else 0
        print(f"\n  {'group':<40} {'objects':>8} {'bytes':>10} {'pinned':>7}")
        for name, g in sorted(groups.items(), key=lambda kv: -kv[1]["bytes"]):
            print(f"  {name[:40]:<40} {g['objects']:>8} "
                  f"{_fmt_bytes(g['bytes']):>10} {g['pinned']:>7}")
    else:
        hdr = (f"  {'object_id':<18} {'size':>10} {'copies':>6} {'pin':>4} "
               f"{'refs':>5} {'age':>8} {'creator':<24} nodes")
        print("\n" + hdr)
        for r in rows:
            pin = "yes" if r["pinned"] else "-"
            flag = " LEAK?" if r["leak_suspect"] else ""
            print(f"  {r['object_id'][:16] + '..':<18} "
                  f"{_fmt_bytes(r['size_bytes']):>10} {r['copies']:>6} "
                  f"{pin:>4} {r['ref_count']:>5} {r['age_s']:>7.1f}s "
                  f"{(r['creator_kind'] + ':' + r['creator'])[:24]:<24} "
                  f"{','.join(n[:8] for n in r['nodes'])}{flag}")
    if view["nodes"]:
        print("\nPer-node stores:")
        for n, agg in sorted(view["nodes"].items()):
            used = agg.get("store_used")
            cap = agg.get("store_capacity")
            occ = (f"  store {_fmt_bytes(used)}/{_fmt_bytes(cap)}"
                   if used is not None and cap else "")
            print(f"  {n[:16]:<16} objects={agg['objects']} "
                  f"bytes={_fmt_bytes(agg['bytes'])} "
                  f"pinned={_fmt_bytes(agg['pinned_bytes'])}{occ}")
    if view["leak_suspects"]:
        print("\nLeak suspects (sealed, unreferenced past grace):")
        for r in view["leak_suspects"]:
            print(f"  {r['object_id'][:16]}..  {_fmt_bytes(r['size_bytes'])}"
                  f"  creator={r['creator_kind']}:{r['creator']}"
                  f"  nodes={','.join(n[:8] for n in r['nodes'])}")
    else:
        print("\nNo leak suspects.")
    return 0


def cmd_list(args) -> int:
    from ray_tpu.util import state

    _init_session(args)
    fn = {
        "tasks": state.list_tasks,
        "actors": state.list_actors,
        "nodes": state.list_nodes,
        "objects": state.list_objects,
        "placement-groups": state.list_placement_groups,
    }[args.resource]
    print(json.dumps(fn(), indent=2, default=str))
    return 0


def cmd_summary(args) -> int:
    from ray_tpu.util import state

    _init_session(args)
    fn = {"tasks": state.summarize_tasks, "actors": state.summarize_actors}[args.resource]
    print(json.dumps(fn(), indent=2))
    return 0


def cmd_timeline(args) -> int:
    from ray_tpu.util import state

    _init_session(args)
    out = args.output or "timeline.json"
    state.timeline(out)
    print(f"Wrote Chrome trace to {out} (open chrome://tracing)")
    return 0


def cmd_debug(args) -> int:
    """List active remote-pdb sessions; attach to one (reference: ray debug)."""
    from ray_tpu.util import rpdb

    _init_session(args)
    sessions = rpdb.list_sessions()
    if not sessions:
        print("no active debugger sessions")
        return 0
    target = None
    if args.session_id:
        target = next((s for s in sessions if s["id"] == args.session_id), None)
        if target is None:
            print(f"unknown session {args.session_id}")
    elif len(sessions) == 1:
        target = sessions[0]
    if target is None:
        for s in sessions:
            print(f"{s['id']}  pid={s['pid']}  {s['host']}:{s['port']}  "
                  f"{s['reason']}")
        return 0
    print(f"attaching to {target['id']} ({target['reason']}) — "
          "'c' continues the task, Ctrl-D detaches")
    rpdb.attach(target)
    return 0


def cmd_job_submit(args) -> int:
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    parts = args.entrypoint
    if parts and parts[0] == "--":
        parts = parts[1:]
    if not parts:
        print("error: no entrypoint given", file=sys.stderr)
        return 2
    job_id = client.submit_job(entrypoint=" ".join(parts))
    print(f"Submitted {job_id}")
    if args.wait:
        status = client.wait_until_finished(job_id, timeout=args.timeout)
        print(client.get_job_logs(job_id), end="")
        print(f"Job {job_id}: {status.value}")
        return 0 if status.value == "SUCCEEDED" else 1
    return 0


def _session_file() -> str:
    """Per-user, 0700 session dir: the file holds the control-plane token, so
    it must not be world-readable (and concurrent users must not collide)."""
    import os

    d = os.path.join(os.path.expanduser("~"), ".ray_tpu")
    os.makedirs(d, mode=0o700, exist_ok=True)
    return os.path.join(d, "head_session.json")


def cmd_start(args) -> int:
    """`ray start`-equivalent (reference: scripts.py ray start --head/--address).

    --head: run a standalone head (control plane + scheduler) this process;
    prints the join command for other hosts and the attach address for
    drivers, then blocks until SIGINT/SIGTERM.
    --address: join an existing head as a worker node (this IS the remote
    host entrypoint; runs the node agent in the foreground).
    """
    import os
    import signal

    if args.head and args.address:
        print("error: pass --head OR --address, not both", file=sys.stderr)
        return 2
    if args.head:
        # explicit flags override any inherited env (assignment, not setdefault)
        os.environ["RAY_TPU_CONTROL_PLANE_HOST"] = args.host
        os.environ["RAY_TPU_CONTROL_PLANE_PORT"] = str(args.port or 0)
        import ray_tpu
        from ray_tpu.core import runtime as rt_mod

        ray_tpu.init(num_cpus=args.num_cpus, log_to_driver=False)
        rt = rt_mod.get_runtime()
        if rt.control_plane is None:
            print("error: control plane failed to start", file=sys.stderr)
            return 1
        addr = rt.control_plane.address
        if addr.startswith("0.0.0.0:"):
            # advertise a routable address, not the wildcard bind
            import socket

            try:
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.connect(("10.255.255.255", 1))
                ip = s.getsockname()[0]
                s.close()
            except OSError:
                ip = "127.0.0.1"
            addr = f"{ip}:{addr.rsplit(':', 1)[1]}"
        token = rt.control_plane.token
        info = {"address": addr, "token": token, "pid": os.getpid()}
        session_file = _session_file()
        fd = os.open(session_file, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w") as f:
            json.dump(info, f)
        print(f"Head started at {addr}")
        print("Join from another host:")
        print(f"  python -m ray_tpu.scripts.cli start --address {addr} --token {token}")
        print("Attach a driver:")
        print(f"  ray_tpu.init(address={addr!r}, token={token!r})")
        stop = {"flag": False}
        signal.signal(signal.SIGTERM, lambda *a: stop.update(flag=True))
        try:
            while not stop["flag"]:
                import time

                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        ray_tpu.shutdown()
        try:
            os.unlink(session_file)
        except OSError:
            pass
        return 0
    if args.address:
        token = args.token or os.environ.get("RAY_TPU_TOKEN")
        if not token:
            print("error: --token (or RAY_TPU_TOKEN) required to join a head",
                  file=sys.stderr)
            return 2
        from ray_tpu.core.cluster import node_agent_argv

        # cross-host nodes own their object plane; objects move via chunked
        # pulls (core/object_plane.py)
        agent_argv = node_agent_argv(
            args.address, token, num_cpus=float(args.num_cpus or 4),
            name=args.name or "", isolated_plane=True,
        )
        os.execv(sys.executable, agent_argv)
    print("error: pass --head or --address", file=sys.stderr)
    return 2


def cmd_stop(args) -> int:
    """Stop the head started by `start --head` (reference: ray stop)."""
    import os
    import signal

    session_file = _session_file()
    try:
        with open(session_file) as f:
            info = json.load(f)
    except OSError:
        print("No running head session found.")
        return 0
    try:
        os.kill(info["pid"], signal.SIGTERM)
        print(f"Stopped head pid {info['pid']} ({info['address']})")
    except ProcessLookupError:
        print("Head process already gone.")
    try:
        os.unlink(session_file)
    except OSError:
        pass
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ray-tpu", description="TPU-native distributed runtime CLI")
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--address", default=None,
                   help="attach to a running head (host:port) instead of "
                        "starting an in-process session")
    p.add_argument("--token", default=None, help="session token for --address")
    sub = p.add_subparsers(dest="cmd", required=True)

    sub.add_parser("status", help="cluster resource status + pending demand")

    mp = sub.add_parser("memory", help="cluster memory anatomy "
                        "(`ray memory` parity: sizes, copies, owners, leaks)")
    mp.add_argument("--sort-by", choices=["size", "age", "copies"],
                    default="size")
    mp.add_argument("--group-by", choices=["creator", "node", "state"],
                    default=None)
    mp.add_argument("--limit", type=int, default=1000)

    lp = sub.add_parser("list", help="list live state")
    lp.add_argument("resource", choices=["tasks", "actors", "nodes", "objects", "placement-groups"])

    sp = sub.add_parser("summary", help="summarize state")
    sp.add_argument("resource", choices=["tasks", "actors"])

    tp = sub.add_parser("timeline", help="export Chrome trace of task events")
    tp.add_argument("-o", "--output", default=None)

    jp = sub.add_parser("job", help="job submission")
    jsub = jp.add_subparsers(dest="job_cmd", required=True)
    jsp = jsub.add_parser("submit")
    jsp.add_argument("--wait", action="store_true")
    jsp.add_argument("--timeout", type=float, default=300.0)
    jsp.add_argument("entrypoint", nargs=argparse.REMAINDER)

    stp = sub.add_parser("start", help="start a head or join one (ray start equiv)")
    stp.add_argument("--head", action="store_true")
    stp.add_argument("--address", default=None, help="head host:port to join")
    stp.add_argument("--token", default=None)
    stp.add_argument("--host", default="0.0.0.0", help="head bind host")
    stp.add_argument("--port", type=int, default=0, help="head bind port (0=ephemeral)")
    stp.add_argument("--name", default=None, help="node name when joining")

    sub.add_parser("stop", help="stop the head started by `start --head`")

    dp = sub.add_parser("debug", help="list / attach to remote pdb sessions "
                        "(reference: `ray debug`)")
    dp.add_argument("session_id", nargs="?", default=None,
                    help="attach to this session (default: the only one, or list)")

    args = p.parse_args(argv)
    if args.cmd == "start":
        return cmd_start(args)
    if args.cmd == "stop":
        return cmd_stop(args)
    if args.cmd == "status":
        return cmd_status(args)
    if args.cmd == "memory":
        return cmd_memory(args)
    if args.cmd == "list":
        return cmd_list(args)
    if args.cmd == "summary":
        return cmd_summary(args)
    if args.cmd == "timeline":
        return cmd_timeline(args)
    if args.cmd == "job":
        return cmd_job_submit(args)
    if args.cmd == "debug":
        return cmd_debug(args)
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
