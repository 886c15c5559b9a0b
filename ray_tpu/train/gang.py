"""Multi-process SPMD gang: per-worker jax.distributed initialization.

Parity: train/v2/jax/config.py:60 (_setup_jax_distributed_environment) — every
train worker is an OS process that calls jax.distributed.initialize against
the rank-0 coordinator, contributing its local devices to ONE global mesh;
MEGASCALE env vars are injected per worker for multislice (config.py:29-35).
On real hardware each gang member owns a TPU host's chips, or its own part
of one host's (_shared_host_chip_env); in CI the members are CPU processes
with virtual devices and the collectives ride Gloo — the same activation
path either way. The launching process must not have initialised a jax
backend on the TPU, or it holds the chips the members need.

Gang members run as runtime tasks (process workers) that each exec a CLEAN
interpreter for the jax work: XLA device-count flags and the TPU platform
choice must be set before jax's first import, and pooled workers may already
hold an initialized jax.
"""

from __future__ import annotations

import os
import pickle
import re
import socket
import subprocess
import sys
import tempfile
from typing import Callable, Optional

import cloudpickle


def _reserve_port() -> "tuple[socket.socket, int]":
    """Bind-and-HOLD an ephemeral coordinator port: the returned socket
    stays bound until the caller closes it at the moment of use, so two
    gang launches on one host can't both be handed the same port (the old
    bind/close/re-bind-later pattern had a TOCTOU window). SO_REUSEADDR
    lets the coordinator re-bind the port immediately after the handoff
    close (no TIME_WAIT stall)."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("", 0))
    return s, s.getsockname()[1]


def _free_port() -> int:
    """Kept for callers that can't hold a socket; prefer _reserve_port —
    this variant re-opens the race it closes."""
    s, port = _reserve_port()
    s.close()
    return port


# Coordinator-bind failure signatures across jax/grpc versions: the rank-0
# child's stderr when another process won the port race.
_BIND_CONFLICT_MARKERS = (
    "address already in use",
    "failed to bind",
    "errno 98",
    "could not bind",
    "bind address",
)


def _is_bind_conflict(err: BaseException) -> bool:
    s = str(err).lower()
    return any(m in s for m in _BIND_CONFLICT_MARKERS)


def _local_ip() -> str:
    """An address other hosts' gang members can reach (multi-node clusters);
    loopback only as a last resort."""
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def _gang_member(rank: int, num_workers: int, coordinator: str,
                 devices_per_worker: int, fn_blob: bytes,
                 env_extra: dict, use_tpu: bool, timeout: float = 600.0) -> bytes:
    """Runtime task: exec a clean interpreter for this gang rank's jax work."""
    payload = {
        "rank": rank,
        "num_workers": num_workers,
        "coordinator": coordinator,
        "fn_blob": fn_blob,
    }
    with tempfile.NamedTemporaryFile(suffix=".in", delete=False) as f:
        f.write(pickle.dumps(payload))
        in_path = f.name
    out_path = in_path + ".out"
    env = dict(os.environ)
    env.update(env_extra or {})
    if use_tpu:
        env["RAY_TPU_WORKER_TPU"] = "1"
        # this task runs in a pool worker, which is pinned to the CPU; a
        # member that inherits the pin trains on the CPU without a word.
        # Naming the platform also makes jax fail if it cannot open a chip.
        env["JAX_PLATFORMS"] = "tpu"
    else:
        env["JAX_PLATFORMS"] = "cpu"
        stripped = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                          env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            stripped + f" --xla_force_host_platform_device_count={devices_per_worker}"
        ).strip()
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [env.get("PYTHONPATH"), pkg_root]))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.train.gang", in_path, out_path],
            env=env, capture_output=True, text=True, timeout=timeout,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"gang rank {rank} failed (rc={proc.returncode}):\n"
                f"{proc.stderr[-2000:]}"
            )
        with open(out_path, "rb") as f:
            return f.read()
    finally:
        for p in (in_path, out_path):
            try:
                os.unlink(p)
            except OSError:
                pass


def _child_main(in_path: str, out_path: str) -> None:
    with open(in_path, "rb") as f:
        payload = pickle.load(f)
    if os.environ.get("RAY_TPU_WORKER_TPU") != "1":
        import jax

        jax.config.update("jax_platforms", "cpu")
        # multi-process CPU collectives need the Gloo backend
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    else:
        import jax
    jax.distributed.initialize(
        payload["coordinator"],
        num_processes=payload["num_workers"],
        process_id=payload["rank"],
    )
    fn = cloudpickle.loads(payload["fn_blob"])
    result = fn(payload["rank"])
    with open(out_path, "wb") as f:
        f.write(cloudpickle.dumps(result))


def _shared_host_chip_env(num_workers: int, devices_per_worker: int) -> list:
    """Per-member libtpu environment when the members of a TPU gang SHARE one
    host's chips. A member that asks for the whole host (the multi-host
    layout: one member a host) needs none. Otherwise every member would
    inherit all of the host's chips and the second to start aborts on
    libtpu's lockfile, so each is bounded to its own: the host's chips are
    cut along their first axis, contiguous chip ids a member, and the
    members' libtpu runtimes are told of each other. Proved on a v5e 2x2
    host with two members of two chips (PR 21); any split that is not of
    that form is refused here rather than left to hang on the chip."""
    from ray_tpu.core.api import _detect_tpu_chips

    host_chips = int(_detect_tpu_chips())
    if (num_workers == 1 or host_chips == 0
            or devices_per_worker >= host_chips):
        return [{}] * num_workers
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS", "")
    try:
        x, y, z = (int(v) for v in bounds.split(","))
    except ValueError:
        x = y = z = 0
    if (x * y * z != host_chips or x % num_workers
            or num_workers * devices_per_worker != host_chips):
        raise RuntimeError(
            f"use_tpu gang: {num_workers} members of {devices_per_worker} "
            f"chips do not tile this host's {host_chips} chips "
            f"(TPU_CHIPS_PER_HOST_BOUNDS={bounds!r}) along their first axis. "
            f"A chip belongs to one process; members that are not each "
            f"bounded to their own chips fail or hang at start-up. Run one "
            f"process over the whole host (single-controller SPMD) instead.")
    ports = [_free_port() for _ in range(num_workers)]
    shared = {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": f"{x // num_workers},{y},{z}",
        "TPU_PROCESS_BOUNDS": f"{num_workers},1,1",
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
    }
    return [{
        **shared,
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(r * devices_per_worker,
                                  (r + 1) * devices_per_worker)),
        "TPU_PROCESS_PORT": str(ports[r]),
        "CLOUD_TPU_TASK_ID": str(r),
    } for r in range(num_workers)]


def run_jax_gang(
    train_fn: Callable[[int], object],
    num_workers: int,
    devices_per_worker: int = 2,
    use_tpu: bool = False,
    num_slices: int = 1,
    slice_id: int = 0,
    coordinator_port: Optional[int] = None,
    timeout: float = 600.0,
) -> list:
    """Run ``train_fn(rank)`` on a gang of ``num_workers`` OS processes that
    share one jax.distributed world (reference: the JaxTrainer worker-group
    backend). Returns each rank's return value, rank-ordered.

    The gang members are submitted as runtime tasks, so worker-crash fault
    tolerance and scheduling apply; each member execs a clean interpreter for
    the jax work (device flags must precede jax's first import)."""
    from ray_tpu.parallel.mesh import multislice_env

    chip_env = (_shared_host_chip_env(num_workers, devices_per_worker)
                if use_tpu else [{}] * num_workers)

    def env_for_rank(rank: int, coordinator: str) -> dict:
        env = dict(chip_env[rank])
        if num_slices > 1:
            env.update(multislice_env(coordinator, num_slices, slice_id))
        return env

    return _launch_gang(
        [cloudpickle.dumps(train_fn)] * num_workers, env_for_rank,
        devices_per_worker, use_tpu, timeout, coordinator_port,
        member_name="jax_gang_member",
    )


def _launch_gang(fn_blobs: list, env_for_rank, devices_per_worker: int,
                 use_tpu: bool, timeout: float,
                 coordinator_port: Optional[int] = None,
                 member_name: str = "jax_gang_member") -> list:
    """Shared launch scaffolding for single- and multi-slice gangs: one
    coordinator, one runtime task per rank, rank-ordered results.

    The coordinator port is RESERVED (socket held, released just before the
    members launch) and a rank-0 bind conflict — some other process grabbed
    the port in the remaining handoff window — retries the whole launch on
    a fresh port instead of failing the gang. An explicitly requested
    ``coordinator_port`` is never silently replaced."""
    import ray_tpu

    num_workers = len(fn_blobs)
    attempts = 1 if coordinator_port else 3
    last_err: BaseException | None = None
    for _attempt in range(attempts):
        if coordinator_port:
            reserved, port = None, coordinator_port
        else:
            reserved, port = _reserve_port()
        coordinator = f"{_local_ip()}:{port}"
        member = ray_tpu.remote(num_cpus=0.1, name=member_name)(_gang_member)
        if reserved is not None:
            reserved.close()  # handoff: rank 0's coordinator binds it next
        refs = [
            member.remote(rank, num_workers, coordinator, devices_per_worker,
                          fn_blobs[rank], env_for_rank(rank, coordinator),
                          use_tpu, timeout)
            for rank in range(num_workers)
        ]
        try:
            blobs = ray_tpu.get(refs, timeout=timeout)
            return [cloudpickle.loads(b) for b in blobs]
        except Exception as e:
            if coordinator_port is None and _is_bind_conflict(e):
                # cancel the failed attempt's survivors BEFORE retrying:
                # ranks 1..N-1 are still blocked in jax.distributed
                # initialize toward a coordinator that will never exist,
                # holding their devices/resources for the whole timeout
                for ref in refs:
                    try:
                        ray_tpu.cancel(ref, force=True)
                    except Exception:
                        pass
                last_err = e  # port raced away in the handoff window
                continue
            raise
    raise RuntimeError(
        f"gang coordinator port collided {attempts} times"
    ) from last_err


def run_multislice_gang(
    train_fn: Callable[[int, int], object],
    num_slices: int,
    hosts_per_slice: int = 1,
    devices_per_host: int = 2,
    use_tpu: bool = False,
    timeout: float = 600.0,
) -> list:
    """Launch a MULTISLICE job: num_slices x hosts_per_slice gang members in
    one jax.distributed world, each with its slice's MEGASCALE env injected
    (reference: get_tpu_coordinator_env_vars util/tpu.py:212 +
    train/v2/jax/config.py:29-35 — the reference builds these vars per slice
    and hands them to worker processes; nothing there launches the slices).

    ``train_fn(slice_id, rank)`` runs on every member. Cross-slice traffic
    rides the 'dcn' mesh axis (parallel.mesh.dcn_mesh); on real TPU the
    MEGASCALE vars configure libtpu's DCN transport, in CI the same code
    shape runs CPU devices over Gloo — identical activation path.
    """
    from ray_tpu.parallel.mesh import multislice_env

    fn_blobs = []
    for s in range(num_slices):
        for _ in range(hosts_per_slice):
            fn_blobs.append(cloudpickle.dumps(
                lambda rank, _fn=train_fn, _s=s: _fn(_s, rank)))

    def env_for_rank(rank: int, coordinator: str) -> dict:
        return multislice_env(coordinator, num_slices, rank // hosts_per_slice)

    return _launch_gang(fn_blobs, env_for_rank, devices_per_host, use_tpu,
                        timeout, member_name="multislice_member")


if __name__ == "__main__":
    _child_main(sys.argv[1], sys.argv[2])
