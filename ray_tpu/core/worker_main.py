"""Worker process entrypoint: `python -m ray_tpu.core.worker_main`.

Parity: python/ray/_private/workers/default_worker.py:203 — workers are exec'd
fresh (never forked from the multi-threaded driver), wired to the parent over
an inherited socketpair fd, and attach the node's shared-memory object store
by name.

TPU discipline: a chip belongs to ONE process, by default the driver. Workers
therefore pin JAX to CPU unless opted into TPU with RAY_TPU_WORKER_TPU=1.
"""

from __future__ import annotations

import argparse
import os


def _pin_worker_jax() -> None:
    if os.environ.get("RAY_TPU_WORKER_TPU") == "1":
        return
    os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--fd", type=int, required=True)
    parser.add_argument("--shm-name", default=None)
    parser.add_argument("--shm-size", type=int, default=0)
    parser.add_argument("--head", default=None, help="host:port of the head control plane")
    parser.add_argument("--token", default=None)
    args = parser.parse_args()

    _pin_worker_jax()

    import os as _os

    if _os.environ.get("RAY_TPU_SESSION_DIR"):
        # join the session's export-event pipeline: workers write their own
        # batched profile events (reference: worker-side TaskEventBuffer)
        try:
            from ray_tpu._private import export_events

            export_events.configure(_os.environ["RAY_TPU_SESSION_DIR"],
                                    owner=False)
        except Exception:
            pass

    try:
        # adopt the driver's tracing opt-in (enable_tracing() stamps the env
        # the spawner copies) so propagated span contexts are recorded here
        from ray_tpu.util import tracing

        tracing.enable_from_env()
    except Exception:
        pass

    try:
        # out-of-band profiler target (ISSUE 13): the node agent triggers an
        # in-process stack sample with a signal — reaches this worker even
        # when its executor is wedged in a lock (a remote task cannot)
        from ray_tpu.util import stack_sampler

        stack_sampler.install()
    except Exception:
        pass

    from multiprocessing.connection import Connection

    conn = Connection(args.fd)
    if args.head:
        # Install a client runtime so user code inside tasks can call
        # ray_tpu.get/put/remote (nested submission through the head).
        try:
            from ray_tpu.core.client_runtime import install_client_runtime

            host, _, port = args.head.rpartition(":")
            install_client_runtime(host, int(port), args.token, args.shm_name, args.shm_size)
        except Exception:
            pass

    from ray_tpu.core.process_pool import _worker_main

    _worker_main(conn, args.shm_name, args.shm_size)


if __name__ == "__main__":
    main()
