"""Which platform a computation will run on: the one kernel-or-dense decision.

Pallas kernels compile only for the TPU; everywhere else they run in
interpret mode (tests) or the caller takes the dense XLA path. Every site
that has to choose asks here, and the answer comes from where the
computation is PLACED — the mesh it is sharded over, or the devices of the
concrete arrays handed to it — not from the process's default device. An
ahead-of-time compile for a TPU topology from a CPU-only host therefore
takes the kernel branch, and a CPU mesh in a process that also holds a chip
takes the dense one.
"""

from __future__ import annotations

import jax


def target_platform(*arrays, mesh=None) -> str:
    """Platform of `mesh`'s devices, else of the first concrete array among
    `arrays`, else the process default backend (tracers under a jit that was
    given no mesh carry no placement; jit then runs on the default backend)."""
    if mesh is not None:
        return mesh.devices.flat[0].platform
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return next(iter(a.devices())).platform
    return jax.default_backend()
