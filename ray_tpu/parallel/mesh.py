"""Device mesh management: the TPU-native substrate for all parallelism.

Where the reference delegates intra-model parallelism to engines (SURVEY §2.5) and
provides only gang scheduling + NCCL process groups (python/ray/util/collective/,
train/torch/config.py:144), this framework owns the mesh: every parallel strategy
(dp/fsdp/tp/sp/ep) is an axis of one `jax.sharding.Mesh`, and XLA inserts the
collectives that ride ICI.

Axis convention (order matters — leading axes get the slower links):
  data   — pure data parallel (gradient psum over DCN/ICI)
  pipe   — pipeline parallel (stage-neighbor activation ppermute, lowest
           bandwidth need of any axis, so it rides the slowest links after data)
  fsdp   — data parallel with sharded params/optimizer (ZeRO-3 style all-gather)
  tensor — megatron-style tensor parallel (activations psum within a layer)
  seq    — sequence/context parallel (ring attention over ICI neighbors)
  expert — MoE expert parallel (all_to_all token routing)

Reference hooks being replaced: SlicePlacementGroup (util/tpu.py:420) topology gangs,
MEGASCALE multislice env injection (train/v2/jax/config.py:29-35), TPU topology labels
(_private/accelerators/tpu.py:736).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Optional, Sequence

import numpy as np

AXES = ("data", "pipe", "fsdp", "tensor", "seq", "expert")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout. -1 on `data` means 'absorb remaining devices'."""

    data: int = -1
    pipe: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = dataclasses.asdict(self)
        fixed = math.prod(v for v in sizes.values() if v != -1)
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError("At most one mesh axis may be -1")
        if free:
            if n_devices % fixed != 0:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
            sizes[free[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"Mesh {sizes} needs {math.prod(sizes.values())} devices, have {n_devices}"
            )
        return sizes

    def build(self, devices: Optional[Sequence] = None):
        """Create a jax.sharding.Mesh over `devices` (default: all local devices).

        Device order is kept in hardware-default order so neighboring mesh
        coordinates map to ICI neighbors (jax device order is torus-major on TPU).
        """
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        sizes = self.resolve(len(devices))
        shape = tuple(sizes[a] for a in AXES)
        arr = np.asarray(devices).reshape(shape)
        return Mesh(arr, AXES)


def make_mesh(
    n_devices: int | None = None,
    *,
    data: int = -1,
    pipe: int = 1,
    fsdp: int = 1,
    tensor: int = 1,
    seq: int = 1,
    expert: int = 1,
    devices: Optional[Sequence] = None,
):
    import jax

    if devices is None:
        devices = jax.devices()
        if n_devices is not None and len(devices) < n_devices:
            # never quietly swap in host CPU devices for missing chips; a
            # dry run on virtual devices pins JAX_PLATFORMS=cpu itself
            raise ValueError(
                f"make_mesh({n_devices}) but the {devices[0].platform} backend "
                f"has {len(devices)} device(s)")
    if n_devices is not None:
        devices = devices[:n_devices]
    return MeshSpec(data=data, pipe=pipe, fsdp=fsdp, tensor=tensor, seq=seq,
                    expert=expert).build(devices)


def single_device_mesh():
    """A 1-device mesh with all axes size 1 — lets sharded code run unmodified."""
    return make_mesh(1, data=1)


@dataclasses.dataclass
class SliceInfo:
    """TPU slice identity/topology (reference: TPUAcceleratorManager
    accelerators/tpu.py:345 pod-type discovery, :736 topology labels)."""

    slice_name: str
    pod_type: str  # e.g. v5p-64
    num_slices: int
    slice_id: int
    topology: tuple[int, ...] | None = None

    @staticmethod
    def detect() -> "SliceInfo":
        env = os.environ
        return SliceInfo(
            slice_name=env.get("TPU_WORKER_HOSTNAMES", env.get("HOSTNAME", "local")),
            pod_type=env.get("TPU_ACCELERATOR_TYPE", env.get("ACCELERATOR_TYPE", "unknown")),
            num_slices=int(env.get("MEGASCALE_NUM_SLICES", "1")),
            slice_id=int(env.get("MEGASCALE_SLICE_ID", "0")),
            topology=_parse_topology(env.get("TPU_TOPOLOGY", "")),
        )


def _parse_topology(s: str) -> tuple[int, ...] | None:
    if not s:
        return None
    try:
        return tuple(int(x) for x in s.replace("x", ",").split(","))
    except ValueError:
        return None


def multislice_env(coordinator_address: str, num_slices: int, slice_id: int) -> dict[str, str]:
    """MEGASCALE env for cross-slice (DCN) coordination.

    Reference: train/v2/jax/config.py:29-35 injects exactly these variables before
    jax.distributed.initialize; the stale-env hang trap (config.py:22-35) is avoided
    by always producing the full fresh set (callers must not merge with stale envs).
    """
    return {
        "MEGASCALE_COORDINATOR_ADDRESS": coordinator_address,
        "MEGASCALE_NUM_SLICES": str(num_slices),
        "MEGASCALE_SLICE_ID": str(slice_id),
    }


def dcn_mesh(num_slices: int, ici_axes: "dict[str, int] | None" = None,
             devices: Optional[Sequence] = None):
    """Mesh whose LEADING axis spans slices (DCN) and whose remaining axes
    tile each slice's devices (ICI). Data-parallel gradients reduce over
    'dcn' via the slower cross-slice links while model axes stay inside a
    slice — the standard multislice layout (scaling-book recipe; the
    reference delegates this to the training framework).

    Device order: jax.devices() is process-ordered and multislice gangs
    launch slice-major (train/gang.py run_multislice_gang), so a contiguous
    reshape puts each slice's devices on one 'dcn' row.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    if len(devs) % num_slices:
        raise ValueError(f"{len(devs)} devices not divisible by {num_slices} slices")
    per_slice = len(devs) // num_slices
    ici_axes = dict(ici_axes or {"data": per_slice})
    ici_total = 1
    for n in ici_axes.values():
        ici_total *= n
    if ici_total != per_slice:
        raise ValueError(f"ici axes {ici_axes} != {per_slice} devices/slice")
    arr = np.array(devs).reshape(num_slices, *ici_axes.values())
    return Mesh(arr, ("dcn", *ici_axes.keys()))


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """jax.distributed bootstrap for multi-host (reference:
    train/v2/jax/config.py:60 _setup_jax_distributed_environment)."""
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)


def ici_neighbors(mesh, axis: str) -> tuple[int, int]:
    """(prev, next) ring neighbors of this process's first device along `axis`."""
    size = mesh.shape[axis]
    idx = 0  # single-controller: logical position handled inside shard_map by axis_index
    return ((idx - 1) % size, (idx + 1) % size)
