"""Collective communication API.

Two planes, mirroring the reference's split (SURVEY §2.6):

1. **Device plane** — `DeviceCollectiveGroup`: the TPU-native replacement for
   ray.util.collective's NCCL groups (util/collective/collective_group/
   nccl_collective_group.py:126). Operations are jax/XLA collectives over a mesh
   axis; inside jit/shard_map they lower to ICI all-reduce/all-gather/ppermute.
   There is no communicator bootstrap (NCCL ids etc.) — the mesh IS the group.

2. **Host plane** — `HostCollectiveGroup`: actor-based barrier/broadcast used for
   control coordination (reference: train/collective/collectives.py:16
   broadcast_from_rank_zero, :59 barrier; sync_actor.py). Built on a named
   coordinator actor in the ray_tpu runtime.
"""

from __future__ import annotations

import threading
from typing import Any

import jax
import jax.numpy as jnp


class DeviceCollectiveGroup:
    """Collectives bound to a mesh axis; usable inside shard_map bodies.

    API parity with ray.util.collective (collective.py:149 init_collective_group,
    allreduce/allgather/reducescatter/broadcast/send/recv) — but declarative: ops
    are traced into the XLA program rather than issued imperatively.
    """

    def __init__(self, axis_name: str):
        self.axis_name = axis_name

    def allreduce(self, x, op: str = "sum"):
        if op == "sum":
            return jax.lax.psum(x, self.axis_name)
        if op == "max":
            return jax.lax.pmax(x, self.axis_name)
        if op == "min":
            return jax.lax.pmin(x, self.axis_name)
        if op == "mean":
            return jax.lax.pmean(x, self.axis_name)
        raise ValueError(f"Unsupported reduce op: {op}")

    def allgather(self, x, axis: int = 0, tiled: bool = True):
        return jax.lax.all_gather(x, self.axis_name, axis=axis, tiled=tiled)

    def reducescatter(self, x, axis: int = 0):
        return jax.lax.psum_scatter(x, self.axis_name, scatter_dimension=axis, tiled=True)

    def broadcast(self, x, root: int = 0):
        idx = jax.lax.axis_index(self.axis_name)
        size = jax.lax.psum(1, self.axis_name)
        # select root's value: zero out non-root then sum
        contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
        return jax.lax.psum(contrib, self.axis_name)

    def all_to_all(self, x, split_axis: int, concat_axis: int):
        return jax.lax.all_to_all(
            x, self.axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
        )

    def permute(self, x, perm: list[tuple[int, int]]):
        return jax.lax.ppermute(x, self.axis_name, perm)

    def send_recv_ring(self, x, shift: int = 1):
        size = jax.lax.psum(1, self.axis_name)
        # static perms require concrete size at trace time via axis env
        raise_if_dynamic = None
        del raise_if_dynamic
        n = _static_axis_size(self.axis_name)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return jax.lax.ppermute(x, self.axis_name, perm)

    def rank(self):
        return jax.lax.axis_index(self.axis_name)

    def size(self):
        return jax.lax.psum(1, self.axis_name)


def _static_axis_size(axis_name: str) -> int:
    try:
        return jax.lax.psum(1, axis_name)  # concrete under shard_map closed mesh
    except Exception as e:  # pragma: no cover
        raise RuntimeError(f"Axis {axis_name} not in scope") from e


# ---------------------------------------------------------------- host plane
class _Coordinator:
    """Rendezvous actor: barriers + rank-0 broadcast (reference: sync_actor.py)."""

    def __init__(self, world_size: int):
        self.world_size = world_size
        self._barrier_gen = 0
        self._barrier_count = 0
        self._cv = threading.Condition()
        self._values: dict[str, Any] = {}

    def barrier(self, timeout: float = 60.0) -> bool:
        with self._cv:
            gen = self._barrier_gen
            self._barrier_count += 1
            if self._barrier_count >= self.world_size:
                self._barrier_count = 0
                self._barrier_gen += 1
                self._cv.notify_all()
                return True
            ok = self._cv.wait_for(lambda: self._barrier_gen > gen, timeout)
            return ok

    def put_value(self, key: str, value: Any) -> None:
        with self._cv:
            self._values[key] = value
            self._cv.notify_all()

    def get_value(self, key: str, timeout: float = 60.0) -> Any:
        with self._cv:
            ok = self._cv.wait_for(lambda: key in self._values, timeout)
            if not ok:
                raise TimeoutError(f"broadcast key {key!r} never arrived")
            return self._values[key]


class HostCollectiveGroup:
    """Host-side barrier/broadcast across a gang of train workers."""

    def __init__(self, name: str, world_size: int, rank: int):
        import ray_tpu

        self.name = name
        self.world_size = world_size
        self.rank = rank
        coordinator_name = f"_collective_{name}"
        # barrier() blocks inside the actor until all ranks arrive, so the actor
        # needs one execution lane per rank (plus slack for broadcast gets).
        actor_cls = ray_tpu.remote(num_cpus=0, max_concurrency=2 * world_size + 1)(_Coordinator)
        self._coord = actor_cls.options(
            name=coordinator_name, get_if_exists=True
        ).remote(world_size)

    def barrier(self, timeout: float = 60.0) -> None:
        import ray_tpu

        ok = ray_tpu.get(self._coord.barrier.remote(timeout), timeout=timeout + 5)
        if not ok:
            raise TimeoutError(f"barrier '{self.name}' timed out")

    def broadcast_from_rank_zero(self, key: str, value: Any = None, timeout: float = 60.0) -> Any:
        """Reference: train/collective/collectives.py:16."""
        import ray_tpu

        if self.rank == 0:
            ray_tpu.get(self._coord.put_value.remote(key, value))
            return value
        return ray_tpu.get(self._coord.get_value.remote(key, timeout), timeout=timeout + 5)


def init_collective_group(world_size: int, rank: int, group_name: str = "default") -> HostCollectiveGroup:
    """API parity with ray.util.collective.init_collective_group (collective.py:149)."""
    return HostCollectiveGroup(group_name, world_size, rank)
