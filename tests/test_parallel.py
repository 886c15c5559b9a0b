"""Mesh / sharding / collectives / ring attention tests (8 virtual CPU devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial

from ray_tpu.parallel import collectives, sharding as shd
from ray_tpu.parallel.mesh import MeshSpec, make_mesh, multislice_env
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.models import llama


def cpu_mesh(**axes):
    return make_mesh(8, devices=jax.devices("cpu")[:8], **axes)


def test_mesh_spec_resolve():
    assert MeshSpec(data=-1, tensor=2).resolve(8) == dict(
        data=4, pipe=1, fsdp=1, tensor=2, seq=1, expert=1)
    with pytest.raises(ValueError):
        MeshSpec(data=3, tensor=3).resolve(8)


def test_mesh_build_axes():
    mesh = cpu_mesh(data=2, fsdp=2, tensor=2)
    assert mesh.shape == {"data": 2, "pipe": 1, "fsdp": 2, "tensor": 2,
                          "seq": 1, "expert": 1}


def test_multislice_env_complete():
    env = multislice_env("10.0.0.1:8080", 4, 2)
    assert env == {
        "MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.1:8080",
        "MEGASCALE_NUM_SLICES": "4",
        "MEGASCALE_SLICE_ID": "2",
    }


def test_sharding_rules():
    from jax.sharding import PartitionSpec as P

    assert shd.spec_from_logical(("batch", "seq", None)) == P(("data", "fsdp"), "seq", None)
    assert shd.spec_from_logical(("vocab", "embed_fsdp")) == P("tensor", "fsdp")


def test_shard_params_places_on_mesh():
    mesh = cpu_mesh(data=2, fsdp=2, tensor=2)
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    sharded = shd.shard_params(params, llama.logical_axes(cfg), mesh)
    wq = sharded["layers"]["wq"]
    assert wq.sharding.mesh.shape == mesh.shape
    # heads axis (last dim) sharded over tensor
    assert wq.sharding.spec[-1] == "tensor"


def test_device_collectives_in_shard_map():
    from jax.sharding import PartitionSpec as P

    mesh = cpu_mesh(data=8)
    g = collectives.DeviceCollectiveGroup("data")

    def body(x):
        s = g.allreduce(x, "sum")
        gathered = g.allgather(x, axis=0)
        rank = g.rank()
        return s, gathered, rank[None]

    x = jnp.arange(8.0).reshape(8, 1)
    f = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                  out_specs=(P("data", None), P("data", None), P("data")))
    s, gathered, ranks = f(x)
    assert float(s[0, 0]) == 28.0  # sum 0..7 everywhere
    assert gathered.shape == (64, 1)
    assert list(np.asarray(ranks)) == list(range(8))


def test_host_collective_group(ray_start_regular):
    import threading

    import ray_tpu

    results = {}

    def worker(rank):
        grp = collectives.init_collective_group(world_size=3, rank=rank, group_name="g1")
        val = grp.broadcast_from_rank_zero("init", value=("payload" if rank == 0 else None))
        grp.barrier(timeout=20)
        results[rank] = val

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(3)]
    [t.start() for t in threads]
    [t.join(timeout=30) for t in threads]
    assert results == {0: "payload", 1: "payload", 2: "payload"}


def test_ring_attention_matches_dense():
    mesh = cpu_mesh(data=1, seq=8)
    B, S, H, D = 2, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))
    dense = llama.attention(q, k, v, causal=True)
    ring = ring_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring), atol=2e-5)


def test_ring_attention_grads_flow():
    mesh = cpu_mesh(data=1, seq=8)
    B, S, H, D = 1, 32, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))

    def loss(q):
        o = ring_attention(q, q, q, mesh)
        return (o * o).sum()

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0
