"""Multi-process SPMD gang tests (reference: train/v2/jax/config.py — per
worker jax.distributed.initialize; CI analog runs CPU processes with virtual
devices over Gloo collectives)."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train.gang import run_jax_gang


@pytest.fixture(autouse=True)
def _session(ray_start_regular):
    yield


def test_two_process_gang_matches_single_process():
    """VERDICT criterion: a 2-process CPU-device gang trains the tiny llama
    with the same loss as single-process execution."""

    def _tiny_losses(rank: int):
        """Two DP train steps on the tiny llama over the GLOBAL 4-device mesh."""
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ray_tpu.models import llama
        from ray_tpu.train import spmd

        cfg = llama.LlamaConfig.tiny()
        devs = jax.devices()
        assert len(devs) == 4, f"expected 4 global devices, got {len(devs)}"
        mesh = Mesh(np.array(devs).reshape(4, 1, 1, 1, 1),
                    ("data", "fsdp", "tensor", "seq", "expert"))
        state = spmd.init_state(cfg, jax.random.PRNGKey(0),
                                optimizer=spmd.make_optimizer(warmup=1))
        step = spmd.make_train_step(
            cfg, mesh, optimizer=spmd.make_optimizer(warmup=1)
        )(state)
        rng = np.random.default_rng(42)
        full_tokens = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        full_targets = rng.integers(0, cfg.vocab_size, (4, 16)).astype(np.int32)
        sh = NamedSharding(mesh, P(("data", "fsdp"), None))
        per = 4 // jax.process_count()
        lo = jax.process_index() * per

        def to_global(arr):
            return jax.make_array_from_process_local_data(
                sh, np.ascontiguousarray(arr[lo:lo + per]), arr.shape
            )

        losses = []
        for _ in range(3):
            state, metrics = step(state, to_global(full_tokens), to_global(full_targets))
            losses.append(float(metrics["loss"]))
        return losses

    multi = run_jax_gang(_tiny_losses, num_workers=2, devices_per_worker=2,
                         timeout=600)
    assert len(multi) == 2 and multi[0] == pytest.approx(multi[1], rel=1e-6)
    single = run_jax_gang(_tiny_losses, num_workers=1, devices_per_worker=4,
                          timeout=600)
    assert multi[0] == pytest.approx(single[0], rel=1e-5)
    assert multi[0][-1] < multi[0][0]  # it actually trained (post-warmup)


def test_gang_megascale_env_injected():
    def probe(rank: int):
        import os

        return {
            k: os.environ.get(k)
            for k in ("MEGASCALE_COORDINATOR_ADDRESS", "MEGASCALE_NUM_SLICES",
                      "MEGASCALE_SLICE_ID")
        }

    out = run_jax_gang(probe, num_workers=1, devices_per_worker=1,
                       num_slices=2, slice_id=1, timeout=300)
    env = out[0]
    assert env["MEGASCALE_NUM_SLICES"] == "2"
    assert env["MEGASCALE_SLICE_ID"] == "1"
    assert env["MEGASCALE_COORDINATOR_ADDRESS"]


def test_gang_rank_failure_surfaces():
    def boom(rank: int):
        if rank == 1:
            raise RuntimeError("rank 1 exploded")
        return "ok"

    with pytest.raises(Exception, match="rank 1"):
        run_jax_gang(boom, num_workers=2, devices_per_worker=1, timeout=300)


def test_jax_trainer_distributed_gang():
    """JaxConfig(distributed=True) activates the multi-process gang through
    the trainer surface (reference: JaxTrainer + jax config.py:60)."""
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.config import JaxConfig, ScalingConfig

    def loop(rank, config):
        import jax

        assert config["tag"] == "gang-run"
        return {"rank": rank, "procs": jax.process_count(),
                "devices": len(jax.devices())}

    trainer = JaxTrainer(
        loop,
        train_loop_config={"tag": "gang-run"},
        scaling_config=ScalingConfig(num_workers=2),
        jax_config=JaxConfig(distributed=True),
    )
    res = trainer.fit()
    assert res.error is None, res.error
    outs = res.metrics["gang"]
    assert [o["rank"] for o in outs] == [0, 1]
    assert all(o["procs"] == 2 and o["devices"] == 4 for o in outs)


def test_multislice_gang_dcn_mesh():
    """Multislice activation: 2 slices x 1 host in ONE jax.distributed world,
    per-slice MEGASCALE env injected, cross-slice dp over the 'dcn' axis
    (reference: util/tpu.py:212 coordinator env + config.py:29-35 injection)."""
    from ray_tpu.train.gang import run_multislice_gang

    def member(slice_id: int, rank: int):
        import os

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.parallel.mesh import dcn_mesh

        assert os.environ["MEGASCALE_SLICE_ID"] == str(slice_id)
        assert os.environ["MEGASCALE_NUM_SLICES"] == "2"
        mesh = dcn_mesh(2, {"data": 2})
        assert mesh.axis_names == ("dcn", "data") and mesh.devices.shape == (2, 2)
        # a dp reduction spanning BOTH axes: every device contributes its
        # global position; the psum must see all 4 contributions
        sh = NamedSharding(mesh, P(("dcn", "data")))
        x = jax.make_array_from_process_local_data(
            sh, jnp.arange(2) + 2 * jax.process_index(), (4,))

        @jax.jit
        def total(v):
            return v.sum()

        return {"slice_id": slice_id, "rank": rank,
                "sum": float(total(x)),
                "num_devices": len(jax.devices())}

    out = run_multislice_gang(member, num_slices=2, hosts_per_slice=1,
                              devices_per_host=2, timeout=600)
    assert len(out) == 2  # one member per (slice, host)
    for r in out:
        assert r["num_devices"] == 4
        assert r["sum"] == 6.0  # 0+1+2+3 across both slices
    assert sorted(r["slice_id"] for r in out) == [0, 1]


def test_tpu_gang_members_sharing_a_host_get_their_own_chips(monkeypatch):
    """use_tpu members inherit ALL of a host's chips unless bounded; the
    layout proved on a v5e 2x2 host (PR 21) is produced, one member a host
    needs nothing, and any other split raises instead of hanging on the chip."""
    from ray_tpu.core import api
    from ray_tpu.train.gang import _shared_host_chip_env

    monkeypatch.setattr(api, "_detect_tpu_chips", lambda: 4.0)
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    a, b = _shared_host_chip_env(2, 2)
    assert (a["TPU_VISIBLE_CHIPS"], b["TPU_VISIBLE_CHIPS"]) == ("0,1", "2,3")
    assert a["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert a["TPU_PROCESS_BOUNDS"] == "2,1,1"
    assert (a["CLOUD_TPU_TASK_ID"], b["CLOUD_TPU_TASK_ID"]) == ("0", "1")
    assert a["TPU_PROCESS_ADDRESSES"] == b["TPU_PROCESS_ADDRESSES"]
    assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
    assert _shared_host_chip_env(2, 4) == [{}, {}]  # a whole host each
    assert _shared_host_chip_env(1, 2) == [{}]
    with pytest.raises(RuntimeError, match="belongs to one process"):
        _shared_host_chip_env(4, 1)
    monkeypatch.delenv("TPU_CHIPS_PER_HOST_BOUNDS")
    with pytest.raises(RuntimeError, match="belongs to one process"):
        _shared_host_chip_env(2, 2)
