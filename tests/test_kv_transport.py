"""Disaggregated PD KV transport tests (serve/kv_transport.py).

Covers the full handoff lifecycle (ack / TTL / claimant death — leak-free,
pool stats return to baseline), the zero-copy pull contract (tracemalloc +
plane-pull-counter asserted like the PR-5 bulk plane), the engine-level
plane handoff, and the acceptance scenario: a decode worker on a DIFFERENT
node than the prefill worker serving a request end-to-end from pulled KV
pages with exact token parity. Reference analog: the NIXL/RDT KV-transfer
layer between prefill and decode fleets.
"""

import os
import threading
import time
import tracemalloc

import numpy as np
import pytest

import ray_tpu
from ray_tpu.serve.kv_transport import KVHandoffLost, KVTransport


@pytest.fixture
def transports():
    pre = KVTransport(ttl_s=30, store_bytes=64 << 20, node_hint="nodeA")
    dec = KVTransport(ttl_s=30, store_bytes=64 << 20, node_hint="nodeB")
    try:
        yield pre, dec
    finally:
        pre.close()
        dec.close()


def _kv(nbytes_each: int, seed: int = 0):
    n = nbytes_each // 4
    rng = np.random.default_rng(seed)
    # a hand-off's payload as the paged pool holds it: [L, n, block_size, row]
    k = rng.standard_normal(n).astype(np.float32).reshape(1, -1, 4, 4)
    v = rng.standard_normal(n).astype(np.float32).reshape(1, -1, 4, 4)
    return k, v


# ----------------------------------------------------------- lifecycle
def test_publish_pull_ack_roundtrip_leak_free(transports):
    pre, dec = transports
    base_pre = pre.stats()["store"]
    base_dec = dec.stats()["store"]
    k, v = _kv(256 << 10)
    desc = pre.publish(k, v, meta={"req": "r1"})
    assert pre.live_handoffs() == 1 and pre.live_bytes() == desc["nbytes"]
    assert desc["node"] == "nodeA" and desc["meta"] == {"req": "r1"}

    kv, ack = dec.pull(desc)
    np.testing.assert_array_equal(kv["k"], k)
    np.testing.assert_array_equal(kv["v"], v)
    ack()
    assert pre.wait_drained(10), "ack did not free the published handoff"

    # leak-free: both stores return to their baseline occupancy once the
    # decode-side views die (the local secondary copy is pinned by them)
    del kv
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if (pre.stats()["store"]["bytes_in_use"] == base_pre["bytes_in_use"]
                and dec.stats()["store"]["bytes_in_use"]
                == base_dec["bytes_in_use"]):
            break
        time.sleep(0.05)
    assert pre.stats()["store"]["bytes_in_use"] == base_pre["bytes_in_use"]
    assert dec.stats()["store"]["bytes_in_use"] == base_dec["bytes_in_use"]
    assert pre.stats()["store"]["num_objects"] == base_pre["num_objects"]


def test_ttl_reclaims_unpulled_handoff():
    from ray_tpu.util import flight_recorder

    pre = KVTransport(ttl_s=0.3, store_bytes=16 << 20)
    try:
        k, v = _kv(64 << 10)
        pre.publish(k, v)
        assert pre.wait_drained(10), "TTL sweep did not reclaim the handoff"
        recs = [r for r in flight_recorder.records("kv")
                if r["event"] == "handoff_ttl_expired"]
        assert recs, "TTL free not flight-recorded"
        assert pre.stats()["store"]["num_objects"] == 0
    finally:
        pre.close()


def test_claimant_death_frees_handoff(transports):
    """A decode replica that pulled but died before acking must not strand
    the published pages until TTL: its connection drop frees them."""
    from ray_tpu.util import flight_recorder

    pre, dec = transports
    k, v = _kv(64 << 10)
    desc = pre.publish(k, v)
    kv, _ack = dec.pull(desc)
    assert pre.live_handoffs() == 1
    dec._client.close()  # the decode process dies without acking
    assert pre.wait_drained(10), "claimant death did not free the handoff"
    recs = [r for r in flight_recorder.records("kv")
            if r["event"] == "handoff_claimant_died"]
    assert recs, "claimant-death free not flight-recorded"


def test_pull_after_free_raises_handoff_lost(transports):
    pre, dec = transports
    k, v = _kv(64 << 10)
    desc = pre.publish(k, v)
    kv, ack = dec.pull(desc)
    ack()
    assert pre.wait_drained(10)
    del kv
    # the local secondary was deleted on ack; a fresh pull finds no source
    with pytest.raises(KVHandoffLost):
        dec.pull(desc, timeout=5)


def test_close_retires_everything():
    pre = KVTransport(ttl_s=60, store_bytes=16 << 20)
    k, v = _kv(64 << 10)
    pre.publish(k, v)
    pre.publish(k, v)
    assert pre.live_handoffs() == 2
    pre.close()
    assert pre.live_handoffs() == 0


def test_dropped_transport_is_garbage_collected():
    """A transport dropped WITHOUT close() must be GC-able — the TTL
    sweeper thread holds only a weak reference, so __del__ (which runs
    close(): shm arena, plane socket, sweeper) stays reachable. A
    sweeper bound to self would pin every churned replica's 128MB arena
    for the process's life."""
    import gc
    import weakref as wr

    t = KVTransport(ttl_s=0.4, store_bytes=16 << 20)
    sweeper = t._sweeper
    ref = wr.ref(t)
    del t
    gc.collect()
    assert ref() is None, "sweeper (or another thread) pins the transport"
    sweeper.join(timeout=5)
    assert not sweeper.is_alive(), "sweeper thread did not exit after GC"


# ----------------------------------------------------------- zero-copy
def test_pull_zero_copy_no_transient_alloc(transports):
    """Acceptance: the pull path lands KV bytes once, in the decode-side
    store slot — no whole-KV transient buffer (tracemalloc), and the bytes
    ride the plane pull counter (counter-asserted like PR-5/PR-10)."""
    from ray_tpu.util import metrics

    pre, dec = transports
    k, v = _kv(8 << 20, seed=3)  # 16 MB total
    desc = pre.publish(k, v)
    counter = metrics.get_metric("ray_tpu_plane_pull_bytes_total")
    before = sum(counter.snapshot().values()) if counter else 0
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        kv, ack = dec.pull(desc)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    try:
        assert peak < desc["nbytes"] // 2, (
            f"transient peak {peak}B on a {desc['nbytes']}B pull")
        after = sum(counter.snapshot().values())
        assert after - before == desc["nbytes"], (
            "KV bytes did not ride the zero-copy plane pull path")
        np.testing.assert_array_equal(kv["k"], k)
    finally:
        ack()


def test_publish_writes_once_into_store_slot(transports):
    """Publish-side: the gathered pages are written straight into the
    create_for_write slot — no extra whole-KV transient."""
    pre, _dec = transports
    k, v = _kv(8 << 20, seed=5)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        desc = pre.publish(k, v)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < desc["nbytes"] // 2, f"publish transient peak {peak}B"


# ------------------------------------------------- engine-level handoff
def test_engine_plane_handoff_in_process():
    """prefill engine (kv_transfer="plane") -> descriptor -> decode engine:
    token parity with the single-engine baseline, allocator + transport
    return to baseline."""
    import dataclasses

    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine

    mc = llama.LlamaConfig.tiny()
    cfg = PagedLLMConfig(model_config=mc, max_batch_size=4, max_seq_len=128,
                         block_size=16)
    params = llama.init(mc, jax.random.PRNGKey(0))
    prompt = list(range(3, 40))

    pre_t = KVTransport(ttl_s=30)
    dec_t = KVTransport(ttl_s=30)
    pre_e = PagedLLMEngine(dataclasses.replace(cfg, kv_transfer="plane"),
                           params=params)
    pre_e.kv_publish = pre_t.publish
    dec_e = PagedLLMEngine(cfg, params=params)
    dec_e.kv_pull = dec_t.pull
    try:
        pre_base = pre_e.allocator.stats()
        h = pre_e.prefill_extract(prompt)
        assert h["kv"] is None and h["kv_ref"] is not None
        assert h["kv_ref"]["nbytes"] > 0
        # the published entry is the prompt's pages in the pool's own layout,
        # block axis 1: [L, n, block_size, Hkv * Dp]
        assert h["kv_ref"]["k_shape"] == h["kv_ref"]["v_shape"] == [
            mc.num_layers, h["n_prefill_blocks"], *pre_e.pool["k"].shape[2:]]
        assert pre_t.live_handoffs() == 1
        toks = dec_e.attach_sequence(h, 8).result(timeout=120).token_ids
        assert pre_t.wait_drained(10), "attach did not ack the handoff"
        assert pre_e.allocator.stats()["free_blocks"] == \
            pre_base["free_blocks"]

        ref = PagedLLMEngine(cfg, params=params)
        try:
            expect = ref.generate_sync(prompt, 8).token_ids
        finally:
            ref.shutdown()
        assert toks == expect
    finally:
        pre_e.shutdown()
        dec_e.shutdown()
        pre_t.close()
        dec_t.close()


# --------------------------------------------------- 2-node acceptance
def _pd_model_config():
    """Bigger than tiny so the handoff is MBs (meaningful zero-copy
    bounds), still CPU-cheap."""
    from ray_tpu.models import llama

    import jax.numpy as jnp

    return llama.LlamaConfig(
        vocab_size=256, hidden_size=256, intermediate_size=512, num_layers=4,
        num_heads=8, num_kv_heads=4, max_seq_len=512, dtype=jnp.float32,
        remat=False)


def test_pd_cross_node_decode():
    """ACCEPTANCE: a decode worker on a DIFFERENT node/agent serves a
    request end-to-end from KV pages pulled over the object plane —
    zero-transient-copy asserted on the pull path, tokens exact vs the
    co-located baseline, handoff ack-freed on the prefill node."""
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    cluster = Cluster(initialize_head=False)
    # 447 tokens (ids bounded by the 256-token test vocab) -> 28 KV blocks
    # -> a ~1.75 MB handoff, so the transient-alloc bound has real teeth
    prompt = [3 + (i % 200) for i in range(447)]
    n_new = 8
    try:
        cluster.add_node(num_cpus=1, resources={"pre": 1},
                         real_process=True, isolated_plane=True)
        cluster.add_node(num_cpus=1, resources={"dec": 1},
                         real_process=True, isolated_plane=True)

        @ray_tpu.remote(num_cpus=1, resources={"pre": 1})
        def prefill_worker(prompt_ids, n):
            import os as _os

            import jax

            from ray_tpu.experimental import pubsub
            from ray_tpu.models import llama as _llama
            from ray_tpu.serve.kv_transport import KVTransport as _T
            from ray_tpu.serve.llm_paged import (PagedLLMConfig,
                                                 PagedLLMEngine)
            from tests.test_kv_transport import _pd_model_config

            mc = _pd_model_config()
            cfg = PagedLLMConfig(model_config=mc, max_batch_size=2,
                                 max_seq_len=512, block_size=16,
                                 kv_transfer="plane")
            params = _llama.init(mc, jax.random.PRNGKey(0))
            t = _T(ttl_s=90)
            eng = PagedLLMEngine(cfg, params=params)
            eng.kv_publish = t.publish
            try:
                ready = pubsub.subscribe("kvtest:ready")
                h = eng.prefill_extract(list(prompt_ids))
                assert ready.poll(timeout=120) is not None, "no decoder"
                pubsub.publish("kvtest:desc", {
                    k: h[k] for k in ("kv_ref", "first_token", "prompt_len",
                                      "n_prefill_blocks", "prompt_ids")})
                drained = t.wait_drained(timeout=120)
                return {"drained": drained,
                        "node": _os.environ.get("RAY_TPU_NODE_ID"),
                        "live_after": t.live_handoffs(),
                        "nbytes": h["kv_ref"]["nbytes"]}
            finally:
                eng.shutdown()
                t.close()

        @ray_tpu.remote(num_cpus=1, resources={"dec": 1})
        def decode_worker(n):
            import os as _os
            import time as _time
            import tracemalloc as _tm

            import jax

            from ray_tpu.experimental import pubsub
            from ray_tpu.models import llama as _llama
            from ray_tpu.serve.kv_transport import KVTransport as _T
            from ray_tpu.serve.llm_paged import (PagedLLMConfig,
                                                 PagedLLMEngine)
            from ray_tpu.util import metrics as _metrics
            from tests.test_kv_transport import _pd_model_config

            sub = pubsub.subscribe("kvtest:desc")
            mc = _pd_model_config()
            cfg = PagedLLMConfig(model_config=mc, max_batch_size=2,
                                 max_seq_len=512, block_size=16)
            params = _llama.init(mc, jax.random.PRNGKey(0))
            t = _T(ttl_s=90)
            eng = PagedLLMEngine(cfg, params=params)
            try:
                deadline = _time.monotonic() + 120
                handoff = None
                while _time.monotonic() < deadline and handoff is None:
                    pubsub.publish("kvtest:ready", True)
                    handoff = sub.poll(timeout=0.5)
                assert handoff is not None, "descriptor never arrived"
                desc = handoff["kv_ref"]
                ctr = _metrics.get_metric("ray_tpu_plane_pull_bytes_total")
                before = sum(ctr.snapshot().values()) if ctr else 0
                _tm.start()
                try:
                    _tm.reset_peak()
                    kv, ack = t.pull(desc)  # the cross-node page transfer
                    _, peak = _tm.get_traced_memory()
                finally:
                    _tm.stop()
                pulled = sum(ctr.snapshot().values()) - before if ctr else -1
                # hand the already-pulled pages to the engine's attach
                eng.kv_pull = lambda _ref: (kv, ack)
                toks = eng.attach_sequence(handoff, n).result(
                    timeout=120).token_ids
                return {"tokens": toks, "peak": peak, "pulled": pulled,
                        "nbytes": desc["nbytes"],
                        "holder_node": desc["node"],
                        "node": _os.environ.get("RAY_TPU_NODE_ID")}
            finally:
                eng.shutdown()
                t.close()

        dec_ref = decode_worker.remote(n_new)
        pre_ref = prefill_worker.remote(prompt, n_new)
        pre_out = ray_tpu.get(pre_ref, timeout=300)
        dec_out = ray_tpu.get(dec_ref, timeout=300)

        # genuinely cross-node: the workers ran on different agents, and the
        # descriptor's holder hint named the prefill node
        assert pre_out["node"] and dec_out["node"]
        assert pre_out["node"] != dec_out["node"]
        assert dec_out["holder_node"] == pre_out["node"]

        # zero-transient-copy on the pull path + bytes rode the BLOB plane
        assert dec_out["nbytes"] > (1 << 20), "handoff unexpectedly small"
        assert dec_out["pulled"] == dec_out["nbytes"], (
            f"pulled {dec_out['pulled']} != {dec_out['nbytes']} — KV did "
            "not ride the zero-copy plane pull")
        assert dec_out["peak"] < dec_out["nbytes"] // 2, (
            f"transient peak {dec_out['peak']}B on the pull path")

        # lifecycle: the prefill node's pages freed on decode ack
        assert pre_out["drained"] and pre_out["live_after"] == 0

        # exact tokens vs the co-located baseline (same params/seed)
        import jax

        from ray_tpu.models import llama as _llama
        from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine

        mc = _pd_model_config()
        cfg = PagedLLMConfig(model_config=mc, max_batch_size=2,
                             max_seq_len=512, block_size=16)
        ref = PagedLLMEngine(cfg, params=_llama.init(mc,
                                                     jax.random.PRNGKey(0)))
        try:
            expect = ref.generate_sync(prompt, n_new).token_ids
        finally:
            ref.shutdown()
        assert dec_out["tokens"] == expect
    finally:
        cluster.shutdown()
        ray_tpu.shutdown()
