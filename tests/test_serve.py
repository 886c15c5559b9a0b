"""Serve library tests (model: reference python/ray/serve/tests/)."""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(autouse=True)
def _session(ray_start_regular):
    yield
    serve.shutdown()


def test_deployment_basic():
    @serve.deployment
    class Echo:
        def __call__(self, body):
            return {"echo": body}

    h = serve.run(Echo.bind())
    assert ray_tpu.get(h.remote({"a": 1}), timeout=10) == {"echo": {"a": 1}}


def test_function_deployment():
    @serve.deployment
    def double(body):
        return body["x"] * 2

    h = serve.run(double.bind())
    assert ray_tpu.get(h.remote({"x": 21}), timeout=10) == 42


def test_num_replicas_and_status():
    @serve.deployment(num_replicas=3)
    class S:
        def __call__(self, body):
            return 1

    serve.run(S.bind())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = serve.status()["S"]
        if st["running_replicas"] == 3:
            break
        time.sleep(0.1)
    assert serve.status()["S"]["running_replicas"] == 3


def test_requests_spread_across_replicas():
    @serve.deployment(num_replicas=2)
    class WhoAmI:
        def __init__(self):
            self.id = id(self)

        def __call__(self, body):
            time.sleep(0.05)
            return self.id

    h = serve.run(WhoAmI.bind())
    ids = set(ray_tpu.get([h.remote({}) for _ in range(20)], timeout=30))
    assert len(ids) == 2  # power-of-two-choices reached both replicas


def test_method_calls_and_user_config():
    @serve.deployment(user_config={"factor": 3})
    class Mult:
        def __init__(self):
            self.factor = 1

        def reconfigure(self, cfg):
            self.factor = cfg["factor"]

        def __call__(self, body):
            return body["x"] * self.factor

        def get_factor(self):
            return self.factor

    h = serve.run(Mult.bind())
    assert ray_tpu.get(h.get_factor.remote(), timeout=10) == 3
    assert ray_tpu.get(h.remote({"x": 2}), timeout=10) == 6


def test_deployment_error_propagates():
    @serve.deployment
    class Boom:
        def __call__(self, body):
            raise ValueError("serve kaboom")

    h = serve.run(Boom.bind())
    with pytest.raises(Exception, match="serve kaboom"):
        ray_tpu.get(h.remote({}), timeout=10)


def test_delete_deployment():
    @serve.deployment
    class Temp:
        def __call__(self, body):
            return 1

    serve.run(Temp.bind())
    serve.delete("Temp")
    assert "Temp" not in serve.status()


def test_http_proxy_roundtrip():
    @serve.deployment
    class Api:
        def __call__(self, body):
            return {"sum": body.get("a", 0) + body.get("b", 0)}

    serve.run(Api.bind(), route_prefix="/api")
    serve.start_http_proxy(port=8456)
    req = urllib.request.Request(
        "http://127.0.0.1:8456/api",
        data=json.dumps({"a": 2, "b": 3}).encode(),
        headers={"Content-Type": "application/json"},
    )
    out = json.loads(urllib.request.urlopen(req, timeout=15).read())
    assert out == {"result": {"sum": 5}}


def test_http_404_and_bad_json():
    @serve.deployment
    class X:
        def __call__(self, body):
            return 1

    serve.run(X.bind(), route_prefix="/x")
    serve.start_http_proxy(port=8457)
    # bad json
    req = urllib.request.Request("http://127.0.0.1:8457/x", data=b"{not json",
                                 headers={"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req, timeout=10)
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_batching():
    sizes = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
    def process(items):
        sizes.append(len(items))
        return [i + 100 for i in items]

    results = [None] * 8
    threads = [threading.Thread(target=lambda i=i: results.__setitem__(i, process(i)))
               for i in range(8)]
    [t.start() for t in threads]
    [t.join(timeout=10) for t in threads]
    assert results == [100 + i for i in range(8)]
    assert max(sizes) > 1  # batching actually happened


def test_autoscaling_scale_up():
    @serve.deployment(autoscaling_config=serve.AutoscalingConfig(
        min_replicas=1, max_replicas=3, target_ongoing_requests=1.0, upscale_delay_s=0.1))
    class Slow:
        def __call__(self, body):
            time.sleep(0.4)
            return 1

    h = serve.run(Slow.bind())
    refs = [h.remote({}) for _ in range(30)]
    deadline = time.monotonic() + 20
    scaled = False
    while time.monotonic() < deadline:
        if serve.status()["Slow"]["target_replicas"] > 1:
            scaled = True
            break
        time.sleep(0.2)
    ray_tpu.get(refs, timeout=60)
    assert scaled


def test_llm_engine_continuous_batching():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=4, max_seq_len=64))
    futs = [eng.generate([1, 2, 3], 6) for _ in range(6)]
    results = [f.result(120) for f in futs]
    assert all(r.num_generated == 6 for r in results)
    # greedy => identical prompts produce identical continuations
    assert results[0].token_ids == results[-1].token_ids
    assert all(r.ttft_s >= 0 and r.total_s >= r.ttft_s for r in results)
    eng.shutdown()


def test_llm_prompt_too_long_rejected():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=32))
    with pytest.raises(ValueError, match="exceeds"):
        eng.generate(list(range(30)), 16).result(10)
    eng.shutdown()


def test_redeploy_replaces_replicas():
    @serve.deployment(user_config={"tag": "v1"})
    class Versioned:
        def __init__(self):
            self.tag = None

        def reconfigure(self, cfg):
            self.tag = cfg["tag"]

        def __call__(self, body):
            return self.tag

    h = serve.run(Versioned.bind())
    assert ray_tpu.get(h.remote({}), timeout=10) == "v1"
    h2 = serve.run(Versioned.options(user_config={"tag": "v2"}).bind())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if ray_tpu.get(h2.remote({}), timeout=10) == "v2":
            break
        time.sleep(0.1)
    assert ray_tpu.get(h2.remote({}), timeout=10) == "v2"


def test_route_prefix_conflict_rejected():
    @serve.deployment
    class A1:
        def __call__(self, body):
            return 1

    @serve.deployment
    class B1:
        def __call__(self, body):
            return 2

    serve.run(A1.bind(), route_prefix="/same")
    with pytest.raises(ValueError, match="already bound"):
        serve.run(B1.bind(), route_prefix="/same")


def test_autoscaling_scales_down_when_idle():
    @serve.deployment(autoscaling_config=serve.AutoscalingConfig(
        min_replicas=1, max_replicas=3, target_ongoing_requests=1.0,
        upscale_delay_s=0.05, downscale_delay_s=0.3))
    class Bursty:
        def __call__(self, body):
            time.sleep(0.3)
            return 1

    h = serve.run(Bursty.bind())
    refs = [h.remote({}) for _ in range(30)]
    ray_tpu.get(refs, timeout=60)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if serve.status()["Bursty"]["target_replicas"] == 1:
            break
        time.sleep(0.3)
    assert serve.status()["Bursty"]["target_replicas"] == 1


def test_llm_empty_prompt_rejected():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=32))
    with pytest.raises(ValueError, match="non-empty"):
        eng.generate([], 4).result(10)
    eng.shutdown()


def test_batch_never_exceeds_max_size():
    sizes = []

    @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
    def process2(items):
        sizes.append(len(items))
        time.sleep(0.02)
        return items

    results = [None] * 11
    threads = [threading.Thread(target=lambda i=i: results.__setitem__(i, process2(i)))
               for i in range(11)]
    [t.start() for t in threads]
    [t.join(timeout=15) for t in threads]
    assert results == list(range(11))
    assert max(sizes) <= 4 and sum(sizes) == 11


def test_llm_engine_survives_bad_request():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=32))
    with pytest.raises(ValueError):
        eng.generate(["a", "b"], 4).result(10)  # non-int tokens rejected up front
    # engine still serves afterwards
    res = eng.generate([1, 2, 3], 4).result(60)
    assert res.num_generated == 4
    eng.shutdown()


def test_llm_max_tokens_zero():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=32))
    res = eng.generate([1, 2], 0).result(10)
    assert res.num_generated == 0 and res.token_ids == []
    eng.shutdown()


def test_proxy_port_released_after_shutdown():
    @serve.deployment
    class P1:
        def __call__(self, body):
            return 1

    serve.run(P1.bind(), route_prefix="/p1")
    serve.start_http_proxy(port=8461)
    serve.shutdown()
    # rebinding the same port must work after cleanup
    @serve.deployment
    class P2:
        def __call__(self, body):
            return 2

    serve.run(P2.bind(), route_prefix="/p2")
    proxy = serve.start_http_proxy(port=8461)
    req = urllib.request.Request("http://127.0.0.1:8461/p2", data=b"{}",
                                 headers={"Content-Type": "application/json"})
    out = json.loads(urllib.request.urlopen(req, timeout=10).read())
    assert out == {"result": 2}


def test_handle_streaming_method():
    @serve.deployment
    class Streamer:
        def chunks(self, body):
            for i in range(body["n"]):
                yield {"chunk": i}

    h = serve.run(Streamer.bind())
    out = list(h.stream({"n": 3}, method_name="chunks"))
    assert out == [{"chunk": 0}, {"chunk": 1}, {"chunk": 2}]


def test_sse_streaming_over_http():
    @serve.deployment
    class SSE:
        def stream_tokens(self, body):
            for i in range(3):
                yield i * 11

    serve.run(SSE.bind(), route_prefix="/sse")
    serve.start_http_proxy(port=8471)
    req = urllib.request.Request(
        "http://127.0.0.1:8471/sse",
        data=json.dumps({"stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        frames = [ln.decode().strip() for ln in r if ln.strip()]
    assert frames == ["data: 0", "data: 11", "data: 22", "data: [DONE]"]


def test_llm_token_streaming():
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    eng = PagedLLMEngine(LLMConfig(max_batch_size=2, max_seq_len=64))
    toks = list(eng.generate_stream([1, 2, 3], 5))
    assert len(toks) == 5
    # matches the non-streaming result (greedy determinism)
    res = eng.generate_sync([1, 2, 3], 5)
    assert res.token_ids == toks
    eng.shutdown()


def test_sse_error_surfaces_as_frame():
    @serve.deployment
    class NoStreamM:
        def __call__(self, body):
            return 1

    serve.run(NoStreamM.bind(), route_prefix="/nostream2")
    serve.start_http_proxy(port=8473)
    req = urllib.request.Request(
        "http://127.0.0.1:8473/nostream2",
        data=json.dumps({"stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        frames = [ln.decode().strip() for ln in r if ln.strip()]
    assert any("error" in f for f in frames)
    assert frames[-1] == "data: [DONE]"


def test_per_node_proxy_actors():
    """Per-node proxy parity (reference: _private/proxy.py — proxy actor per
    node; serve/api.py:4 documented this as the known delta): SPREAD-placed
    proxy ACTORS in their own processes route to deployments via the
    controller-synced table; traffic through every proxy address works."""
    import json as _json
    import urllib.request

    from ray_tpu import serve

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, body):
            return {"echo": body["x"], "who": "echo"}

    serve.run(Echo.bind(), route_prefix="/echo")
    addrs = serve.start_proxies(count=2, base_port=8130)
    try:
        assert len(addrs) == 2
        for host, port in addrs:
            host = "127.0.0.1" if host in ("0.0.0.0",) else host
            req = urllib.request.Request(
                f"http://{host}:{port}/echo", method="POST",
                data=_json.dumps({"x": 5}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                out = _json.loads(r.read())
            assert out == {"result": {"echo": 5, "who": "echo"}}
        # a route added AFTER the proxies started becomes visible via sync
        @serve.deployment
        class Late:
            def __call__(self, body):
                return {"late": True}

        serve.run(Late.bind(), route_prefix="/late", name="late")
        host, port = addrs[0]
        host = "127.0.0.1" if host == "0.0.0.0" else host
        deadline = time.time() + 15
        ok = False
        while time.time() < deadline and not ok:
            try:
                req = urllib.request.Request(
                    f"http://{host}:{port}/late", method="POST", data=b"{}",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    ok = _json.loads(r.read()) == {"result": {"late": True}}
            except Exception:
                time.sleep(0.3)
        assert ok, "late route never propagated to the proxy actor"
    finally:
        serve.stop_proxies()
