"""serve public API: run/delete/shutdown/status + HTTP ingress.

Parity: python/ray/serve/api.py (serve.run :930, serve.delete, serve.status,
serve.shutdown) and the per-node HTTP proxy (_private/proxy.py:1010 HTTPProxy) —
here a single aiohttp ingress bound to the controller's route table.
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import Optional

import ray_tpu
from ray_tpu.serve.controller import CONTROLLER_NAME, DeploymentHandle, ServeController
from ray_tpu.serve.deployment import Application

_state: dict = {"controller": None, "proxy": None, "routes": {}}
_STREAM_END = object()
_lock = threading.Lock()

# OpenAI surface: subpath under a route -> (method, streaming method)
_OPENAI_METHODS = {
    "completions": ("completions", "completions_stream"),
    "chat/completions": ("chat_completions", "chat_completions_stream"),
    "models": ("models", None),
}


def _get_or_create_controller():
    from ray_tpu.core.runtime import get_runtime

    with _lock:
        rt = get_runtime()
        if _state.get("_rt") is not rt:
            # a new session started (possibly resumed from persistence):
            # cached handles point at the dead runtime; stop the old proxy so
            # its port is released instead of serving dead handles
            for key in ("proxy", "grpc_proxy"):
                old = _state.get(key)
                if old is not None:
                    try:
                        old.stop()
                    except Exception:
                        pass
            _state.update(controller=None, proxy=None, grpc_proxy=None,
                          routes={}, _rt=rt)
        if _state["controller"] is None:
            try:
                _state["controller"] = ray_tpu.get_actor(CONTROLLER_NAME)
            except ValueError:
                # detached + named: with gcs_storage_path set, the controller
                # is re-created on resume and self-heals apps from its KV
                # checkpoint (reference: controller.py:133 crash recovery)
                cls = ray_tpu.remote(num_cpus=0, max_concurrency=16)(ServeController)
                _state["controller"] = cls.options(
                    name=CONTROLLER_NAME, get_if_exists=True, lifetime="detached"
                ).remote()
        return _state["controller"]


def run(app: Application, *, name: str = "default", route_prefix: str | None = "/",
        blocking: bool = False) -> DeploymentHandle:
    """Deploy an application and return its handle (reference: serve.run api.py:930)."""
    if not ray_tpu.is_initialized():
        ray_tpu.init(ignore_reinit_error=True)
    controller = _get_or_create_controller()
    dep = app.deployment
    prefix = dep.config.route_prefix or route_prefix
    if prefix:
        # validate against the CONTROLLER's route table (authoritative — it
        # includes routes restored from a checkpoint), before deploying so a
        # conflict doesn't leave orphan replicas
        bound = ray_tpu.get(controller.get_routes.remote()).get(prefix)
        if bound is not None and bound != dep.config.name:
            raise ValueError(
                f"Route prefix {prefix!r} is already bound to deployment "
                f"'{bound}'; pass a distinct route_prefix."
            )
    ray_tpu.get(controller.deploy.remote(dep, prefix))
    handle = DeploymentHandle(controller, dep.config.name)
    if prefix:
        with _lock:
            _state["routes"] = {**_state["routes"], prefix: handle}
    # wait for at least one replica
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if ray_tpu.get(controller.get_replicas.remote(dep.config.name)):
            break
        time.sleep(0.05)
    if blocking:
        try:
            while True:
                time.sleep(1)
        except KeyboardInterrupt:
            pass
    return handle


def get_deployment_handle(name: str) -> DeploymentHandle:
    """Handle to an already-deployed deployment — e.g. after a resumed session
    restored the controller from its checkpoint (reference:
    serve.get_deployment_handle / get_app_handle)."""
    controller = _get_or_create_controller()
    if name not in ray_tpu.get(controller.get_deployment_names.remote()):
        raise ValueError(f"Deployment {name!r} not found")
    return DeploymentHandle(controller, name)


def delete(name: str) -> None:
    controller = _get_or_create_controller()
    ray_tpu.get(controller.delete_deployment.remote(name))
    _state["routes"] = {p: h for p, h in _state["routes"].items() if h.deployment_name != name}


def status() -> dict:
    controller = _get_or_create_controller()
    return ray_tpu.get(controller.status.remote())


def shutdown() -> None:
    try:
        from ray_tpu.serve.front_door import stop_front_door

        stop_front_door()
    except Exception:
        pass
    stop_proxies()
    with _lock:
        c = _state["controller"]
        if c is not None:
            try:
                ray_tpu.get(c.shutdown.remote(), timeout=10)
                ray_tpu.kill(c)
            except Exception:
                pass
            _state["controller"] = None
        if _state["proxy"] is not None:
            _state["proxy"].stop()
            _state["proxy"] = None
        if _state.get("grpc_proxy") is not None:
            _state["grpc_proxy"].stop()
            _state["grpc_proxy"] = None
        _state["routes"] = {}


def _ntokens_of(result) -> int:
    """Generated-token count from the common reply shapes (PD/LLM bodies
    and OpenAI objects both carry usage.completion_tokens)."""
    if isinstance(result, dict):
        usage = result.get("usage")
        if isinstance(usage, dict):
            try:
                return int(usage.get("completion_tokens") or 0)
            except (TypeError, ValueError):
                return 0
        if isinstance(result.get("token_ids"), (list, tuple)):
            return len(result["token_ids"])
    return 0


async def _await_ref(ref, timeout: float, executor=None):
    """Await an ObjectRef on the reactor: the runtime's future-based get
    parks NO thread per in-flight request (reference: the asyncio router of
    serve/_private/router.py:614 — replica replies resolve on the event
    loop). Falls back to an executor get for runtimes without get_async.
    ``executor`` bounds the blocking-get path: each parked get holds one
    worker, so the pool size IS the proxy's in-flight dispatch budget."""
    from ray_tpu.core.runtime import get_runtime
    from ray_tpu.dag import CompiledDAGRef

    rt = get_runtime()
    ga = getattr(rt, "get_async", None)
    # compiled-graph results live in the graph's result buffer, not the
    # object store — get_async only speaks ObjectRef, so compiled refs take
    # the executor path (ray_tpu.get dispatches on ref kind)
    if isinstance(ref, CompiledDAGRef):
        ga = None
    if ga is not None:
        try:
            return await asyncio.wait_for(asyncio.wrap_future(ga(ref)),
                                          timeout)
        except asyncio.TimeoutError as e:
            raise TimeoutError(f"request timed out after {timeout}s") from e
    loop = asyncio.get_running_loop()
    return await loop.run_in_executor(
        executor, lambda: ray_tpu.get(ref, timeout=timeout))


# ------------------------------------------------------------------ HTTP proxy
class HttpProxy:
    """aiohttp ingress: POST <route_prefix> with JSON body -> handle.remote(body).

    Reference: _private/proxy.py HTTPProxy:1010 (ASGI); routes resolved by
    longest matching prefix (proxy_router.py).
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 8000,
                 route_lookup=None, admission=None):
        from concurrent.futures import ThreadPoolExecutor

        self.host = host
        self.port = port
        # pluggable router: per-node proxy actors resolve routes against
        # their own controller-synced table instead of this process's _state
        self._route_lookup = route_lookup
        # pluggable admission gate (serve/admission.py): called with the
        # deployment name BEFORE anatomy.admit — a shed request never
        # creates a ledger, so it can't count against goodput. May block
        # (degrade-to-queue), so it runs on an executor, not the reactor.
        self._admission = admission
        self._loop = None
        self._runner = None
        # dedicated pool for long-lived SSE polls so streams can't starve the
        # default executor used by non-streaming requests
        self._stream_pool = ThreadPoolExecutor(max_workers=64, thread_name_prefix="sse")
        # per-proxy in-flight dispatch budget: every non-streaming request
        # whose result needs a blocking get (compiled-graph refs, runtimes
        # without get_async) parks one worker here until the replica
        # answers — the pool size is THE concurrency ceiling of this
        # ingress, and replicating ingresses (serve/front_door.py) is how
        # the fleet raises the aggregate budget
        self._dispatch_pool = ThreadPoolExecutor(
            max_workers=int(os.environ.get(
                "RAY_TPU_SERVE_INGRESS_CONCURRENCY", "8")),
            thread_name_prefix="dispatch")
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._started = threading.Event()
        self._thread.start()
        if not self._started.wait(10):
            raise RuntimeError("HTTP proxy failed to start")

    def _serve(self) -> None:
        from aiohttp import web

        async def handler(request: "web.Request") -> "web.Response":
            from ray_tpu.serve import anatomy

            route, handle = self._match(request.path)
            if handle is None:
                return web.json_response({"error": f"no route for {request.path}"}, status=404)
            try:
                body = await request.json() if request.can_read_body else {}
            except json.JSONDecodeError:
                return web.json_response({"error": "invalid JSON body"}, status=400)
            if self._admission is not None:
                loop = asyncio.get_running_loop()
                ok, reason = await loop.run_in_executor(
                    None, self._admission, handle.deployment_name)
                if not ok:
                    return web.json_response(
                        {"error": "shed", "reason": reason,
                         "deployment": handle.deployment_name},
                        status=503, headers={"Retry-After": "1"})
            # anatomy front door: the proxy admits the request (rid rides the
            # body through router -> replica -> engine) and, having admitted,
            # owns the completion record for both reply shapes below
            rid = anatomy.admit(body, handle.deployment_name)
            # OpenAI-compatible endpoints (reference: ray.serve.llm ingress,
            # llm/_internal/serve/core/ingress/): only for deployments that
            # opted into the surface (build_openai_app) — the subpath selects
            # the deployment method, responses are raw OpenAI objects.
            from ray_tpu.serve.openai_api import OPENAI_DEPLOYMENT_NAMES

            sub = request.path[len(route.rstrip("/")):].strip("/") if route else ""
            if sub in _OPENAI_METHODS and handle.deployment_name in OPENAI_DEPLOYMENT_NAMES:
                method, stream_method = _OPENAI_METHODS[sub]
                if isinstance(body, dict) and body.get("stream") and stream_method:
                    body = {**body, "stream_method": stream_method}
                    return await self._stream_response(request, handle, body)
                ref = getattr(handle, method).remote(body)
                try:
                    result = await _await_ref(ref, timeout=120,
                                               executor=self._dispatch_pool)
                except Exception as e:  # noqa: BLE001
                    if rid is not None:
                        anatomy.complete(rid, handle.deployment_name,
                                         ok=False, err=str(e)[:200])
                    return web.json_response(
                        {"error": {"message": str(e)[:500], "type": type(e).__name__}},
                        status=500,
                    )
                if rid is not None:
                    anatomy.complete(rid, handle.deployment_name,
                                     ntokens=_ntokens_of(result))
                return web.json_response(result)
            if isinstance(body, dict) and body.get("stream"):
                return await self._stream_response(request, handle, body)
            ref = handle.remote(body)
            try:
                result = await _await_ref(ref, timeout=60,
                                           executor=self._dispatch_pool)
            except Exception as e:  # noqa: BLE001
                if rid is not None:
                    anatomy.complete(rid, handle.deployment_name,
                                     ok=False, err=str(e)[:200])
                return web.json_response({"error": str(e)[:500]}, status=500)
            if rid is not None:
                anatomy.complete(rid, handle.deployment_name,
                                 ntokens=_ntokens_of(result))
            if isinstance(result, (dict, list, str, int, float)) or result is None:
                return web.json_response({"result": result})
            return web.json_response({"result": repr(result)})

        async def start():
            app = web.Application()
            app.router.add_route("*", "/{tail:.*}", handler)
            self._runner = web.AppRunner(app)
            await self._runner.setup()
            site = web.TCPSite(self._runner, self.host, self.port)
            await site.start()
            if self.port == 0:  # read back the OS-assigned ephemeral port
                self.port = site._server.sockets[0].getsockname()[1]
            self._started.set()

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        self._loop.run_until_complete(start())
        self._loop.run_forever()

    async def _stream_response(self, request, handle, body):
        """Server-sent events: one `data:` frame per yielded item
        (reference: serve streaming responses through the proxy)."""
        from aiohttp import web

        from ray_tpu.serve import anatomy
        from ray_tpu.serve.stream_cell import stream_cell
        from ray_tpu.util import timeline

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        method = body.get("stream_method", "stream_tokens")
        rid = anatomy.rid_of(body)
        # what the stream costs this proxy's threads goes into its request's
        # cell, which an engine in this process reads (serve/stream_cell.py);
        # with the replica in another process nobody reads it
        cell = stream_cell(rid)
        cell.sink = 1
        it = handle.stream(body, method_name=method)
        nframes = 0
        err = None

        def next_item():
            c0 = time.thread_time() if timeline.profiling() else None
            try:
                return next(it)
            except StopIteration:
                return _STREAM_END
            finally:
                if c0 is not None:   # the pool thread's CPU; waiting costs none
                    cell.fetch_cpu += time.thread_time() - c0

        try:
            while True:
                try:
                    item = await loop.run_in_executor(self._stream_pool, next_item)
                except Exception as e:  # noqa: BLE001 - stream errors become frames
                    err = str(e).splitlines()[-1][:200] if str(e) else type(e).__name__
                    await resp.write(f"data: {json.dumps({'error': err})}\n\n".encode())
                    break
                if item is _STREAM_END:
                    break
                if nframes == 0 and rid is not None:
                    # front-door first-token clock; an engine-side stamp
                    # (earlier, more precise) folds over this one when the
                    # replica's push beat lands
                    anatomy.stamp(rid, "decode_first_token",
                                  anatomy.now_wall())
                nframes += 1
                c0 = time.thread_time() if timeline.profiling() else None
                await resp.write(f"data: {json.dumps(item)}\n\n".encode())
                if c0 is not None:   # the event loop's CPU: a write that had
                    # to wait for the socket counts what ran meanwhile too
                    cell.write_cpu += time.thread_time() - c0
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (ConnectionError, ConnectionResetError, asyncio.CancelledError):
            err = err or "client_disconnected"
        finally:
            cell.sink = 2
            it.close()  # releases the router's in-flight slot (GeneratorExit)
            if rid is not None:
                anatomy.complete(rid, handle.deployment_name,
                                 ntokens=nframes, ok=err is None, err=err)
        return resp

    def _match(self, path: str):
        if self._route_lookup is not None:
            return self._route_lookup(path)
        return _match_route(path)

    def stop(self) -> None:
        if self._loop is None:
            return

        async def _teardown():
            if self._runner is not None:
                await self._runner.cleanup()  # closes the listening socket
            self._loop.stop()

        try:
            fut = asyncio.run_coroutine_threadsafe(_teardown(), self._loop)
            fut.result(timeout=5)
        except Exception:
            self._loop.call_soon_threadsafe(self._loop.stop)


def _match_route(path: str, routes: dict | None = None):
    """Longest-prefix route match (shared by the HTTP and gRPC ingresses and
    the per-node proxy actors — reference: proxy_router.py)."""
    best = None
    # snapshot: run()/delete() rebind the dict rather than mutating it
    for prefix, handle in list((_state["routes"] if routes is None else routes).items()):
        if path == prefix or path.startswith(prefix.rstrip("/") + "/") or prefix == "/":
            if best is None or len(prefix) > len(best[0]):
                best = (prefix, handle)
    return best if best else (None, None)


def start_http_proxy(host: str = "127.0.0.1", port: int = 8000) -> HttpProxy:
    with _lock:
        if _state["proxy"] is None:
            _state["proxy"] = HttpProxy(host, port)
        return _state["proxy"]


class _ProxyActor:
    """One ingress per placement (reference: _private/proxy.py — a proxy
    ACTOR on every node, any node's address serves traffic). Runs in its own
    process (isolate_process) with a controller-synced route table; requests
    route to replicas through deployment handles over the worker's client
    runtime, so the data plane no longer funnels through the head's single
    aiohttp loop."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1",
                 refresh_s: float = 10.0):
        import ray_tpu as _ray

        self._controller = _ray.get_actor(CONTROLLER_NAME)
        self._routes: dict = {}
        self._refresh_s = refresh_s
        self._stop = threading.Event()
        self._sync()  # serve correctly from the first request
        # Long-poll equivalent: the controller PUSHES route-table updates
        # over pubsub (reference: long_poll.py:318 LongPollHost); the
        # periodic sync is only a slow self-heal fallback now.
        self._sub = None
        try:
            from ray_tpu.experimental import pubsub

            self._sub = pubsub.subscribe("serve:routes")
            threading.Thread(target=self._push_loop, daemon=True,
                             name="proxy-route-push").start()
        except Exception:
            pass
        threading.Thread(target=self._sync_loop, daemon=True,
                         name="proxy-route-sync").start()
        self._proxy = HttpProxy(host, port, route_lookup=self._lookup)

    def _push_loop(self) -> None:
        while not self._stop.is_set():
            try:
                routes = self._sub.poll(timeout=1.0)
            except Exception:
                continue
            if routes is None:
                continue
            try:
                self._apply_routes(routes)
            except Exception:
                pass

    def _sync(self) -> None:
        self._apply_routes(ray_tpu.get(self._controller.get_routes.remote()))

    def _apply_routes(self, routes: dict) -> None:
        # Reuse existing handles: DeploymentHandle construction is expensive
        # (controller RPC + a router watcher thread that lives as long as the
        # handle) — rebuilding per refresh would leak a thread per route per
        # tick and reset the router's in-flight balancing counts.
        prev = self._routes
        new_table = {}
        for prefix, name in routes.items():
            cur = prev.get(prefix)
            if cur is not None and cur.deployment_name == name:
                new_table[prefix] = cur
            else:
                new_table[prefix] = DeploymentHandle(self._controller, name)
        self._routes = new_table

    def _sync_loop(self) -> None:
        while not self._stop.wait(self._refresh_s):
            try:
                self._sync()
            except Exception:
                pass  # controller briefly unavailable; keep the last table

    def _lookup(self, path: str):
        return _match_route(path, self._routes)

    def address(self) -> tuple:
        import socket as _socket

        host = self._proxy.host
        if host == "0.0.0.0":
            host = _socket.gethostbyname(_socket.gethostname())
        return (host, self._proxy.port)

    def ready(self) -> bool:
        return True

    def stop(self) -> None:
        self._stop.set()
        if self._sub is not None:
            try:
                self._sub.close()
            except Exception:
                pass
        self._proxy.stop()


def start_proxies(count: int = 2, base_port: int = 8100,
                  host: str = "127.0.0.1") -> list[tuple]:
    """Start `count` SPREAD-placed proxy actors (one per node when nodes are
    available) and return their (host, port) addresses. The reference runs
    exactly this shape: a proxy actor per node behind any load balancer.
    Binds loopback by default (reference HTTP ingress default); pass
    host="0.0.0.0" to expose the data plane. Safe to call again (names are
    unique per call); a failed boot is killed rather than leaked."""
    import uuid as _uuid

    if host in ("127.0.0.1", "localhost"):
        try:
            n_nodes = len(ray_tpu.nodes())
        except Exception:
            n_nodes = 1
        if n_nodes > 1:
            import warnings

            warnings.warn(
                "start_proxies(host='127.0.0.1') on a multi-node cluster: "
                "proxies placed on other nodes will only accept loopback "
                "traffic there; pass host='0.0.0.0' to serve cross-node "
                "ingress", stacklevel=2)

    addrs = []
    for i in range(count):
        actor = ray_tpu.remote(
            isolate_process=True, num_cpus=0.5,
            scheduling_strategy="SPREAD",
            name=f"SERVE_PROXY:{_uuid.uuid4().hex[:6]}:{i}",
        )(_ProxyActor).remote(port=base_port + i, host=host)
        with _lock:
            # registered BEFORE the readiness wait: a concurrent
            # stop_proxies/shutdown can always find (and kill) it
            _state.setdefault("proxy_actors", []).append(actor)
        try:
            ray_tpu.get(actor.ready.remote(), timeout=60)
            addrs.append(tuple(ray_tpu.get(actor.address.remote(), timeout=30)))
        except Exception:
            try:
                ray_tpu.kill(actor)
            except Exception:
                pass
            with _lock:
                acts = _state.get("proxy_actors", [])
                if actor in acts:
                    acts.remove(actor)
            raise
    return addrs


def stop_proxies() -> None:
    with _lock:
        actors = _state.pop("proxy_actors", [])
    for a in actors:
        try:
            ray_tpu.get(a.stop.remote(), timeout=10)
        except Exception:
            pass
        try:
            ray_tpu.kill(a)
        except Exception:
            pass


def start_grpc_proxy(host: str = "127.0.0.1", port: int = 9000):
    """gRPC ingress next to HTTP (reference: gRPCProxy proxy.py:527)."""
    from ray_tpu.serve.grpc_ingress import GrpcProxy

    with _lock:
        if _state.get("grpc_proxy") is None:
            _state["grpc_proxy"] = GrpcProxy(host, port)
        return _state["grpc_proxy"]
