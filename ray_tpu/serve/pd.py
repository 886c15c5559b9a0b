"""Disaggregated prefill/decode serving.

Parity: llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py +
the NIXL tensor-transport hop between the two engine fleets. Prefill is
burst-compute-bound, decode is memory-bandwidth-bound (opposite hardware
profiles — PAPERS.md, arxiv 2605.25645), so they run as SEPARATE
deployments that scale independently:

- ``PDPrefill`` replicas own a ``kv_transfer="plane"`` PagedLLMEngine and a
  ``KVTransport``: ``prefill(body)`` computes the prompt's KV pages,
  publishes them as one sealed object-plane entry, and returns a compact
  KV-handoff descriptor (ref id + endpoint, block table, first token,
  sampling state). Routed with ``kv_aware`` prompt-prefix affinity so
  shared prefixes prefill once.
- ``PDDecode`` replicas own their own engine + transport: ``decode(body)``
  pulls the handoff's pages with zero-copy BLOB frames straight into the
  local store, scatters them into the engine's block pool, acks (freeing
  the prefill-side entry), and streams the decode. Routed with the
  ``kv_aware`` decode-side placement score (holder locality +
  ``node_io_view`` pressure).
- ``PDController`` is the ingress deployment joining the two: one POST
  body in, prefill -> handoff -> decode, tokens out. A handoff lost
  between the phases (TTL/holder death) re-prefills once.

``build_pd_deployment`` (the previous co-located single-replica shape)
remains as the baseline the serve bench A/Bs against.
"""

from __future__ import annotations

from typing import Optional


class _ReplicaLifecycle:
    """Shared PD replica teardown: stop every engine loop and close the
    transport (shm arena, plane server socket, TTL sweeper). Runs via the
    explicit ``shutdown`` method or ``__del__`` once a killed replica's
    instance is dropped (kill_actor clears state.instance), so replica
    churn — drain, health-check failure, redeploy — can't accrete engine
    threads or shm arenas."""

    def _engines(self):
        return [self.engine]

    def _init_tag(self) -> None:
        import os

        self.tag = f"{os.getpid()}-{id(self):x}"

    def shutdown(self) -> None:
        for e in self._engines():
            e.shutdown()
        t = getattr(self, "transport", None)
        if t is not None:
            t.close()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


def _init_engine(decode_cfg, prefill_cfg=None, kv_transfer: str | None = None):
    """One parameter set shared by every PD engine (same model both sides)."""
    import dataclasses

    import jax

    from ray_tpu.models import model_of
    from ray_tpu.serve.llm_paged import PagedLLMEngine

    cfg = prefill_cfg or decode_cfg
    if kv_transfer is not None:
        cfg = dataclasses.replace(cfg, kv_transfer=kv_transfer)
    key = jax.random.PRNGKey(0)
    params = model_of(cfg.model_config).init(cfg.model_config, key)
    return PagedLLMEngine(cfg, params=params), params


def build_prefill_deployment(config=None, *, prefill_config=None,
                             num_replicas: int = 1, name: str = "PDPrefill",
                             slo_ttft_ms: float | None = None,
                             autoscaling_config=None):
    """The prefill fleet: KV pages out, descriptors back."""
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm_paged import PagedLLMConfig

    cfg = config or PagedLLMConfig()

    @deployment(name=name, num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0}, max_ongoing_requests=32,
                request_router="kv_aware", compiled_dispatch=True,
                slo_ttft_ms=slo_ttft_ms,
                autoscaling_config=autoscaling_config)
    class PrefillServer(_ReplicaLifecycle):
        def __init__(self, decode_cfg, prefill_cfg):
            from ray_tpu.serve.kv_transport import KVTransport

            self.engine, _ = _init_engine(decode_cfg, prefill_cfg,
                                          kv_transfer="plane")
            self.transport = KVTransport()
            self.engine.kv_publish = self.transport.publish
            self._init_tag()

        def prefill(self, body: dict) -> dict:
            import time

            from ray_tpu.serve import anatomy

            t0 = time.monotonic()
            t0_w = anatomy.now_wall()
            h = self.engine.prefill_extract(body.get("prompt_ids", []))
            rid = anatomy.rid_of(body)
            if rid is not None:
                # the prefill_exec window brackets the engine call (the
                # kv_publish window it contains is stamped oid-keyed inside
                # the transport); link rid<->oid so the head can join them
                anatomy.stamp(rid, "prefill_exec", t0_w, anatomy.now_wall())
                kv_ref = h.get("kv_ref")
                if isinstance(kv_ref, dict) and kv_ref.get("oid") is not None:
                    anatomy.link_kv(rid, bytes(kv_ref["oid"]).hex())
            return {
                "handoff": {
                    # the compact descriptor: plane ref + endpoint inside
                    # kv_ref; the page order within the handoff entry; the
                    # sampling state the decode fleet VALIDATES against its
                    # own config (a temperature-mismatched fleet would
                    # silently decode differently than the prefill sampled
                    # the first token)
                    "kv_ref": h["kv_ref"],
                    "first_token": h["first_token"],
                    "prompt_len": h["prompt_len"],
                    "n_prefill_blocks": h["n_prefill_blocks"],
                    # page order within the sealed entry that attach must
                    # scatter in (identity today; a future ragged/reordered
                    # layout permutes it) — the engine validates its length
                    # against the PULLED pages, guarding descriptor-vs-
                    # payload consistency
                    "block_table": list(range(h["n_prefill_blocks"])),
                    "sampling": {
                        "temperature": self.engine.config.temperature},
                    "prompt_ids": h["prompt_ids"],
                },
                "prefill_s": time.monotonic() - t0,
                "replica": self.tag,
            }

        def stats(self) -> dict:
            return {**self.engine.stats(), "kv": self.transport.stats()}

        def check_health(self) -> None:
            pass

    return PrefillServer.bind(cfg, prefill_config)


def build_decode_deployment(config=None, *, num_replicas: int = 1,
                            name: str = "PDDecode",
                            slo_ttft_ms: float | None = None,
                            autoscaling_config=None):
    """The decode fleet: handoff descriptors in, token streams out."""
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm_paged import PagedLLMConfig

    cfg = config or PagedLLMConfig()

    # compiled_dispatch: the engine stepping loop serializes requests
    # anyway, so the resident-graph channel (one frame per request, zero
    # control-plane) replaces an actor-task submit per decode — and the
    # fabric lets these replicas live on REMOTE agents (ISSUE 15)
    @deployment(name=name, num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0}, max_ongoing_requests=32,
                request_router="kv_aware", compiled_dispatch=True,
                slo_ttft_ms=slo_ttft_ms,
                autoscaling_config=autoscaling_config)
    class DecodeServer(_ReplicaLifecycle):
        def __init__(self, decode_cfg):
            from ray_tpu.serve.kv_transport import KVTransport

            self.engine, _ = _init_engine(decode_cfg)
            self.transport = KVTransport()
            self.engine.kv_pull = self.transport.pull
            self._init_tag()

        def decode(self, body: dict) -> dict:
            from ray_tpu.serve import anatomy
            from ray_tpu.serve.kv_transport import KVHandoffLost

            handoff = dict(body["handoff"])
            rid = anatomy.rid_of(body)
            if rid is not None:
                # ride the rid into the engine's attach payload so the
                # stepping loop can stamp decode_first_token; link the
                # handoff's oid on THIS side too (the pull window is
                # stamped by a different process than the publish one)
                handoff["_rid"] = rid
                kv_ref = handoff.get("kv_ref")
                if isinstance(kv_ref, dict) and kv_ref.get("oid") is not None:
                    anatomy.link_kv(rid, bytes(kv_ref["oid"]).hex())
            max_tokens = body.get("max_tokens")
            if max_tokens is None:
                max_tokens = 32
            # descriptor sanity: a sampling-state mismatch across the
            # fleets must fail loudly, not decode subtly different tokens
            # than the prefill side sampled (block_table-vs-payload
            # consistency is checked engine-side against the PULLED pages)
            temp = (handoff.get("sampling") or {}).get("temperature")
            if temp is not None and \
                    temp != self.engine.config.temperature:
                if handoff.get("kv_ref") is not None:
                    # free the published pages NOW instead of pinning the
                    # prefill store for a full TTL per rejected request —
                    # a misconfigured fleet rejects EVERY request, and the
                    # accumulated entries would turn a clear diagnosis
                    # into opaque store-full publish failures
                    self.transport.ack(handoff["kv_ref"])
                return {"error": "sampling_mismatch",
                        "detail": f"prefill temperature {temp} != decode "
                                  f"{self.engine.config.temperature}",
                        "replica": self.tag}
            try:
                if handoff.get("kv_ref") is not None:
                    # pull on THIS request thread (replica calls run
                    # concurrently under max_ongoing_requests), NOT the
                    # engine stepping thread: a hung prefill holder must
                    # not freeze every other in-flight decode stream on
                    # the replica. The ack closure still fires
                    # engine-side, right after the pool scatter lands.
                    handoff = dict(handoff)
                    handoff["_pulled"] = self.transport.pull(
                        handoff["kv_ref"], timeout=30.0)
                res = self.engine.attach_sequence(
                    handoff, max_tokens).result(timeout=120)
            except KVHandoffLost as e:
                # the published pages were reclaimed (TTL beat us / the
                # prefill endpoint died): tell the controller to re-prefill
                # instead of failing the request
                return {"error": "kv_handoff_lost", "detail": str(e)[:200],
                        "replica": self.tag}
            return {
                "token_ids": res.token_ids,
                "usage": {
                    "prompt_tokens": res.num_prompt_tokens,
                    "completion_tokens": res.num_generated,
                },
                "finish_reason": res.finish_reason,
                "replica": self.tag,
            }

        def stats(self) -> dict:
            return {**self.engine.stats(), "kv": self.transport.stats()}

        def check_health(self) -> None:
            pass

    return DecodeServer.bind(cfg)


def build_pd_controller(prefill_name: str = "PDPrefill",
                        decode_name: str = "PDDecode",
                        name: str = "PDIngress", num_replicas: int = 1,
                        slo_ttft_ms: float | None = None,
                        autoscaling_config=None):
    """The ingress joining the fleets (reference: pd_server.py's
    orchestration, now across deployments instead of inside one replica)."""
    from ray_tpu.serve.deployment import deployment

    @deployment(name=name, num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0}, max_ongoing_requests=64,
                slo_ttft_ms=slo_ttft_ms,
                autoscaling_config=autoscaling_config)
    class PDController:
        def __init__(self, prefill_name: str, decode_name: str,
                     name: str = "PDIngress"):
            self._prefill_name = prefill_name
            self._decode_name = decode_name
            self._name = name  # ledger deployment tag (anatomy)
            self._prefill = None
            self._decode = None

        def _handles(self):
            if self._prefill is None:
                from ray_tpu.serve.api import get_deployment_handle

                self._prefill = get_deployment_handle(self._prefill_name)
                self._decode = get_deployment_handle(self._decode_name)
            return self._prefill, self._decode

        def __call__(self, body: dict) -> dict:
            import time

            import ray_tpu
            from ray_tpu.serve import anatomy

            ph, dh = self._handles()
            # idempotent: returns a rid ONLY when this call newly admitted
            # (direct handle calls); an HTTP-proxied body arrives already
            # admitted and the proxy owns the completion record
            self_rid = anatomy.admit(body, self._name)
            a = body.get("_anatomy")
            max_tokens = body.get("max_tokens")
            if max_tokens is None:
                max_tokens = 32  # explicit 0 honored (prefill-only probe)
            t0 = time.monotonic()
            out = pre = None
            try:
                for attempt in range(2):
                    sub = {"prompt_ids": body.get("prompt_ids", [])}
                    if isinstance(a, dict):
                        # per-leg copy: the router writes sent_w/route into
                        # it, and the two legs must not share those marks
                        sub["_anatomy"] = dict(a)
                    pre = ray_tpu.get(ph.prefill.remote(sub), timeout=120)
                    dsub = {"handoff": pre["handoff"],
                            "max_tokens": max_tokens}
                    if isinstance(a, dict):
                        dsub["_anatomy"] = dict(a)
                    out = ray_tpu.get(dh.decode.remote(dsub), timeout=120)
                    if not (isinstance(out, dict)
                            and out.get("error") == "kv_handoff_lost"):
                        break
                    # pages reclaimed between the phases: one fresh prefill
                    anatomy.record_reprefill(
                        self._name, out.get("replica"),
                        out.get("detail") or "kv_handoff_lost")
                if isinstance(out, dict) and out.get("error"):
                    raise RuntimeError(f"PD decode failed: {out['error']}")
            except BaseException as e:
                if self_rid is not None:
                    anatomy.complete(self_rid, self._name, ok=False,
                                     err=str(e)[:200])
                raise
            result = {
                "token_ids": out["token_ids"],
                "usage": out["usage"],
                "timings": {"ttft_s": pre["prefill_s"],
                            "total_s": time.monotonic() - t0},
                "finish_reason": out["finish_reason"],
                "disaggregated": True,
                "pd": {"prefill_replica": pre.get("replica"),
                       "decode_replica": out.get("replica")},
            }
            if self_rid is not None:
                anatomy.complete(
                    self_rid, self._name, replica=out.get("replica"),
                    ntokens=out["usage"].get("completion_tokens", 0))
            return result

        def stats(self) -> dict:
            import ray_tpu

            ph, dh = self._handles()
            return {
                "prefill": ray_tpu.get(ph.stats.remote(), timeout=30),
                "decode": ray_tpu.get(dh.stats.remote(), timeout=30),
            }

    return PDController.bind(prefill_name, decode_name, name)


def deploy_pd_app(config=None, *, prefill_config=None,
                  num_prefill_replicas: int = 1,
                  num_decode_replicas: int = 1,
                  route_prefix: str | None = "/pd",
                  name_prefix: str = "PD",
                  slo_ttft_ms: float | None = None,
                  autoscaling_config=None):
    """Deploy the disaggregated app (prefill fleet + decode fleet +
    controller ingress) and return the controller handle.

    ``slo_ttft_ms`` / ``autoscaling_config`` plumb through to BOTH engine
    fleets (the front door's admission gate and the SLO autoscaler read
    them per deployment); the thin controller ingress carries only the SLO
    tag so its ledger rows land on the scoreboard too."""
    from ray_tpu import serve

    prefill_name = f"{name_prefix}Prefill"
    decode_name = f"{name_prefix}Decode"
    serve.run(build_prefill_deployment(
        config, prefill_config=prefill_config,
        num_replicas=num_prefill_replicas, name=prefill_name,
        slo_ttft_ms=slo_ttft_ms, autoscaling_config=autoscaling_config),
        route_prefix=None)
    serve.run(build_decode_deployment(
        config, num_replicas=num_decode_replicas, name=decode_name,
        slo_ttft_ms=slo_ttft_ms, autoscaling_config=autoscaling_config),
        route_prefix=None)
    # the ingress is named distinctly from build_pd_deployment's hard-coded
    # co-located "PDServer": deploying both shapes side by side for an A/B
    # (the module docstring's framing) must not silently redeploy one over
    # the other
    return serve.run(build_pd_controller(
        prefill_name, decode_name, name=f"{name_prefix}Ingress",
        slo_ttft_ms=slo_ttft_ms),
        route_prefix=route_prefix)


def build_pd_deployment(config=None, *, num_replicas: int = 1,
                        prefill_config=None,
                        slo_ttft_ms: float | None = None,
                        autoscaling_config=None):
    """The CO-LOCATED baseline: one replica owns both engines and hands KV
    over in-process (the pre-disaggregation shape; kept as the serve-bench
    A/B control and the small-deployment fallback).

    POST body: {"prompt_ids": [...], "max_tokens": N} -> token ids + timings
    (the LLMServer surface, served through the PD pipeline)."""
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm_paged import PagedLLMConfig

    cfg = config or PagedLLMConfig()

    @deployment(name="PDServer", num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0}, max_ongoing_requests=32,
                slo_ttft_ms=slo_ttft_ms,
                autoscaling_config=autoscaling_config)
    class PDServer(_ReplicaLifecycle):
        def __init__(self, decode_cfg, prefill_cfg):
            from ray_tpu.serve.llm_paged import PagedLLMEngine

            self.prefill_engine, params = _init_engine(decode_cfg,
                                                       prefill_cfg)
            self.decode_engine = PagedLLMEngine(decode_cfg, params=params)

        def _engines(self):
            return [self.prefill_engine, self.decode_engine]

        def __call__(self, body: dict) -> dict:
            import time

            prompt_ids = body.get("prompt_ids", [])
            max_tokens = body.get("max_tokens")
            if max_tokens is None:
                max_tokens = 32  # explicit 0 is honored (prefill-only probe)
            t0 = time.monotonic()
            handoff = self.prefill_engine.prefill_extract(prompt_ids)
            ttft = time.monotonic() - t0
            res = self.decode_engine.attach_sequence(handoff, max_tokens).result(
                timeout=120
            )
            return {
                "token_ids": res.token_ids,
                "usage": {
                    "prompt_tokens": res.num_prompt_tokens,
                    "completion_tokens": res.num_generated,
                },
                "timings": {"ttft_s": ttft,
                            "total_s": time.monotonic() - t0},
                "finish_reason": res.finish_reason,
                "disaggregated": False,
            }

        def stats(self) -> dict:
            return {
                "prefill": self.prefill_engine.stats(),
                "decode": self.decode_engine.stats(),
            }

    return PDServer.bind(cfg, prefill_config)
