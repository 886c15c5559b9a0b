"""OpenAI-compatible ingress for the LLM engine.

Parity: ray.serve.llm `build_openai_app` + the OpenAI-compatible HTTP surface
(python/ray/llm/_internal/serve/core/ingress/ — /v1/completions,
/v1/chat/completions, /v1/models; streaming via SSE chunks terminated by
`data: [DONE]`). The engine is the native continuous-batching TPU engine
(serve/llm.py), not a vLLM delegation.

Tokenization is pluggable: pass any object with encode(str)->list[int] and
decode(list[int])->str (e.g. a HuggingFace tokenizer); the default is a
hermetic byte-level tokenizer so the API surface works without model assets.
"""

from __future__ import annotations

import json
import time
import uuid
from typing import TYPE_CHECKING, Any, Optional

import ray_tpu
from ray_tpu.serve.deployment import deployment as _deployment

if TYPE_CHECKING:
    from ray_tpu.serve.llm import LLMConfig

# Deployments that opted into the OpenAI proxy surface (the proxy only
# dispatches /v1-style method routing for names registered here; arbitrary
# apps keep their plain __call__ routing).
OPENAI_DEPLOYMENT_NAMES: set[str] = {"OpenAIServer"}


class ByteTokenizer:
    """Hermetic fallback tokenizer: UTF-8 bytes shifted past special ids.
    Ids beyond the byte range fold back into it (random-weight demo mode
    samples from the full model vocab)."""

    OFFSET = 3  # 0=pad, 1=bos, 2=eos

    def encode(self, text: str) -> list[int]:
        return [b + self.OFFSET for b in text.encode("utf-8")]

    def decode(self, ids: list[int]) -> str:
        data = bytes((i - self.OFFSET) % 256 for i in ids if i >= self.OFFSET)
        return data.decode("utf-8", errors="replace")


def _render_chat(messages: list[dict]) -> str:
    """Minimal chat template (reference: chat templates live with the model;
    this is the fallback rendering)."""
    parts = [f"{m.get('role', 'user')}: {m.get('content', '')}" for m in messages]
    parts.append("assistant:")
    return "\n".join(parts)


def build_openai_app(config: "LLMConfig | None" = None, *,
                     model_id: str = "ray-tpu-llm",
                     tokenizer=None, num_replicas: int = 1):
    """An OpenAI-API-shaped deployment over the native engine
    (reference: ray.serve.llm build_openai_app). jax-heavy imports stay inside
    this builder so `import ray_tpu.serve` never pays them."""
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.pd import _ReplicaLifecycle

    cfg = config or LLMConfig()
    tok = tokenizer or ByteTokenizer()

    @_deployment(name="OpenAIServer", num_replicas=num_replicas,
                 ray_actor_options={"num_tpus": 0.0}, max_ongoing_requests=64)
    class OpenAIServer(_ReplicaLifecycle):
        def __init__(self, llm_config, tokenizer, model_id: str):
            from ray_tpu.serve.llm_paged import PagedLLMEngine

            self.engine = PagedLLMEngine(llm_config)
            self.tok = tokenizer
            self.model_id = model_id

        # ---- OpenAI surface ----
        def models(self, body: dict | None = None) -> dict:
            return {
                "object": "list",
                "data": [{"id": self.model_id, "object": "model",
                          "owned_by": "ray_tpu"}],
            }

        def completions(self, body: dict) -> dict:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = "".join(prompt)
            ids = self.tok.encode(prompt)
            res = self.engine.generate_sync(ids, body.get("max_tokens"))
            text = self.tok.decode(res.token_ids)
            return {
                "id": f"cmpl-{uuid.uuid4().hex[:24]}",
                "object": "text_completion",
                "created": int(time.time()),
                "model": body.get("model", self.model_id),
                "choices": [{
                    "index": 0,
                    "text": text,
                    "finish_reason": res.finish_reason,
                    "logprobs": None,
                }],
                "usage": {
                    "prompt_tokens": res.num_prompt_tokens,
                    "completion_tokens": res.num_generated,
                    "total_tokens": res.num_prompt_tokens + res.num_generated,
                },
            }

        def chat_completions(self, body: dict) -> dict:
            prompt = _render_chat(body.get("messages", []))
            ids = self.tok.encode(prompt)
            res = self.engine.generate_sync(ids, body.get("max_tokens"))
            text = self.tok.decode(res.token_ids)
            return {
                "id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": body.get("model", self.model_id),
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": res.finish_reason,
                }],
                "usage": {
                    "prompt_tokens": res.num_prompt_tokens,
                    "completion_tokens": res.num_generated,
                    "total_tokens": res.num_prompt_tokens + res.num_generated,
                },
            }

        def _stream_deltas(self, ids: list[int], max_tokens, body=None):
            """Incremental detokenization: decode the WHOLE generated id list
            each step and emit the text delta, holding back a trailing
            partial character (multi-byte/multi-token chars must not split
            into replacement chars across chunks — vLLM's incremental
            detokenizer behavior).

            Under a profiler session the stream's cell (`serve/stream_cell.py`)
            gains this thread's CPU in the detokeniser and from a yielded
            delta to the resumption, which is the replica's store of the
            chunk: the engine's `decode` records carry the sums."""
            from ray_tpu.serve import anatomy
            from ray_tpu.serve.stream_cell import stream_cell
            from ray_tpu.util import timeline

            arid = rid = anatomy.rid_of(body)
            cell = stream_cell(rid)
            generated: list[int] = []
            emitted = ""
            for tok_id in self.engine.generate_stream(ids, max_tokens,
                                                      rid=rid, cell=cell):
                if arid is not None:
                    # replica-clock first-token stamp: closest observer to
                    # the engine, beats the proxy's first-SSE-frame clock
                    anatomy.stamp(arid, "decode_first_token",
                                  anatomy.now_wall())
                    arid = None
                generated.append(int(tok_id))
                c0 = time.thread_time() if timeline.profiling() else None
                text = self.tok.decode(generated)
                if c0 is not None:
                    c1 = time.thread_time()
                    cell.detok_cpu += c1 - c0
                if text.endswith("�"):
                    text = text[:-1]  # maybe-incomplete char: wait one token
                if len(text) > len(emitted):
                    delta, emitted = text[len(emitted):], text
                    yield delta
                    if c0 is not None:
                        cell.relay_cpu += time.thread_time() - c1
            final = self.tok.decode(generated)
            if len(final) > len(emitted):
                yield final[len(emitted):]

        def chat_completions_stream(self, body: dict):
            """Generator of OpenAI chat chunks (SSE frames at the proxy)."""
            rid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
            prompt = _render_chat(body.get("messages", []))
            ids = self.tok.encode(prompt)
            for delta in self._stream_deltas(ids, body.get("max_tokens"),
                                             body):
                yield {
                    "id": rid,
                    "object": "chat.completion.chunk",
                    "created": int(time.time()),
                    "model": body.get("model", self.model_id),
                    "choices": [{
                        "index": 0,
                        "delta": {"content": delta},
                        "finish_reason": None,
                    }],
                }
            yield {
                "id": rid,
                "object": "chat.completion.chunk",
                "created": int(time.time()),
                "model": body.get("model", self.model_id),
                "choices": [{"index": 0, "delta": {}, "finish_reason": "stop"}],
            }

        def completions_stream(self, body: dict):
            rid = f"cmpl-{uuid.uuid4().hex[:24]}"
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = "".join(prompt)
            ids = self.tok.encode(prompt)
            for delta in self._stream_deltas(ids, body.get("max_tokens"),
                                             body):
                yield {
                    "id": rid,
                    "object": "text_completion",
                    "created": int(time.time()),
                    "model": body.get("model", self.model_id),
                    "choices": [{"index": 0, "text": delta, "finish_reason": None}],
                }
            yield {
                "id": rid,
                "object": "text_completion",
                "created": int(time.time()),
                "model": body.get("model", self.model_id),
                "choices": [{"index": 0, "text": "", "finish_reason": "stop"}],
            }

        def stats(self) -> dict:
            return self.engine.stats()

    return OpenAIServer.bind(cfg, tok, model_id)
