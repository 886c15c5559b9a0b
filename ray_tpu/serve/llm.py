"""LLM serving: what a request and its answer are, and the serve deployment.

Parity: python/ray/llm/ — ``LLMConfig``/``LLMServer``/``build_openai_app``
(serve/llm/__init__.py). The engine layer the reference delegates to vLLM
(_internal/serve/engines/vllm/vllm_engine.py) is ONE class here,
``serve/llm_paged.py::PagedLLMEngine``: every builder (this file's
``build_llm_deployment``, ``serve/openai_api.py::build_openai_app``,
``data/llm.py::Processor``) constructs it for any ``LLMConfig``. This file
holds the engine's configuration, the result it resolves a request to, the
slot and token-queue records of a live request, and the plain deployment.
"""

from __future__ import annotations

import dataclasses
import queue
from typing import Any

from ray_tpu.models import llama
from ray_tpu.serve.stream_cell import StreamCell


@dataclasses.dataclass
class LLMConfig:
    """Reference: ray.serve.llm LLMConfig (model + engine kwargs)."""

    # any family's configuration: the engine takes the family's forward, pool
    # and weights from its `Model` record (`ray_tpu.models.model_of`)
    model_config: Any = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    max_batch_size: int = 8
    max_seq_len: int = 256
    max_new_tokens_default: int = 32
    temperature: float = 0.0  # 0 = greedy
    eos_token_id: int = -1  # -1: never stop early (random-weight demo mode)
    prefill_buckets: tuple = (32, 128)
    block_size: int = 16  # tokens a KV page; `max_seq_len` is a multiple of it
    num_blocks: int = 0  # 0 = every slot can hold max_seq_len (B * Smax / block_size + 1)
    # PD handoff transport: "host" ships KV as numpy in the handoff dict;
    # "device" keeps KV device-resident and ships only a transfer TICKET —
    # the decode engine pulls the pages device->device over the jax transfer
    # server (experimental/rdt.py offer_device/pull_device; reference:
    # rdt/nixl_tensor_transport.py); "plane" publishes the pages as a sealed
    # object-plane entry (serve/kv_transport.py) and ships only the compact
    # descriptor — a decode engine on ANY node pulls them with zero-copy
    # BLOB frames straight into its own store (reference: NIXL/RDT KV
    # transfer riding the shared object plane)
    kv_transfer: str = "host"


@dataclasses.dataclass
class GenerationResult:
    token_ids: list
    num_prompt_tokens: int
    num_generated: int
    ttft_s: float
    total_s: float
    finish_reason: str = "length"


class _TokenQueue(queue.Queue):
    """A stream's tokens on their way from the engine thread to the stream's
    thread: `(token, the time.monotonic() of its put)`, then None at the end.
    `cell` counts both ends (`serve/stream_cell.py`)."""

    def __init__(self, cell: StreamCell):
        super().__init__()
        self.cell = cell

    def emit(self, tok: int, t: float) -> None:
        self.cell.put += 1
        self.put((tok, t))


class _Slot:
    __slots__ = ("future", "max_new", "generated", "start", "first_token_time",
                 "prompt_len", "token_queue")

    def __init__(self, future, max_new, prompt_len, enqueue_time, token_queue=None):
        self.future = future
        self.max_new = max_new
        self.generated = []
        self.start = enqueue_time  # TTFT measured from request arrival, incl. queueing
        self.first_token_time = None
        self.prompt_len = prompt_len
        self.token_queue = token_queue  # streaming consumers get tokens as decoded


# ------------------------------------------------------------------ serve glue
def build_llm_deployment(config: LLMConfig | None = None, num_replicas: int = 1):
    """An LLMServer deployment (reference: ray.serve.llm LLMServer + build_openai_app).

    POST body: {"prompt_ids": [...], "max_tokens": N} -> token ids + timings.
    """
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.llm_paged import PagedLLMEngine
    from ray_tpu.serve.pd import _ReplicaLifecycle

    cfg = config or LLMConfig()

    @deployment(name="LLMServer", num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0})
    class LLMServer(_ReplicaLifecycle):
        def __init__(self, llm_config: LLMConfig):
            self.engine = PagedLLMEngine(llm_config)

        def __call__(self, body: dict) -> dict:
            prompt_ids = body.get("prompt_ids", [])
            max_tokens = body.get("max_tokens")
            res = self.engine.generate_sync(prompt_ids, max_tokens)
            return {
                "token_ids": res.token_ids,
                "usage": {
                    "prompt_tokens": res.num_prompt_tokens,
                    "completion_tokens": res.num_generated,
                },
                "timings": {"ttft_s": res.ttft_s, "total_s": res.total_s},
                "finish_reason": res.finish_reason,
            }

        def stats(self) -> dict:
            return self.engine.stats()

        def stream_tokens(self, body: dict):
            """Generator: one token id per yield (serve streaming path)."""
            yield from self.engine.generate_stream(
                body.get("prompt_ids", []), body.get("max_tokens")
            )

    return LLMServer.bind(cfg)
