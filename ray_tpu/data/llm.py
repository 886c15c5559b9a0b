"""Batch LLM inference over Datasets.

Parity: python/ray/data/llm.py (ProcessorConfig :26, build_llm_processor :104)
and the staged batch pipeline in ray.llm _internal/batch/stages/
(chat_template → tokenize → engine → detokenize). The engine stage runs the
same continuous-batching engine the serve path uses (`PagedLLMEngine`) — one per
processor, shared across blocks, so the MXU sees full decode batches even when
dataset blocks are small.

Plane-native since ISSUE-12: the engine stage CONSUMES THE STREAMING
EXECUTOR — upstream blocks arrive as plane descriptors
(``Dataset.iter_block_refs``), materialize one at a time at the engine's
edge, and every prompt is submitted the moment its block lands while up to
``max_inflight_batches`` earlier blocks are still decoding. Dataset blocks
feed the engine's continuous batches WITHOUT materializing the dataset:
the driver holds a bounded window of in-flight batches, never the corpus.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from ray_tpu.data.block import Block
from ray_tpu.data.dataset import Dataset
from ray_tpu.serve.llm import LLMConfig
from ray_tpu.serve.llm_paged import PagedLLMEngine


@dataclasses.dataclass
class ProcessorConfig:
    """Reference: data/llm.py ProcessorConfig / vLLMEngineProcessorConfig."""

    llm_config: LLMConfig = dataclasses.field(default_factory=LLMConfig)
    prompt_column: str = "prompt_ids"
    output_column: str = "generated_ids"
    max_new_tokens: int | None = None
    tokenizer: Callable[[str], list[int]] | None = None
    detokenizer: Callable[[list[int]], str] | None = None
    batch_size: int = 16
    # Engine-feed window: how many dataset batches may be decoding at once
    # before the stream stops pulling upstream (the engine-side analog of
    # the executor's byte budget — keeps slots full across block
    # boundaries, bounds driver residency).
    max_inflight_batches: int = 4
    generate_timeout_s: float = 600.0


class Processor:
    """Dataset -> Dataset map with a shared generation engine fed by the
    streaming executor (blocks in flight bounded, prompts submitted as
    blocks land, outputs yielded in input order)."""

    def __init__(self, config: ProcessorConfig, engine: PagedLLMEngine | None = None):
        self.config = config
        self._engine = engine

    def _get_engine(self) -> PagedLLMEngine:
        if self._engine is None:
            self._engine = PagedLLMEngine(self.config.llm_config)
        return self._engine

    def _tokenize(self, prompts) -> list[list[int]]:
        cfg = self.config
        token_lists = []
        for p in prompts:
            if cfg.tokenizer is not None and isinstance(p, str):
                token_lists.append(list(cfg.tokenizer(p)))
            else:
                token_lists.append([int(t) for t in np.asarray(p).tolist()])
        return token_lists

    def _submit_batch(self, engine: PagedLLMEngine, batch: dict):
        """Submit every prompt of one batch; continuous batching interleaves
        them with whatever earlier batches are still decoding."""
        toks = self._tokenize(batch[self.config.prompt_column])
        futs = [engine.generate(t, self.config.max_new_tokens) for t in toks]
        return batch, futs

    def _finish_batch(self, pending) -> Block:
        cfg = self.config
        batch, futs = pending
        results = [f.result(cfg.generate_timeout_s) for f in futs]
        out = dict(batch)
        generated = [r.token_ids for r in results]
        if cfg.detokenizer is not None:
            out[cfg.output_column.replace("_ids", "_text")] = np.asarray(
                [cfg.detokenizer(g) for g in generated], dtype=object
            )
        out[cfg.output_column] = np.asarray(generated, dtype=object)
        out["num_generated"] = np.asarray([r.num_generated for r in results])
        return Block.from_numpy(out)

    def __call__(self, dataset: Dataset) -> Dataset:
        cfg = self.config
        proc = self

        def batches():
            # blocks arrive as plane descriptors and land here, at the
            # engine edge; batching stays WITHIN blocks (prompt columns may
            # be ragged — cross-block concat is not defined for them)
            for blk in dataset.iter_blocks():
                rows = blk.num_rows()
                if rows == 0:
                    continue
                for i in range(0, rows, max(1, cfg.batch_size)):
                    yield blk.slice(i, min(i + cfg.batch_size, rows)).to_numpy()

        def source():
            engine = proc._get_engine()
            window: deque = deque()
            for batch in batches():
                window.append(proc._submit_batch(engine, batch))
                # the NEXT batch is admitted while these decode; drain the
                # head only once the window is full — input-order outputs,
                # engine slots stay occupied across batch boundaries
                while len(window) >= max(1, cfg.max_inflight_batches):
                    yield proc._finish_batch(window.popleft())
            while window:
                yield proc._finish_batch(window.popleft())

        return Dataset(source, (), f"{dataset._name}.llm")

    def shutdown(self) -> None:
        if self._engine is not None:
            self._engine.shutdown()


def build_llm_processor(config: ProcessorConfig) -> Processor:
    """Reference: data/llm.py:104 build_llm_processor."""
    return Processor(config)
