"""LLM serving: continuous-batching engine on JAX + serve deployment + OpenAI-ish API.

Parity: python/ray/llm/ — ``LLMConfig``/``LLMServer``/``build_openai_app``
(serve/llm/__init__.py) and the engine layer the reference delegates to vLLM
(_internal/serve/engines/vllm/vllm_engine.py). TPU-native design:

- The engine owns a slot-based KV cache with static shapes (one XLA compile for
  decode, a few for bucketed prefill). Continuous batching = slots join/leave
  the batched decode step without recompiles — the scheduling idea of
  continuous-batching servers expressed in XLA-friendly form. (Paged/ragged KV
  via a pallas kernel is the planned upgrade; see PAPERS.md ragged paged attn.)
- Prefill and decode are separate jitted programs (the prefill/decode split the
  reference implements as separate *deployments* — pd_server.py — exists here
  inside one engine; cross-chip PD disaggregation follows the same interfaces).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional

import numpy as np

from ray_tpu.models import llama, model_of
from ray_tpu.ops.platform import target_platform
from ray_tpu.serve.stream_cell import StreamCell
from ray_tpu.util.compile_cache import compile_totals, ensure_compile_cache


@dataclasses.dataclass
class LLMConfig:
    """Reference: ray.serve.llm LLMConfig (model + engine kwargs)."""

    # any family's configuration: the engines take the family's forward, cache
    # and weights from its `Model` record (`ray_tpu.models.model_of`)
    model_config: Any = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    max_batch_size: int = 8
    max_seq_len: int = 256
    max_new_tokens_default: int = 32
    temperature: float = 0.0  # 0 = greedy
    eos_token_id: int = -1  # -1: never stop early (random-weight demo mode)
    prefill_buckets: tuple = (32, 128)


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


class LLMEngine:
    """Continuous-batching generation engine (vLLM-engine equivalent, jax-native)."""

    def __init__(self, config: LLMConfig, params=None, seed: int = 0,
                 external_step: bool = False):
        import jax
        import jax.numpy as jnp

        self.config = config
        cfg = config.model_config
        self._jax = jax
        self._jnp = jnp
        key = jax.random.PRNGKey(seed)
        self.model = model_of(cfg)
        self.params = params if params is not None else self.model.init(cfg, key)
        # Where the weights actually live, so where every step runs: a
        # CPU-pinned worker process reports "cpu" here however many chips the
        # host has (stats() carries it to whoever has to check)
        self.platform = target_platform(*jax.tree.leaves(self.params))
        ensure_compile_cache(self.platform)
        B = config.max_batch_size
        self.lengths = np.zeros(B, dtype=np.int32)
        self.last_tokens = np.zeros((B, 1), dtype=np.int32)
        self.active = np.zeros(B, dtype=bool)
        self.slots: list[Optional[_Slot]] = [None] * B
        # (prompt, max_new, future, enqueue time, token queue | None, request id | None)
        self._pending: "queue.Queue[tuple]" = queue.Queue()
        self._lock = threading.Lock()
        # the cells of the live streams (`generate_stream` appends its own),
        # the sums of those folded away, and the sums as the last `decode`
        # record noted them (`_stream_sums`, the engine thread's alone)
        self._streams: list[StreamCell] = []
        self._streams_lock = threading.Lock()
        self._st_folded = self._st_noted = (0,) * len(StreamCell.COUNTS)
        self._running = True
        self._sample_key = key  # the paged engine's `pick` draws from it on the device
        self._rng = np.random.default_rng(seed)  # `_sample`'s, the engine's own
        self._init_backend()  # subclass hook: cache/pool + jitted programs
        # external_step: no internal loop thread — a coordinator drives the
        # engine via step_once() (DP-attention rank lockstep, dp_attention.py)
        self._loop_thread = None
        if not external_step:
            self._loop_thread = threading.Thread(target=self._loop, daemon=True,
                                                 name=type(self).__name__)
            self._loop_thread.start()

    def step_once(self) -> bool:
        """One admit/decode round under external control; True if work ran."""
        try:
            return self._loop_step()
        except Exception as e:  # noqa: BLE001 - engine must survive any request
            self._fail_all_active(e)
            return True

    def _init_backend(self) -> None:
        """Dense per-slot KV cache backend (paged subclass overrides)."""
        jax, jnp = self._jax, self._jnp
        cfg = self.config.model_config
        B, S = self.config.max_batch_size, self.config.max_seq_len
        forward_with_cache = self.model.forward_with_cache
        if forward_with_cache is None or self.model.init_kv_cache is None:
            raise TypeError(
                f"the family of {type(cfg).__qualname__} gives no `forward_with_cache` "
                f"/ `init_kv_cache`: it serves through the paged engine "
                f"(serve/llm_paged.py) only")
        self.cache = self.model.init_kv_cache(cfg, B, S)

        def prefill(params, cache, tokens, slot, length):
            # slice this slot's cache, run, write back (single compile per bucket)
            sl = lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)
            sub = {"k": sl(cache["k"]), "v": sl(cache["v"])}
            # the head runs on the last real prompt position alone (tokens
            # are right-padded): logits [1, 1, V]
            logits, sub = forward_with_cache(
                params, tokens, cfg, sub, jnp.zeros((1,), jnp.int32),
                head_rows=jnp.reshape(length - 1, (1,)),
            )
            wr = lambda c, s: jax.lax.dynamic_update_slice_in_dim(c, s, slot, axis=1)
            cache = {"k": wr(cache["k"], sub["k"]), "v": wr(cache["v"], sub["v"])}
            return logits[0, 0], cache

        def decode(params, cache, last_tokens, lengths):
            logits, cache = forward_with_cache(params, last_tokens, cfg, cache, lengths)
            return logits[:, 0], cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(1,))

    # ---- public API ----
    def _validate(self, prompt_ids, max_new) -> Optional[Exception]:
        if not prompt_ids:
            return ValueError("prompt_ids must be non-empty")
        vocab = self.config.model_config.vocab_size
        if not all(isinstance(t, (int, np.integer)) and 0 <= t < vocab
                   for t in prompt_ids):
            return ValueError("prompt_ids must be ints within the vocabulary")
        if len(prompt_ids) + max_new > self.config.max_seq_len:
            return ValueError(
                f"prompt ({len(prompt_ids)}) + max_new_tokens ({max_new}) exceeds "
                f"max_seq_len {self.config.max_seq_len}"
            )
        return None

    def generate(self, prompt_ids: list[int], max_new_tokens: int | None = None) -> Future:
        fut: Future = Future()
        max_new = self.config.max_new_tokens_default if max_new_tokens is None else max_new_tokens
        err = self._validate(prompt_ids, max_new)
        if err is not None:
            fut.set_exception(err)
            return fut
        if max_new <= 0:
            fut.set_result(GenerationResult([], len(prompt_ids), 0, 0.0, 0.0))
            return fut
        self._pending.put((list(prompt_ids), max_new, fut, time.monotonic(), None, None))
        return fut

    def generate_stream(self, prompt_ids: list[int], max_new_tokens: int | None = None,
                        rid: str | None = None, cell: StreamCell | None = None):
        """Yield token ids as they are decoded (streaming TTFT path).

        Validation matches generate(); every engine path (completion, request
        failure, engine failure, shutdown) terminates the stream via the None
        sentinel so consumers never hang.

        `rid` is the request's id where it has one (`serve/anatomy.py`): the
        paged engine's `admit` record carries it. The stream counts what it
        costs into `cell`: the caller's, if it has stages of its own to count
        there (`openai_api.py::_stream_deltas`), else a new one."""
        fut: Future = Future()
        max_new = self.config.max_new_tokens_default if max_new_tokens is None else max_new_tokens
        err = self._validate(prompt_ids, max_new)
        if err is not None:
            raise err
        if max_new <= 0:
            return
        if cell is None:
            cell = StreamCell()
        tq = _TokenQueue(cell)
        with self._streams_lock:
            self._streams.append(cell)
        self._pending.put((list(prompt_ids), max_new, fut, time.monotonic(), tq, rid))
        try:
            while True:
                item = tq.get(timeout=300)
                if item is None:
                    if fut.done() and fut.exception() is not None:
                        raise fut.exception()
                    return
                tok, t_put = item
                cell.taken += 1
                cell.wake += time.monotonic() - t_put
                yield tok
        finally:
            cell.ended = True

    def generate_sync(self, prompt_ids: list[int], max_new_tokens: int | None = None,
                      timeout: float = 120.0) -> GenerationResult:
        return self.generate(prompt_ids, max_new_tokens).result(timeout)

    def stats(self) -> dict:
        # compiles / compile_s are the PROCESS's (util/compile_cache.py): a
        # step that compiles after warm-up shows as a rise between two reads
        compiles, compile_s = compile_totals()[:2]
        with self._lock:
            return {
                "active_slots": int(self.active.sum()),
                "max_slots": self.config.max_batch_size,
                "pending": self._pending.qsize(),
                # tokens put on their streams' queues and not yet taken
                "stream_backlog": self._stream_totals()[1],
                "platform": self.platform,
                "compiles": compiles,
                "compile_s": compile_s,
            }

    def shutdown(self) -> None:
        """Stop the loop and wait for it: a daemon thread still inside a
        jitted call when the interpreter tears down aborts the process
        (status 134) with the device open. Requests still queued end too:
        nothing will admit them, and a stream would wait out its poll."""
        self._running = False
        t = self._loop_thread
        if t is not None and t is not threading.current_thread():
            t.join()
        exc = RuntimeError("LLM engine shut down")
        self._fail_all_active(exc)
        while True:
            try:
                _, _, fut, _, tq, _ = self._pending.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_exception(exc)
            if tq is not None:
                tq.put(None)

    # ---- engine loop ----
    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return self.config.max_seq_len

    def _sample(self, logits_np: np.ndarray) -> int:
        if self.config.temperature <= 0:
            return int(np.argmax(logits_np))
        z = logits_np / self.config.temperature
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self._rng.choice(len(p), p=p))

    def _stream_totals(self) -> tuple[tuple, int]:
        """(the sums of `StreamCell.COUNTS` over every stream this engine has
        fed, the tokens put on live streams' queues and not yet taken). A
        cell that has ended with no sink open is folded into `_st_folded`
        here and dropped, so nothing of its tail is lost: whether it is over
        is read BEFORE its counts, so the counts folded are its last."""
        with self._streams_lock:
            rows, ended, live, backlog = [self._st_folded], [], [], 0
            for cell in self._streams:
                over = cell.ended and cell.sink != 1
                row = cell.counts()
                rows.append(row)
                if over:
                    ended.append(row)
                else:
                    live.append(cell)
                    if not cell.ended:
                        backlog += cell.put - cell.taken
            if ended:
                self._st_folded = tuple(map(sum, zip(self._st_folded, *ended)))
                self._streams = live
        return tuple(map(sum, zip(*rows))), backlog

    def _stream_sums(self) -> dict:
        """For a `decode` record: `st_<count>` for each of `StreamCell.COUNTS`,
        what this engine's streams gained since the record before, and
        `st_backlog` as it stands. The engine thread's alone."""
        totals, backlog = self._stream_totals()
        noted, self._st_noted = self._st_noted, totals
        out = {"st_" + name: now - was for name, now, was in
               zip(StreamCell.COUNTS, totals, noted)}
        out["st_backlog"] = backlog
        return out

    def _loop(self) -> None:
        while self._running:
            try:
                did_work = self._loop_step()
            except Exception as e:  # noqa: BLE001 - engine must survive any request
                self._fail_all_active(e)
                did_work = True
            if not did_work:
                time.sleep(0.002)

    def _release_slot(self, i: int) -> None:
        """Free a slot's resources (paged subclass also returns KV blocks and
        zeroes the slot's table row)."""
        self.active[i] = False
        self.slots[i] = None

    def cancel_future(self, fut) -> bool:
        """Cancel the in-flight request whose slot holds `fut`: release the
        slot (and its KV blocks, in the paged engine) under the engine lock.
        Public so callers (DP ranks, routers) never touch slot internals.
        Returns False if the future holds no slot (finished or still queued)."""
        with self._lock:
            for i, st in enumerate(self.slots):
                if st is not None and st.future is fut:
                    self._release_slot(i)
                    return True
        return False

    def _fail_all_active(self, exc: Exception) -> None:
        with self._lock:
            for i in range(self.config.max_batch_size):
                st = self.slots[i]
                if st is not None:
                    self._release_slot(i)
                    if not st.future.done():
                        st.future.set_exception(exc)
                    if st.token_queue is not None:
                        st.token_queue.put(None)

    def _loop_step(self) -> bool:
        jnp = self._jnp
        did_work = False
        # 1) admit pending requests into free slots (prefill)
        free = [i for i in range(self.config.max_batch_size) if not self.active[i]]
        while free and not self._pending.empty():
            try:
                prompt, max_new, fut, t_enq, tq, _ = self._pending.get_nowait()
            except queue.Empty:
                break
            slot = free.pop(0)
            try:
                bucket = self._bucket(len(prompt))
                padded = np.zeros((1, bucket), dtype=np.int32)
                padded[0, : len(prompt)] = prompt
                last_logits, self.cache = self._prefill(
                    self.params, self.cache, jnp.asarray(padded), slot, len(prompt)
                )
                tok = self._sample(np.asarray(last_logits))
            except Exception as e:  # noqa: BLE001 - bad request: fail it, keep serving
                if not fut.done():
                    fut.set_exception(e)
                if tq is not None:
                    tq.put(None)  # terminate any streaming consumer
                free.insert(0, slot)
                continue
            with self._lock:
                st = _Slot(fut, max_new, len(prompt), t_enq, tq)
                st.generated.append(tok)
                st.first_token_time = time.monotonic()
                if tq is not None:
                    tq.emit(tok, st.first_token_time)
                self.slots[slot] = st
                self.active[slot] = True
                self.lengths[slot] = len(prompt)
                self.last_tokens[slot, 0] = tok
            did_work = True
            self._maybe_finish(slot, tok)
        # 2) batched decode step for all active slots
        if self.active.any():
            logits, self.cache = self._decode(
                self.params, self.cache,
                jnp.asarray(self.last_tokens), jnp.asarray(self.lengths),
            )
            logits_np = np.asarray(logits)
            t_put = time.monotonic()
            with self._lock:
                for i in range(self.config.max_batch_size):
                    if not self.active[i]:
                        continue
                    tok = self._sample(logits_np[i])
                    st = self.slots[i]
                    st.generated.append(tok)
                    if st.token_queue is not None:
                        st.token_queue.emit(tok, t_put)
                    self.lengths[i] += 1
                    self.last_tokens[i, 0] = tok
            for i in range(self.config.max_batch_size):
                if self.active[i]:
                    self._maybe_finish(i, self.slots[i].generated[-1])
            did_work = True
        return did_work

    def _maybe_finish(self, slot: int, last_tok: int) -> None:
        st = self.slots[slot]
        if st is None:
            return
        eos = self.config.eos_token_id >= 0 and last_tok == self.config.eos_token_id
        if eos or len(st.generated) >= st.max_new:
            now = time.monotonic()
            result = GenerationResult(
                token_ids=list(st.generated),
                num_prompt_tokens=st.prompt_len,
                num_generated=len(st.generated),
                ttft_s=(st.first_token_time or now) - st.start,
                total_s=now - st.start,
                finish_reason="stop" if eos else "length",
            )
            with self._lock:
                self._release_slot(slot)
            if st.token_queue is not None:
                st.token_queue.put(None)  # end-of-stream
            if not st.future.done():
                st.future.set_result(result)


# ------------------------------------------------------------------ serve glue
def build_llm_deployment(config: LLMConfig | None = None, num_replicas: int = 1):
    """An LLMServer deployment (reference: ray.serve.llm LLMServer + build_openai_app).

    POST body: {"prompt_ids": [...], "max_tokens": N} -> token ids + timings.
    """
    from ray_tpu.serve.deployment import deployment
    from ray_tpu.serve.pd import _ReplicaLifecycle

    cfg = config or LLMConfig()

    @deployment(name="LLMServer", num_replicas=num_replicas,
                ray_actor_options={"num_tpus": 0.0})
    class LLMServer(_ReplicaLifecycle):
        def __init__(self, llm_config: LLMConfig):
            self.engine = LLMEngine(llm_config)

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
