"""Paged-KV continuous-batching engine + prefill/decode disaggregation.

Parity: the reference delegates both to vLLM (paged KV / automatic prefix
caching in the engine, PD disaggregation in
llm/_internal/serve/serving_patterns/prefill_decode/pd_server.py). Here both
are native:

- ``PagedLLMEngine``: THE serving engine, the one every builder constructs
  (`build_openai_app`, `build_llm_deployment`, `data/llm.py::Processor`): the
  request lifecycle (`generate`, `generate_stream`, cancel, finish and fail
  paths, shutdown), admission and the decode loop over a block-pool KV (the
  family's `Model.forward_paged` + serve/paged_kv.py allocator). Slots
  join and leave the batched decode step without a recompile (static shapes:
  one program for decode, one a prefill bucket). Memory scales with actual
  tokens reserved per request — many short sequences or few long ones share
  one pool — and full prompt blocks are content-addressed so shared prefixes
  prefill once and occupy memory once. With `num_blocks` 0 every slot can
  hold `max_seq_len`, so the allocator refuses nothing a slot is free for.
- ``prefill_extract`` / ``attach_sequence``: the KV handoff pair backing PD
  disaggregation — a prefill engine computes a sequence's KV pages and ships
  them (host numpy; cross-host this rides the object plane), a decode engine
  adopts them and streams tokens. Both run ON the engine thread (the pool is
  donated through jit calls; foreign-thread mutation would race).

Admission reserves ceil((prompt+max_new)/block) pages upfront, so decode
never preempts mid-sequence (vLLM-style preemption is a later refinement).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from concurrent.futures import Future
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

from ray_tpu.models import model_of
from ray_tpu.ops.platform import target_platform
from ray_tpu.serve import anatomy
from ray_tpu.serve.llm import GenerationResult, LLMConfig, _Slot, _TokenQueue
from ray_tpu.serve.paged_kv import BlockPool, NoFreeBlocks
from ray_tpu.serve.stream_cell import StreamCell
from ray_tpu.util.compile_cache import compile_totals, ensure_compile_cache
from ray_tpu.util.timeline import PhaseClock, PhaseLoop

# the phases of the engine's timeline records, in the order they run; each
# is `<phase>_s` in the record and `engine:<record>.<phase>` in a profile
_ADMIT_PHASES = ("alloc", "prefill", "wait", "copy", "sample")
_DECODE_PHASES = ("dispatch", "wait", "copy", "sample", "finish")


class _Flight(NamedTuple):
    """A decode step on the device's queue whose ids the host has not read."""

    ids: object      # [B, 1] int32 on the device: what `pick` chose, the next step's tokens
    rows: dict       # row -> the `_Slot` that was live in it when the step was enqueued
    counters: dict   # the pool's `counters` as that step left them, copied on the device


# the engine's one configuration class, under the name `ray_tpu.serve` exports
# and the benchmark imports (ROADMAP D14: the name goes with the next
# `benchmark` issue that edits `harness/families/llama.py`)
PagedLLMConfig = LLMConfig


def paged_step(name: str, cfg, block_size: int, platform: str,
               head, table_first: bool = False, fresh: bool = False):
    """The jitted step `name` of a paged engine: the `forward_paged` of the
    configuration's family (`model_of(cfg)`) over a pool it donates ->
    (logits, the pool). Called as (params, pool, tokens, lengths, tables); a
    prefill (`table_first`, B = 1) as (params, pool, tokens, table, span),
    span int32 [2]: where the tokens start in the sequence and how many of
    them are live (the rest pad the bucket).

    `fresh` is the program of a prefill whose span starts at 0
    (`forward_paged(fresh=True)`): its attention reads the rows it has just
    computed, the flash forward from the 1,024 bucket up on a TPU, and nothing
    of the pool. Without it a step reads the pool back through its table: the
    kernel over live pages at S == 1, the whole gathered table at any other S
    (a prompt that continues a cached prefix, the speculative window). The
    caller that builds a `fresh` step is the one that knows where its spans
    start (`prefill_step` chooses by the span itself). A `fresh` step whose
    tokens fill whole blocks also WRITES the pool as whole pages, the rest row
    by row (`prefill_writes`; an `admit` record's `writes`).

    `head` is which positions' logits the caller reads, and the output head
    runs on those alone (`decoder_trunk`'s `head_rows`), so a step returns
    nothing its caller drops: "all" -> [B, S, vocab]; "last", a prefill's last
    live position, or an int, that position of every sequence -> [B, vocab];
    None -> no logits (None in their place, and no head in the program). A
    profile knows the step by `name` (`jit(decode)/while/...`). The step holds
    its arguments only, never the engine.

    For a family whose pool has pages a SEQUENCE's (`Model.sequence_leaves`) a
    table row is one column wider: its LAST entry is the sequence's state
    page, which the step takes off and hands to `forward_paged(state_pages=)`.
    So a sequence stays what it was to every caller, a table row and a
    length, and a row of zeros is a dead row in both classes of page."""
    import jax
    import jax.numpy as jnp

    model = model_of(cfg)
    forward_paged = model.forward_paged
    if model.sequence_leaves:
        forward_paged = lambda params, tokens, cfg, pool, tables, *a, **kw: model.forward_paged(
            params, tokens, cfg, pool, tables[:, :-1], *a, state_pages=tables[:, -1], **kw)

    def step(params, pool, tokens, first, second):
        tables, lengths = (first, second[:1]) if table_first else (second, first)
        B, S = tokens.shape
        if head == "last":
            head_rows = second[1:] - 1
        elif isinstance(head, int) and S > 1:
            head_rows = jnp.full((B,), head, jnp.int32)
        else:  # every position; at S == 1 the only row is every row
            head_rows = None
        logits, pool = forward_paged(
            params, tokens, cfg, pool, tables, lengths, block_size,
            platform=platform, head_rows=head_rows, fresh=fresh)
        if head is None:
            return None, pool  # unused, so XLA drops the head with them
        return (logits if head == "all" else logits[:, 0]), pool

    step.__name__ = step.__qualname__ = name
    return jax.jit(step, donate_argnums=(1,))


def prefill_reads(start: int) -> str:
    """Which of a prefill's two programs a span that starts at `start` runs,
    by what its attention reads: "own_rows" (no cached prefix: the rows the
    call computes, `paged_step(fresh=True)`) or "table" (the suffix of a
    prompt whose first blocks the prefix cache held: the pool through the
    block table). An `admit` record notes it as `reads`. It names the program
    the engine chose, and every family's forward does what the name says
    (the latent one, `models/kimi_k2.py`, since PR 41)."""
    return "own_rows" if start == 0 else "table"


def prefill_writes(start: int, bucket: int, block_size: int) -> str:
    """How the prefill of `bucket` tokens from position `start` puts its keys
    and values (a family's latent rows) into the pool: "pages" (the program
    told `fresh` whose bucket fills whole blocks: bucket / block_size whole
    pages a layer, `models/llama.py::write_pages`) or "rows" (a row a token,
    `page_rows`: a suffix behind a cached prefix, a bucket that ends inside a
    block). An `admit` record notes it as `writes` beside `reads`; the rule is
    the model's own (`llama.writes_pages`), asked here with what the engine
    knows on the host."""
    from ray_tpu.models.llama import writes_pages

    fresh = prefill_reads(start) == "own_rows"
    return "pages" if writes_pages(fresh, bucket, block_size) else "rows"


def prefill_step(name: str, cfg, block_size: int, platform: str, head):
    """A B = 1 prefill as ONE callable (params, pool, tokens, table, span) over
    its two jitted programs (`paged_step(table_first=True)`, `fresh` and not),
    both `name` to a profile; which of the two runs also decides how the pool
    is written (`prefill_writes`: an `admit` record's `writes`). The choice is
    made here, on the host, from the span's first entry, so no caller can
    choose wrongly: hand it the span as the host array it is, and reading
    `span[0]` waits for no device. Each program compiles at the first call
    that takes it, a bucket at a time; a warm-up of fresh prompts compiles the
    `fresh` ones alone."""
    step = partial(paged_step, name, cfg, block_size, platform, head, table_first=True)
    programs = {"own_rows": step(fresh=True), "table": step()}

    def prefill(params, pool, tokens, table, span):
        return programs[prefill_reads(int(span[0]))](params, pool, tokens, table, span)

    return prefill


def pick_step(temperature: float):
    """The jitted step `pick`: a decode step's `[B, vocab]` float32 logits ->
    the `[B, 1]` int32 ids the next step takes as its tokens, chosen on the
    device so that nothing of the vocabulary crosses to the host. Called as
    (logits, counters, key) -> (ids, counters, key). At temperature 0 the
    argmax, first index on ties as `np.argmax`; otherwise one draw a row from
    softmax(logits / temperature) with a key split off `key`, which comes
    back advanced. `counters` (the pool's, `{}` for a family that counts
    nothing) come back as copies: the pool is donated to the next step, and
    the host reads what this step counted only after that one is enqueued."""
    import jax
    import jax.numpy as jnp

    def pick(logits, counters, key):
        if temperature <= 0:
            ids = jnp.argmax(logits, axis=-1)
        else:
            key, sub = jax.random.split(key)
            ids = jax.random.categorical(sub, logits / temperature, axis=-1)
        return ids.astype(jnp.int32)[:, None], jax.tree.map(jnp.copy, counters), key

    return jax.jit(pick)


def carry_step():
    """The jitted step `carry`: the tokens of the step about to be enqueued,
    (ids, host) -> [B, 1] int32. `ids` are the step in flight's own, still
    unread; `host` holds the first token of a row admitted since that step was
    enqueued and -1 in every other row."""
    import jax
    import jax.numpy as jnp

    def carry(ids, host):
        return jnp.where(host >= 0, host, ids)

    return jax.jit(carry)


def page_leaves(pool: dict) -> dict:
    """The cache of a family's pool (`Model.init_kv_pool`): every entry but
    `counters`, each with its pages on the SECOND axis, `[L, num_blocks, ...]`:
    a row a token (`[L, num_blocks, block_size, row]`: `k` and `v`; a latent
    family's one `latent`), or rows a block (`models/lfm2.py`'s `conv`,
    `[Lc, num_blocks, 2, H]`: a convolution's state at the block's end), and
    L a leaf's own. A leaf the family's `Model.sequence_leaves` names has
    pages of the OTHER class, one a sequence (`[L, num_sequences, ...]`:
    `models/nemotron_h.py`'s `ssm` and `conv`), so a leaf's page count is its
    own. They are what a PD hand-off moves, `leaf[:, idx]` with the ids of the
    leaf's own class, whatever the family names them and whatever follows the
    second axis."""
    return {name: leaf for name, leaf in pool.items() if name != "counters"}


def pool_counters(pool: dict) -> dict:
    """{name: number} of the pool's `counters` entry: what the family's last
    step counted (`kimi_k2`: `moe_rows`, an int; `xing4`: `hc_residue` beside
    it, a float), for the engine's records. Empty, and nothing is read from
    the device, for a pool that has none."""
    return {name: np.asarray(v).item() for name, v in pool.get("counters", {}).items()}


def _n_pages(kv: dict, sequence_leaves: tuple = ()) -> int:
    """Token pages (blocks) of a hand-off's payload: the second axis of a leaf
    of that class (a leaf of the other class carries ONE page, the sequence's)."""
    return next(leaf for name, leaf in kv.items() if name not in sequence_leaves).shape[1]


class PagedLLMEngine:
    """Continuous batching over a paged KV pool with prefix caching (the
    vLLM-engine equivalent, jax-native)."""

    def __init__(self, config: LLMConfig | None = None, params=None, seed: int = 0,
                 external_step: bool = False):
        import jax
        import jax.numpy as jnp

        self.config = config = config or LLMConfig()
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
        self._sample_key = key  # `pick` draws from it on the device
        self._rng = np.random.default_rng(seed)  # `_sample`'s: an admission's first token
        # PD ops (prefill_extract / attach) processed on the engine thread
        self._ops: "queue.Queue" = queue.Queue()
        # slot -> anatomy rid awaiting its first DECODED token (the attach
        # payload's _rid); stamped+popped by the first _step_decode that
        # appends a token for the slot, popped unstamped when the slot is
        # released first (0/1-token requests finish at attach)
        self._anatomy_pending: dict = {}
        # kv_transfer="plane" wiring (set by the PD deployment that owns the
        # engine): kv_publish(k, v, meta=...) -> descriptor publishes the
        # gathered pages (KVTransport.publish); kv_pull(descriptor) ->
        # ({"k","v"}, ack) lands a remote handoff (KVTransport.pull)
        self.kv_publish = None
        self.kv_pull = None
        # the engine thread's records in a row (PERF.md section 3): the time
        # between two of them, while the loop is busy, is the later one's `turn`
        self._records = PhaseLoop("engine")
        self._init_backend()  # the pool + the jitted programs; a subclass adds its own
        # external_step: no internal loop thread — a coordinator drives the
        # engine via step_once() (DP-attention rank lockstep, dp_attention.py)
        self._loop_thread = None
        if not external_step:
            self._loop_thread = threading.Thread(target=self._loop, daemon=True,
                                                 name=type(self).__name__)
            self._loop_thread.start()

    def _init_backend(self) -> None:
        cfg = self.config.model_config
        B, S, bs = (self.config.max_batch_size, self.config.max_seq_len,
                    self.config.block_size)
        if S % bs:
            raise ValueError(f"max_seq_len {S} must be a block_size {bs} multiple")
        self.max_blocks_per_seq = S // bs
        n_blocks = self.config.num_blocks or (B * self.max_blocks_per_seq + 1)
        self.pool_blocks = n_blocks
        # a pool with pages a SEQUENCE's (`Model.sequence_leaves`): one a
        # slot and the garbage page 0, and a table row's last column its id
        self.sequence_leaves = tuple(self.model.sequence_leaves)
        n_seq = B + 1 if self.sequence_leaves else 0
        self.pool = self.model.init_kv_pool(
            cfg, n_blocks, bs, **({"num_sequences": n_seq} if n_seq else {}))
        self.allocator = BlockPool(n_blocks, bs, num_sequences=n_seq)
        self.slot_state_page = [0] * B
        self.tables = self._table_rows(B)
        self.slot_blocks: list[list[int]] = [[] for _ in range(B)]
        self.slot_prompts: list[Optional[list[int]]] = [None] * B
        step = partial(paged_step, cfg=cfg, block_size=bs, platform=self.platform)
        # prefill: a B=1 row, the logits of the suffix's last live position;
        # one callable over the own-rows program and the table one
        self._prefill = prefill_step("prefill", cfg, bs, self.platform, head="last")
        self._decode = step("decode", head=0)
        self._pick = pick_step(self.config.temperature)
        self._carry = carry_step()
        # the decode step enqueued and not yet read (`_step_decode`); the
        # engine thread's alone
        self._flight: Optional[_Flight] = None

    def _table_rows(self, n: int, block_ids=(), state_page: int = 0) -> np.ndarray:
        """`n` table rows [n, max_blocks (+ 1)], each `block_ids` then zeros
        and, where the pool has sequence pages, `state_page` last."""
        rows = np.zeros((n, self.max_blocks_per_seq + bool(self.sequence_leaves)), np.int32)
        rows[:, :len(block_ids)] = block_ids
        if self.sequence_leaves:
            rows[:, -1] = state_page
        return rows

    def _page_ids(self, name: str, block_ids, state_page: int) -> np.ndarray:
        """The ids of a sequence's pages of the leaf `name`'s own class."""
        ids = [state_page] if name in self.sequence_leaves else block_ids
        return np.asarray(ids, dtype=np.int32)

    def step_once(self) -> bool:
        """One admit/decode round under external control; True if work ran."""
        try:
            return self._loop_step()
        except Exception as e:  # noqa: BLE001 - engine must survive any request
            self._fail_all_active(e)
            return True

    def dummy_decode(self) -> None:
        """Cadence-keeping round for DP-attention lockstep (dp_attention.py):
        decode the zeroed batch — inactive rows write into the reserved
        garbage block 0, burning a real round's FLOPs/collective shape.
        Lives HERE with the jit definition because `_decode` donates the
        pool: the returned pool must be rebound, and a failure after
        dispatch invalidates the donated buffer — fatal for the engine, so
        it propagates instead of being swallowed."""
        _, self.pool = self._decode(self.params, self.pool, self.last_tokens,
                                    self.lengths, self.tables)

    # ---- slot lifecycle ----
    def _release_slot(self, i: int) -> None:
        """Free blocks AND zero the slot's rows: the batched decode scatters
        every row each step, so a stale table/length would keep writing into
        blocks after they're reallocated to other sequences (silent KV
        corruption). Zeroed rows write into reserved garbage block 0.

        That holds for every step enqueued from now on. A step IN FLIGHT
        (`_step_decode` reads one step behind) was enqueued with this slot's
        table and length and still writes ONE row: the KV of the token it was
        given, at a position at or past the prompt's end, so never in a block
        the prefix cache holds by content (those are whole blocks of prompt).
        It runs before any program enqueued later (one queue, and every step
        takes the pool the one before returned), and whoever is given the
        block next writes a position before it reads it (a prefill every
        position from its suffix's start, a decode step its own position), so
        the stale row is overwritten or never read. The id that step chose for
        this row is dropped when it is read: the row's slot is no longer the
        one it was enqueued for."""
        self.active[i] = False
        self.slots[i] = None
        self._anatomy_pending.pop(i, None)
        self.tables[i] = 0
        self.lengths[i] = 0
        self.last_tokens[i] = 0
        if self.slot_blocks[i]:
            self.allocator.free(self.slot_blocks[i])
            self.slot_blocks[i] = []
        if self.slot_state_page[i]:
            # the step in flight still writes this page once; whoever gets it
            # next trusts nothing in it (a fresh prefill reads no state)
            self.allocator.free_sequence(self.slot_state_page[i])
            self.slot_state_page[i] = 0
        self.slot_prompts[i] = None

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
        `admit` record carries it. The stream counts what it
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
            out = {
                "active_slots": int(self.active.sum()),
                "max_slots": self.config.max_batch_size,
                "pending": self._pending.qsize(),
                # tokens put on their streams' queues and not yet taken
                "stream_backlog": self._stream_totals()[1],
                "platform": self.platform,
                "compiles": compiles,
                "compile_s": compile_s,
                # no prefix is looked up or registered over a pool with pages
                # a sequence's: a block's hash says nothing of a running sum,
                # nor of the window's rows at the prefix's end
                "prefix_cache": not self.sequence_leaves,
            }
        return {**out, **self.allocator.stats()}

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
        # a step the loop left in flight: its slots are failed, so its ids go
        # unread, but the device is not left with work behind the exit
        flight, self._flight = self._flight, None
        if flight is not None:
            with contextlib.suppress(Exception):
                flight.ids.block_until_ready()
        # drain queued PD ops so their callers fail fast instead of timing out
        while True:
            try:
                _, _, fut = self._ops.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_exception(exc)

    def kv_memory_bytes(self) -> int:
        """Persistent KV pool footprint (the headroom metric vs dense): the
        pool as allocated, a head under 128 lanes in its 128-wide tile."""
        return sum(leaf.nbytes for leaf in page_leaves(self.pool).values())

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

    def cancel_future(self, fut) -> bool:
        """Cancel the in-flight request whose slot holds `fut`: release the
        slot and its KV blocks under the engine lock.
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

    def _admit_one(self, prompt, max_new, fut, t_enq, tq, rid, slot) -> bool:
        bs = self.config.block_size
        # one engine/admit timeline record per call (PERF.md section 3): the
        # phases tile it; `failed` stands unless a path below says otherwise
        clock = self._records.clock("admit", _ADMIT_PHASES)
        compile_s0 = compile_totals()[1]
        info = {"prompt": len(prompt), "cached": 0, "bucket": 0, "slot": slot,
                "queue_wait_s": clock.t0 - t_enq, "outcome": "failed"}
        if rid is not None:
            info["rid"] = rid  # the request's spans elsewhere carry it (serve/anatomy.py)
        try:
            total_blocks = -(-(len(prompt) + max_new) // bs)
            if total_blocks > self.pool_blocks - 1:
                # can never fit this pool: reject now rather than requeue forever
                if not fut.done():
                    fut.set_exception(ValueError(
                        f"request needs {total_blocks} KV blocks but the pool has "
                        f"{self.pool_blocks - 1}; raise num_blocks or shorten the request"
                    ))
                if tq is not None:
                    tq.put(None)
                info["outcome"] = "rejected"
                return True
            hit_ids, cached_len, state_page = [], 0, 0
            if not self.sequence_leaves:
                hit_ids, cached_len = self.allocator.lookup_prefix(prompt)
            if cached_len >= len(prompt):
                # whole prompt block-aligned-cached: recompute the last block so
                # we still have logits to sample the first token from
                self.allocator.free([hit_ids.pop()])
                cached_len -= bs
            try:
                fresh = self.allocator.alloc(total_blocks - len(hit_ids))
                if self.sequence_leaves:
                    try:
                        state_page = self.allocator.alloc_sequence()
                    except NoFreeBlocks:
                        self.allocator.free(fresh)
                        raise
            except NoFreeBlocks:
                for b in hit_ids:
                    self.allocator.free([b])
                info["outcome"] = "requeued"
                return False  # requeue: capacity frees as sequences finish
            block_ids = hit_ids + fresh
            suffix = prompt[cached_len:]
            # clamp the prefill bucket so padded positions stay inside the table
            bucket = min(self._bucket(len(suffix)),
                         self.config.max_seq_len - cached_len)
            info.update(cached=cached_len, bucket=bucket, reads=prefill_reads(cached_len),
                        writes=prefill_writes(cached_len, bucket, bs), blocks=total_blocks)
            if state_page:   # the other class's reservation: ONE page, whatever the length
                info["state_page"] = state_page
            padded = np.zeros((1, bucket), dtype=np.int32)
            padded[0, : len(suffix)] = suffix
            table_row = self._table_rows(1, block_ids, state_page)
            try:
                clock.mark("prefill")  # returns when the program is enqueued
                # the host's arrays as they are: `_prefill` reads the span
                logits, self.pool = self._prefill(
                    self.params, self.pool, padded, table_row,
                    np.asarray([cached_len, len(suffix)], np.int32))
                clock.mark("wait")  # the device's part; np.asarray would wait too
                logits.block_until_ready()
                clock.mark("copy")  # [1, vocab] float32 to the host
                logits_np = np.asarray(logits)
                info.update(pool_counters(self.pool))
                clock.mark("sample")
                tok = self._sample(logits_np[0])
            except Exception as e:  # noqa: BLE001 - bad request: fail, keep serving
                self.allocator.free(block_ids)
                if state_page:
                    self.allocator.free_sequence(state_page)
                if not fut.done():
                    fut.set_exception(e)
                if tq is not None:
                    tq.put(None)
                return True
            if not self.sequence_leaves:
                self.allocator.register_prefix(prompt, block_ids,
                                               skip_blocks=cached_len // bs)
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
                self.tables[slot] = table_row[0]
                self.slot_blocks[slot] = block_ids
                self.slot_state_page[slot] = state_page
                self.slot_prompts[slot] = list(prompt)
            self._maybe_finish(slot, tok)
            info["outcome"] = "admitted"
            return True
        finally:
            clock.close(compile_s=compile_totals()[1] - compile_s0, **info)

    def _loop_step(self) -> bool:
        did_work = self._step_ops()
        did_work = self._step_admit() or did_work
        did_work = self._step_decode() or did_work
        if not did_work:
            self._records.rest()  # the sleep that follows is no turn
        return did_work

    def _step_ops(self) -> bool:
        did_work = False
        for _ in range(self._ops.qsize()):  # bounded: attach may requeue itself
            try:
                kind, payload, fut = self._ops.get_nowait()
            except queue.Empty:
                break
            clock = self._records.clock("ops")
            try:
                if kind == "prefill_extract":
                    fut.set_result(self._do_prefill_extract(payload))
                else:
                    self._do_attach(payload, fut)
            except Exception as e:  # noqa: BLE001
                if not fut.done():
                    fut.set_exception(e)
            finally:
                clock.close(kind=kind)
            did_work = True
        return did_work

    def _step_admit(self) -> bool:
        """Admit from the head of the line while slots and blocks last. A
        request that found no blocks keeps its place at the head (requeued:
        once a pass, then the pass ends), so admission is in order of arrival
        and a large request is not overtaken forever by smaller ones."""
        did_work = False
        # a row whose last token is still in flight is occupied, not active
        free = [i for i in range(self.config.max_batch_size) if self.slots[i] is None]
        while free and not self._pending.empty():
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            if not self._admit_one(*req, free[0]):
                with self._pending.mutex:   # back to the head, not the tail
                    self._pending.queue.appendleft(req)
                break  # pool exhausted: stop admitting this pass
            free.pop(0)
            did_work = True
        return did_work

    @contextlib.contextmanager
    def _decode_clock(self, phases: tuple):
        """One engine/decode timeline record for the step run inside it
        (PERF.md section 3); the caller marks the phases after the first and
        notes what else the step knows. `live` and `ctx` are the active rows',
        the ones the step enqueues, taken before it advances a length; the
        `st_*` fields are what the streams counted since the record before."""
        clock = self._records.clock("decode", phases)
        compile_s0 = compile_totals()[1]
        live = int(self.active.sum())
        ctx = int(self.lengths[self.active].sum())
        # the allocator's running count: `stats()` walks every cached block,
        # 1% of a step with a 4,097-block pool of cached prompts (PERF.md
        # section 6, PR 31)
        pages = {"blocks": self.allocator.in_use}
        if self.sequence_leaves:
            pages["state_pages_used"] = self.allocator.sequences_in_use
        try:
            yield clock
        finally:
            # the record ends at `stop`; adding up the streams' cells (a lock
            # and a scan of the live ones) is the turn's, not the record's
            clock.stop()
            clock.close(live=live, ctx=ctx, **pages,
                        compile_s=compile_totals()[1] - compile_s0,
                        **self._stream_sums())

    def _step_decode(self) -> bool:
        """One pass of the decode loop, one step BEHIND itself: step k+1 is
        enqueued, its tokens step k's ids still on the device (`pick` chose
        them there), and only then does the host wait for step k's ids, hand
        them to the streams and run the finish checks, while the device runs
        step k+1. A pass is one `decode` record: `dispatch` (step k+1; `live`,
        `ctx`, `ahead` are its), then `wait`, `copy`, `sample`, `finish` (step
        k; `late_rows` and the pool's counters are its).

        What the lag changes, and why each is safe. A length does not depend
        on what was sampled, so it advances when a step is ENQUEUED. A
        sequence that ends by count is known to before its last step is read:
        `_enqueue` takes its row out of `active` with that step, so no step is
        ever enqueued for a token nobody asked for, and the row stays occupied
        (`slots[i]`) until the token is read. One that ends on `eos_token_id`
        is found a step late, and so is a `cancel_future` from another thread:
        the step in flight still runs that row, writes one stale KV row
        (`_release_slot` says why nothing reads it) and chooses an id that is
        dropped and counted, `late_rows`, because an id goes only to the
        `_Slot` object its step was enqueued for. When the step just enqueued
        was the last of every live sequence, or nothing is left to enqueue, no
        step follows to be read behind: the step in flight is read in this
        same pass, so a `step_once()` driver and `generate_sync` see every
        token. The host's `self.last_tokens` stays what was EMITTED."""
        before = self._flight
        if before is None and not self.active.any():
            return False
        try:
            with self._decode_clock(_DECODE_PHASES) as clock:
                enqueued = self.active.any()
                if enqueued:
                    self._flight = self._enqueue(before)
                clock.note(ahead=bool(enqueued and before is not None))
                late = 0 if before is None else self._emit(before, clock)
                if not self.active.any():
                    if enqueued:
                        late += self._emit(self._flight, clock)
                    self._flight = None
                clock.note(late_rows=late)
        except BaseException:
            self._flight = None  # the caller fails its slots with the others
            raise
        return True

    def _enqueue(self, before: Optional[_Flight]) -> _Flight:
        """The `dispatch` phase: enqueue a decode step for the active rows and
        `pick` behind it; both calls return before the device has run them.
        What goes up from the host is a copy: the rows change under a step
        that has not run yet."""
        unread = before.rows if before is not None else {}
        with self._lock:
            rows = {int(i): self.slots[i] for i in np.flatnonzero(self.active)}
            lengths, tables = self.lengths.copy(), self.tables.copy()
            if before is None:
                tokens = self.last_tokens.copy()
            else:
                # the host knows the token of a row admitted since `before`
                # was enqueued; every other row's is still on the device
                tokens = np.full_like(self.last_tokens, -1)
                for i, st in rows.items():
                    if unread.get(i) is not st:
                        tokens[i] = self.last_tokens[i]
        # the host's arrays go into the calls as they are (an upload of its
        # own for each was 0.8 ms of a chat step's dispatch; PERF.md, PR 34)
        if before is not None:
            tokens = self._carry(before.ids, tokens)
        logits, self.pool = self._decode(self.params, self.pool, tokens, lengths, tables)
        ids, counters, self._sample_key = self._pick(
            logits, self.pool.get("counters", {}), self._sample_key)
        for leaf in (ids, *counters.values()):
            leaf.copy_to_host_async()   # on the host by the time `_emit` asks
        with self._lock:
            for i, st in rows.items():
                if self.slots[i] is not st:
                    continue  # released meanwhile: its rows are zero already
                self.lengths[i] += 1
                if len(st.generated) + (unread.get(i) is st) + 1 >= st.max_new:
                    # that was its last step: the row sits out the steps that
                    # follow, zeroed as a released one, and stays occupied
                    # until `_emit` has read the token and freed it
                    self.active[i] = False
                    self.lengths[i] = 0
                    self.tables[i] = 0
        return _Flight(ids, rows, counters)

    def _emit(self, flight: _Flight, clock: PhaseClock) -> int:
        """The `wait`, `copy`, `sample` and `finish` phases of the step
        `flight`: its ids to the slots it was enqueued for. Returns how many
        of its rows were dropped (released or re-admitted since)."""
        clock.mark("wait")  # the device's step, or what is left of it
        flight.ids.block_until_ready()
        clock.mark("copy")  # [B, 1] int32, and what the step counted
        ids = np.asarray(flight.ids)
        clock.note(**pool_counters(flight._asdict()))
        t_put = clock.mark("sample")  # every row's put carries this one read
        late = 0
        with self._lock:
            for i, st in flight.rows.items():
                if self.slots[i] is not st:
                    late += 1
                    continue
                tok = int(ids[i, 0])
                st.generated.append(tok)
                if st.token_queue is not None:
                    st.token_queue.emit(tok, t_put)
                self.last_tokens[i, 0] = tok
        clock.mark("finish")
        if self._anatomy_pending:  # falsy-dict check: zero cost per step
            t_w = anatomy.now_wall()
            for i in list(self._anatomy_pending):
                if i in flight.rows and self.slots[i] is flight.rows[i]:
                    anatomy.stamp(self._anatomy_pending.pop(i),
                                  "decode_first_token", t_w)
        for i, st in flight.rows.items():
            if self.slots[i] is st:
                self._maybe_finish(i, st.generated[-1])
        return late

    # ---- PD disaggregation handoff (reference: pd_server.py + NIXL KV
    # transfer; here KV pages travel as host arrays over the object plane) ----
    def prefill_extract(self, prompt_ids: list[int], timeout: float = 120.0) -> dict:
        """Prefill-only: compute the prompt's KV pages and first token, then
        release local blocks. Returns a handoff payload for attach_sequence."""
        fut: Future = Future()
        self._ops.put(("prefill_extract", list(prompt_ids), fut))
        return fut.result(timeout=timeout)

    def attach_sequence(self, handoff: dict, max_new_tokens: int) -> Future:
        """Adopt a prefilled sequence (KV pages + first token) and decode it
        (the decode half of PD disaggregation)."""
        fut: Future = Future()
        self._ops.put(("attach", (handoff, max_new_tokens), fut))
        return fut

    def _do_prefill_extract(self, prompt_ids: list[int]) -> dict:
        bs = self.config.block_size
        err = self._validate(prompt_ids, 1)
        if err is not None:
            raise err
        n_blocks = -(-len(prompt_ids) // bs)
        block_ids = self.allocator.alloc(n_blocks)
        state_page = 0
        padded_len = min(self._bucket(len(prompt_ids)), self.config.max_seq_len)
        padded = np.zeros((1, padded_len), dtype=np.int32)
        padded[0, : len(prompt_ids)] = prompt_ids
        try:
            if self.sequence_leaves:
                state_page = self.allocator.alloc_sequence()
            table_row = self._table_rows(1, block_ids, state_page)
            logits, self.pool = self._prefill(
                self.params, self.pool, padded, table_row,
                np.asarray([0, len(prompt_ids)], np.int32))
            first_tok = self._sample(np.asarray(logits)[0])
            kv = kv_ticket = kv_ref = None
            # the pool's own pages, leaf by leaf, by the ids of the leaf's own
            # class: [L, n, ...] a leaf of token pages, [L, 1, ...] a sequence's
            pages = {name: leaf[:, self._page_ids(name, block_ids, state_page)]
                     for name, leaf in page_leaves(self.pool).items()}
            if self.config.kv_transfer == "device":
                # the gather creates independent device arrays (pool blocks
                # free below); only a tiny ticket crosses the control plane —
                # the decode side pulls the pages device->device
                from ray_tpu.experimental import rdt

                kv_ticket = rdt.offer_device(pages)
            elif self.config.kv_transfer == "plane":
                # publish the gathered pages as one sealed plane entry
                # (written once into the transport store's mapped slot); the
                # handoff that crosses the control plane is just the
                # descriptor — a remote decode engine lands the pages with
                # zero-copy BLOB pulls (serve/kv_transport.py)
                if self.kv_publish is None:
                    raise RuntimeError(
                        "kv_transfer='plane' requires engine.kv_publish to "
                        "be bound to a KVTransport.publish")
                if set(pages) != {"k", "v"}:
                    raise RuntimeError(
                        f"kv_transfer='plane' ships one K and one V entry "
                        f"(serve/kv_transport.py); this family's pool has the "
                        f"page leaves {sorted(pages)}: use 'host' or 'device'")
                kv_ref = self.kv_publish(np.asarray(pages["k"]), np.asarray(pages["v"]))
            else:
                kv = {name: np.asarray(leaf) for name, leaf in pages.items()}
        finally:
            self.allocator.free(block_ids)
            if state_page:
                self.allocator.free_sequence(state_page)
        return {
            "kv": kv,
            "kv_ticket": kv_ticket,
            "kv_ref": kv_ref,
            "n_prefill_blocks": len(block_ids),
            "first_token": first_tok,
            "prompt_len": len(prompt_ids),
            # lets draft-model engines (spec decode) rebuild their own KV
            "prompt_ids": list(prompt_ids),
        }

    def _do_attach(self, payload, fut: Future) -> Optional[int]:
        import jax.numpy as jnp

        handoff, max_new_tokens = payload
        prompt_len = handoff["prompt_len"]
        bs = self.config.block_size
        if prompt_len <= 0:
            raise ValueError("handoff prompt_len must be positive")
        if prompt_len + max_new_tokens > self.config.max_seq_len:
            raise ValueError(
                f"attached sequence ({prompt_len}+{max_new_tokens}) exceeds "
                f"max_seq_len {self.config.max_seq_len}"
            )
        with self._lock:
            slot = next(
                (i for i in range(self.config.max_batch_size)
                 if not self.active[i] and self.slots[i] is None), None,
            )
        if slot is None:
            # decode side saturated: requeue the op for a later pass
            self._ops.put(("attach", payload, fut))
            return None
        kv = handoff.get("kv")
        ack = None
        pulled = handoff.get("_pulled")
        if kv is None and pulled is not None:
            # plane path, pre-pulled by the serving replica's request
            # thread (pd.DecodeServer.decode): the engine thread never
            # blocks on the network. Ack timing is unchanged — fired
            # below, only after the pool scatter lands.
            kv, ack = pulled
        if kv is None and handoff.get("kv_ref") is not None:
            # plane path, direct-engine fallback: land the published pages
            # in THIS node's store with zero-copy BLOB pulls; ``kv``
            # aliases the local slot (no transient whole-KV buffer). NOTE
            # this pull runs ON the engine thread — serving deployments
            # pre-pull instead (above) so a hung holder can't stall every
            # in-flight decode stream. The ack is sent only AFTER the
            # pool scatter lands, so a failure here leaves the publisher's
            # copy alive for a retry (TTL reclaims eventually).
            if self.kv_pull is None:
                raise RuntimeError(
                    "handoff carries a kv_ref but engine.kv_pull is not "
                    "bound to a KVTransport.pull")
            kv, ack = self.kv_pull(handoff["kv_ref"])
            expect = handoff.get("n_prefill_blocks")
            if expect is not None and _n_pages(kv, self.sequence_leaves) != expect:
                raise ValueError(
                    f"KV handoff shape mismatch: pulled {_n_pages(kv, self.sequence_leaves)} "
                    f"blocks, handoff says {expect}")
        if kv is None and handoff.get("kv_ticket") is not None:
            # device path: pull the pages straight into THIS process's
            # device memory over the transfer connection (no host pickle).
            # NOTE the validations above run BEFORE the pull so a rejected
            # handoff never consumes the one-shot ticket... but an
            # early-raise DOES strand the producer-side pin (offer_device
            # has no cancel — see rdt.offer_device); keep validation errors
            # rare by validating prompt_len/max_new at submission time.
            from ray_tpu.experimental import rdt

            kv = rdt.pull_device(handoff["kv_ticket"])
            expect = handoff.get("n_prefill_blocks")
            if expect is not None and _n_pages(kv, self.sequence_leaves) != expect:
                raise ValueError(
                    f"KV ticket shape mismatch: pulled {_n_pages(kv, self.sequence_leaves)} "
                    f"blocks, handoff says {expect}")
        if set(kv) != set(page_leaves(self.pool)):
            raise ValueError(
                f"KV handoff carries the leaves {sorted(kv)}; this engine's pool "
                f"has {sorted(page_leaves(self.pool))}")
        # a leaf of the payload is [L, n, ...], n its own class's pages
        n_prefill_blocks = _n_pages(kv, self.sequence_leaves)
        table = handoff.get("block_table")
        if table is not None and len(table) != n_prefill_blocks:
            # descriptor-vs-payload consistency: the block table is the
            # page-order contract for the transferred entry, so its length
            # must match what actually arrived (not what the descriptor's
            # own n_prefill_blocks claims — that would be tautological)
            raise ValueError(
                f"KV handoff block_table lists {len(table)} pages but the "
                f"transferred entry carries {n_prefill_blocks}")
        total_blocks = -(-(prompt_len + max_new_tokens) // bs)
        block_ids = self.allocator.alloc(total_blocks)
        state_page = 0
        try:
            if self.sequence_leaves:
                state_page = self.allocator.alloc_sequence()   # any free page takes it
            for name, leaf in kv.items():
                idx = self._page_ids(name, block_ids[:n_prefill_blocks], state_page)
                self.pool[name] = self.pool[name].at[:, idx].set(jnp.asarray(leaf))
            with self._lock:
                st = _Slot(fut, max_new_tokens, prompt_len, time.monotonic())
                st.generated.append(handoff["first_token"])
                st.first_token_time = time.monotonic()
                self.slots[slot] = st
                self.active[slot] = True
                self.lengths[slot] = prompt_len
                self.last_tokens[slot, 0] = handoff["first_token"]
                self.tables[slot] = self._table_rows(1, block_ids, state_page)[0]
                self.slot_blocks[slot] = block_ids
                self.slot_state_page[slot] = state_page
        except BaseException:
            self.allocator.free(block_ids)
            if state_page:
                self.allocator.free_sequence(state_page)
            raise
        if ack is not None:
            try:
                ack()  # pages landed in the pool: free both plane copies
            except Exception:
                pass  # publisher gone/old-wire: its TTL sweep reclaims
        rid = handoff.get("_rid")
        if rid is not None:
            self._anatomy_pending[slot] = rid
        # a 1-token (or 0-token) request is already complete with first_token
        self._maybe_finish(slot, handoff["first_token"])
        return slot
