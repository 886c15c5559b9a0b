"""Speculative decoding over the paged-KV engine.

Parity: the reference delegates speculative decoding to vLLM
(`llm/_internal/serve/` engine_kwargs pass-through: speculative_config /
num_speculative_tokens). Here it is native and TPU-shaped: a small draft
model proposes K tokens autoregressively (cheap host loop over tiny jitted
decodes), then the target model scores all K+1 positions in ONE batched
paged forward — the verify step keeps the MXU busy with a [B, K+1] window
instead of K+1 sequential [B, 1] decodes.

Greedy invariant: with temperature 0 the committed output is exactly the
target model's greedy decode REGARDLESS of draft quality — a bad draft only
costs speed (acceptance drops toward 1 committed token/step, the base decode
rate), never correctness. Both KV pools share one block allocator: the draft
pool mirrors the target pool's block ids, so a sequence's table row addresses
its pages in both.

Rejected-position hygiene: verify writes target KV for all K+1 window
positions; committing only a prefix leaves stale KV at future positions,
which the causal position mask already excludes — the next window overwrites
them (same argument for the draft pool).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import numpy as np

from ray_tpu.models import llama
from ray_tpu.serve.llm_paged import (_DECODE_PHASES, PagedLLMConfig,
                                     PagedLLMEngine, page_leaves, paged_step,
                                     pool_counters)

_SPEC_DECODE_PHASES = ("draft",) + _DECODE_PHASES


@dataclasses.dataclass
class SpecDecodeConfig(PagedLLMConfig):
    draft_model_config: Optional[llama.LlamaConfig] = None
    num_speculative_tokens: int = 4


class SpecDecodeLLMEngine(PagedLLMEngine):
    """Draft-propose / target-verify continuous batching (greedy sampling)."""

    def __init__(self, config: SpecDecodeConfig, params=None,
                 draft_params=None, seed: int = 0):
        if config.draft_model_config is None:
            raise ValueError("SpecDecodeConfig.draft_model_config is required")
        if config.num_speculative_tokens < 1:
            raise ValueError("num_speculative_tokens must be >= 1")
        if config.temperature > 0:
            raise ValueError(
                "speculative decoding implements the greedy acceptance rule; "
                "temperature must be 0"
            )
        dm, tm = config.draft_model_config, config.model_config
        if dm.vocab_size != tm.vocab_size:
            raise ValueError("draft and target models must share a vocabulary")
        self._draft_params_init = draft_params
        super().__init__(config, params=params, seed=seed)

    def _init_backend(self) -> None:
        super()._init_backend()
        jax = self._jax
        cfg = self.config.model_config
        dcfg = self.config.draft_model_config
        bs = self.config.block_size
        if self.sequence_leaves:
            # a state that sums over the whole past has no earlier position to
            # go back to at all (ROADMAP R6: a state rewind under speculation),
            # and a window layer's ring has lost the rows that the window's later
            # positions wrote over (ROADMAP R2: a speculative window over a ring)
            raise ValueError(
                f"speculative decoding rewinds `lengths` after a rejected window, and "
                f"this family's pool keeps {sorted(self.sequence_leaves)} as ONE page a "
                f"sequence (a running state, or a window layer's ring), which every position "
                f"of the window has advanced or written over: what the committed position "
                f"resumes from is gone and cannot be stepped from again")
        per_block = sorted(name for name, leaf in page_leaves(self.pool).items()
                           if leaf.shape[2] != bs)
        if per_block:
            # a row a TOKEN can be rewound: a rejected window's rows lie past
            # the committed length and the next window overwrites them. Rows a
            # BLOCK (a recurrent state's last few positions) cannot: a window
            # that ran K + 1 positions ahead has overwritten the rows the
            # committed position must resume from (ROADMAP R8)
            raise ValueError(
                f"speculative decoding rewinds `lengths` after a rejected window, and "
                f"this family's pool keeps {per_block} as rows a block, not a row a "
                f"token ({self.pool[per_block[0]].shape[2]} rows where block_size is "
                f"{bs}): the state at the committed position is overwritten by the "
                f"window's later positions and cannot be stepped from again")
        self.draft_params = (self._draft_params_init
                             if self._draft_params_init is not None
                             else llama.init(dcfg, jax.random.PRNGKey(7)))
        # mirror pool: same block ids resolve in both pools via one table
        self.draft_pool = llama.init_kv_pool(dcfg, self.pool_blocks, bs)

        step = partial(paged_step, block_size=bs, platform=self.platform)
        # the draft's prefill fills its pool and proposes nothing: no head;
        # it always takes the whole prompt from position 0, so it attends
        # over its own rows (`_draft_prefill_slot`)
        self._draft_prefill = step("draft_prefill", dcfg, head=None, table_first=True,
                                   fresh=True)
        self._draft_decode = step("draft_decode", dcfg, head=0)
        # [B, 2] window: re-process [prev, last] so a fully-accepted prior
        # step's final proposal (whose draft KV was never written — the
        # classic bonus-token hole) gets its page filled before proposing
        self._draft_decode2 = step("draft_decode2", dcfg, head=1)
        # [B, K+1] window scored in one target forward
        self._verify = step("verify", cfg, head="all")
        # second-to-last committed token per slot (the 2-token window's head)
        self.prev_tokens = np.zeros((self.config.max_batch_size, 1), dtype=np.int32)

    # ---- admission: also prefill the DRAFT pool for the slot ----
    def _admit_one(self, prompt, max_new, fut, t_enq, tq, rid, slot) -> bool:
        jnp = self._jnp
        admitted = super()._admit_one(prompt, max_new, fut, t_enq, tq, rid, slot)
        if not admitted or not self.active[slot]:
            # not admitted, rejected, or already finished (max_new reached)
            return admitted
        try:
            self._draft_prefill_slot(slot, prompt)
        except Exception as e:  # noqa: BLE001 - fail THIS request, keep serving
            st = self.slots[slot]
            with self._lock:
                self._release_slot(slot)
            if st is not None:
                if not st.future.done():
                    st.future.set_exception(e)
                if st.token_queue is not None:
                    st.token_queue.put(None)
        return True

    def _draft_prefill_slot(self, slot: int, prompt) -> None:
        """Draft-prefill the WHOLE prompt (start 0): independent of the
        target's prefix-cache skip, and shared prefix blocks get identical
        draft KV rewritten, so sharing stays sound."""
        jnp = self._jnp
        bucket = min(self._bucket(len(prompt)), self.config.max_seq_len)
        padded = np.zeros((1, bucket), dtype=np.int32)
        padded[0, : len(prompt)] = prompt
        table_row = self.tables[slot][None, :]
        _, self.draft_pool = self._draft_prefill(
            self.draft_params, self.draft_pool, jnp.asarray(padded),
            jnp.asarray(table_row), jnp.asarray([0, len(prompt)], np.int32),
        )
        self.prev_tokens[slot, 0] = prompt[-1]

    def _release_slot(self, i: int) -> None:
        super()._release_slot(i)
        self.prev_tokens[i] = 0

    def _do_attach(self, payload, fut):
        """PD attach: also rebuild this sequence's DRAFT KV from the prompt
        ids carried in the handoff — without it, acceptance collapses to ~0
        and the decode half of PD becomes slower than plain paged decode."""
        handoff, _ = payload
        prompt_ids = handoff.get("prompt_ids")
        if not prompt_ids:
            raise NotImplementedError(
                "speculative decode attach requires 'prompt_ids' in the "
                "handoff (produced by prefill_extract)"
            )
        slot = super()._do_attach(payload, fut)
        if slot is not None and self.active[slot]:
            self._draft_prefill_slot(slot, prompt_ids)
        return slot

    # ---- decode: propose K draft tokens, verify in one target pass ----
    def _step_decode(self) -> bool:
        jnp = self._jnp
        if not self.active.any():
            return False
        K = self.config.num_speculative_tokens
        B = self.config.max_batch_size
        # the engine's own engine/decode record, with the proposals as one
        # more phase in front; `wait` is the verify pass on the device
        with self._decode_clock(_SPEC_DECODE_PHASES) as clock:
            proposals = np.zeros((B, K), dtype=np.int32)
            base_lengths = self.lengths.copy()
            # device residents hoisted out of the loop: tables/lengths don't change
            # within a step, so upload once and derive shifted lengths on device
            tables_dev = jnp.asarray(self.tables)
            base_dev = jnp.asarray(base_lengths)
            # first draft step: [prev, last] 2-token window (fills any bonus-token
            # draft-KV hole from a fully-accepted prior step), logits propose p1
            window2 = np.concatenate([self.prev_tokens, self.last_tokens], axis=1)
            dlogits, self.draft_pool = self._draft_decode2(
                self.draft_params, self.draft_pool, jnp.asarray(window2),
                jnp.maximum(base_dev - 1, 0), tables_dev,
            )
            proposals[:, 0] = np.argmax(np.asarray(dlogits), axis=-1)
            cur = proposals[:, 0:1]
            for k in range(1, K):
                dlogits, self.draft_pool = self._draft_decode(
                    self.draft_params, self.draft_pool, jnp.asarray(cur),
                    base_dev + k, tables_dev,
                )
                proposals[:, k] = np.argmax(np.asarray(dlogits), axis=-1)
                cur = proposals[:, k : k + 1]
            clock.mark("dispatch")
            window = np.concatenate([self.last_tokens, proposals], axis=1)  # [B, K+1]
            logits, self.pool = self._verify(
                self.params, self.pool, jnp.asarray(window), base_dev, tables_dev,
            )
            clock.mark("wait")
            logits.block_until_ready()
            clock.mark("copy")
            logits_np = np.asarray(logits)  # [B, K+1, V]
            clock.note(**pool_counters(self.pool))  # what the verify pass counted
            t_put = clock.mark("sample")
            target_preds = np.argmax(logits_np, axis=-1)  # [B, K+1]
            finished = []
            with self._lock:
                for i in range(B):
                    if not self.active[i]:
                        continue
                    st = self.slots[i]
                    # accept proposals while they match the target's greedy choice
                    a = 0
                    while a < K and proposals[i, a] == target_preds[i, a]:
                        a += 1
                    committed = list(proposals[i, :a]) + [int(target_preds[i, a])]
                    remaining = st.max_new - len(st.generated)
                    committed = committed[: max(0, remaining)]
                    eos = self.config.eos_token_id
                    if eos >= 0 and eos in committed:
                        committed = committed[: committed.index(eos) + 1]
                    for tok in committed:
                        st.generated.append(int(tok))
                        if st.token_queue is not None:
                            st.token_queue.emit(int(tok), t_put)
                    self.lengths[i] = base_lengths[i] + len(committed)
                    if len(committed) >= 2:
                        self.prev_tokens[i, 0] = committed[-2]
                    elif committed:
                        self.prev_tokens[i, 0] = self.last_tokens[i, 0]
                    if committed:
                        self.last_tokens[i, 0] = committed[-1]
                    finished.append(i)
            clock.mark("finish")
            for i in finished:
                if self.active[i]:
                    self._maybe_finish(i, self.slots[i].generated[-1])
        return True
