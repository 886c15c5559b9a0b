"""Speculative decoding: draft-propose / target-verify over paged KV.

The load-bearing property is the greedy invariant — committed output equals
the target model's greedy decode exactly, for ANY draft model. A good draft
only raises tokens-per-step; a garbage draft only lowers it.
"""
import dataclasses

import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
from ray_tpu.serve.spec_decode import SpecDecodeConfig, SpecDecodeLLMEngine


def _tiny(vocab=128):
    return dataclasses.replace(llama.LlamaConfig.tiny(), vocab_size=vocab)


def _baseline_tokens(prompt, max_new, seed=0):
    eng = PagedLLMEngine(PagedLLMConfig(model_config=_tiny(), max_batch_size=2,
                                        max_seq_len=128, temperature=0.0),
                         seed=seed)
    try:
        return eng.generate_sync(prompt, max_new).token_ids
    finally:
        eng.shutdown()


@pytest.mark.parametrize("draft_seed", [0, 99])
def test_greedy_invariant_any_draft(draft_seed):
    """draft == target (seed 0) and a random unrelated draft (seed 99) must
    both reproduce the target's exact greedy output."""
    prompt = [5, 17, 3, 42]
    max_new = 12
    expected = _baseline_tokens(prompt, max_new, seed=0)
    cfg = SpecDecodeConfig(model_config=_tiny(), draft_model_config=_tiny(),
                           max_batch_size=2, max_seq_len=128, temperature=0.0,
                           num_speculative_tokens=3)
    import jax

    draft_params = llama.init(cfg.draft_model_config, jax.random.PRNGKey(draft_seed))
    eng = SpecDecodeLLMEngine(cfg, draft_params=draft_params, seed=0)
    try:
        got = eng.generate_sync(prompt, max_new).token_ids
    finally:
        eng.shutdown()
    assert got == expected, f"spec(draft_seed={draft_seed}) diverged from target greedy"


def test_identical_draft_accepts_everything():
    """With draft == target, every proposal is accepted: the engine finishes a
    long generation in ~ceil(max_new/(K+1)) verify steps. We can't count steps
    directly, but all tokens must match and multi-slot batching must hold."""
    cfg = SpecDecodeConfig(model_config=_tiny(), draft_model_config=_tiny(),
                           max_batch_size=3, max_seq_len=128, temperature=0.0,
                           num_speculative_tokens=4)
    import jax

    # same seed => same params => p(draft) == p(target)
    params = llama.init(cfg.model_config, jax.random.PRNGKey(0))
    eng = SpecDecodeLLMEngine(cfg, params=params, draft_params=params)
    try:
        prompts = [[5, 17, 3, 42], [9, 9, 2], [77, 1, 30, 8, 4]]
        futs = [eng.generate(p, 10) for p in prompts]
        results = [f.result(timeout=180) for f in futs]
        for p, r in zip(prompts, results):
            assert r.num_generated == 10
            assert r.token_ids == _baseline_tokens(p, 10, seed=0), p
    finally:
        eng.shutdown()


def test_eos_respected_mid_window():
    """An eos token inside an accepted window truncates the output there."""
    cfg = SpecDecodeConfig(model_config=_tiny(), draft_model_config=_tiny(),
                           max_batch_size=2, max_seq_len=128, temperature=0.0,
                           num_speculative_tokens=4)
    import jax

    params = llama.init(cfg.model_config, jax.random.PRNGKey(0))
    base = PagedLLMEngine(PagedLLMConfig(model_config=_tiny(), max_batch_size=2,
                                         max_seq_len=128, temperature=0.0),
                          params=params)
    try:
        ref_toks = base.generate_sync([5, 17, 3, 42], 12).token_ids
    finally:
        base.shutdown()
    eos = ref_toks[5]  # a token we know appears at step 5
    cfg = dataclasses.replace(cfg, eos_token_id=int(eos))
    eng = SpecDecodeLLMEngine(cfg, params=params, draft_params=params)
    try:
        res = eng.generate_sync([5, 17, 3, 42], 12)
    finally:
        eng.shutdown()
    assert res.token_ids == ref_toks[: ref_toks.index(eos) + 1]
    assert res.finish_reason == "stop"


def test_config_validation():
    with pytest.raises(ValueError, match="draft_model_config"):
        SpecDecodeLLMEngine(SpecDecodeConfig(model_config=_tiny()))
    with pytest.raises(ValueError, match="temperature"):
        SpecDecodeLLMEngine(SpecDecodeConfig(
            model_config=_tiny(), draft_model_config=_tiny(), temperature=0.7))
    with pytest.raises(ValueError, match="vocabulary"):
        SpecDecodeLLMEngine(SpecDecodeConfig(
            model_config=_tiny(), draft_model_config=_tiny(vocab=64)))


def test_streaming_with_spec_decode():
    cfg = SpecDecodeConfig(model_config=_tiny(), draft_model_config=_tiny(),
                           max_batch_size=2, max_seq_len=128, temperature=0.0,
                           num_speculative_tokens=3)
    import jax

    params = llama.init(cfg.model_config, jax.random.PRNGKey(0))
    eng = SpecDecodeLLMEngine(cfg, params=params, draft_params=params)
    try:
        toks = list(eng.generate_stream([5, 17, 3, 42], 8))
        assert toks == _baseline_tokens([5, 17, 3, 42], 8, seed=0)
    finally:
        eng.shutdown()


def test_pd_attach_with_spec_decode():
    """Prefill on one engine, attach + speculative decode on another: output
    matches the plain engine's greedy decode (draft KV rebuilt from the
    handoff's prompt_ids)."""
    import jax

    tiny = _tiny()
    params = llama.init(tiny, jax.random.PRNGKey(0))
    prompt = [5, 17, 3, 42]
    expected = _baseline_tokens(prompt, 10, seed=0)

    prefiller = PagedLLMEngine(PagedLLMConfig(model_config=tiny, max_batch_size=2,
                                              max_seq_len=128, temperature=0.0),
                               params=params)
    try:
        handoff = prefiller.prefill_extract(prompt)
    finally:
        prefiller.shutdown()
    assert handoff["prompt_ids"] == prompt

    cfg = SpecDecodeConfig(model_config=tiny, draft_model_config=tiny,
                           max_batch_size=2, max_seq_len=128, temperature=0.0,
                           num_speculative_tokens=3)
    eng = SpecDecodeLLMEngine(cfg, params=params, draft_params=params)
    # the draft's mirror pool is a pool like the target's: one table row
    # addresses the same blocks of both ([L, NB, block_size, Hkv * Dp])
    assert eng.draft_pool["k"].shape == eng.pool["k"].shape
    assert handoff["kv"]["k"].shape[1] == handoff["n_prefill_blocks"]
    try:
        res = eng.attach_sequence(handoff, 10).result(timeout=180)
    finally:
        eng.shutdown()
    assert res.token_ids == expected
