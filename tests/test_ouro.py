"""models/ouro.py (a layer stack run several times, a cache layer for every
pass) at a tiny size against the plain reference
(benchmarks/reference/ouro_reference.py): the cache-less forward, prefill then
decode through `forward_paged`, and the paged engine with two sequences live.
On LOGITS, in float32; and each of four wrong programs fails the comparison."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import ouro as family
from benchmarks.reference import ouro_reference
from ray_tpu.models import llama, model_of, ouro
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine
from tests.test_paged_attention import PAGE_WRITE_CASES, check_page_write_against_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (batched heads against a loop over heads, a cache against a full
# recompute): they differ by float32 rounding, measured 8e-7 of the logits'
# size. 1e-5 admits that; each wrong program below misses by 0.4 to 1.1.
TOL = 1e-5
BS = 16


@pytest.fixture(scope="module")
def tiny():
    """4 layers x 3 passes, hidden 64, 4 heads of 16: the benchmark's CPU
    stand-in of the Ouro configuration."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "ouro-serve.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert (cfg.num_layers, cfg.loop_steps, cfg.hidden_size, cfg.num_heads) == (4, 3, 64, 4)
    params = jax.jit(lambda k: ouro.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 31))
    # norm weights other than one, so that a norm in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    params["layers"] = {k: noisy(i, v) if k.endswith("_norm") else v
                        for i, (k, v) in enumerate(sorted(params["layers"].items()))}
    params["final_norm"] = noisy(99, params["final_norm"])
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 46)
    return model, cfg, params, tokens


def _miss(got, want) -> float:
    """The benchmark's two measures (`serve_cell.check_against_reference`),
    the larger: rms error / rms logit and max error / max logit."""
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


def _prefill_then_decode(params, tokens, cfg, n_prompt: int, use_kernel: bool,
                         forward_paged=ouro.forward_paged, slot: int = 1):
    """Logits of positions n_prompt - 1 .. len(tokens) - 1 of ONE sequence in
    slot `slot` of 2: a prefill of `n_prompt` tokens, then one decode step a
    token (the kernel interpreted, or the gathered view)."""
    pool = ouro.init_kv_pool(cfg, 9, BS)
    assert pool["k"].shape[0] == cfg.loop_steps * cfg.num_layers
    tables = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)
    step = jax.jit(lambda pool, toks, lengths, kernel: forward_paged(
        params, toks, cfg, pool, tables, lengths, BS, use_kernel=kernel),
        static_argnums=3)
    toks = np.zeros((2, n_prompt), np.int32)
    toks[slot] = tokens[:n_prompt]
    logits, pool = step(pool, jnp.asarray(toks), jnp.zeros(2, jnp.int32), False)
    rows = [logits[slot, -1]]
    for t in range(n_prompt, len(tokens)):
        last = np.zeros((2, 1), np.int32)
        last[slot] = tokens[t]
        lengths = np.zeros(2, np.int32)
        lengths[slot] = t
        logits, pool = step(pool, jnp.asarray(last), jnp.asarray(lengths), use_kernel)
        rows.append(logits[slot, 0])
    return np.stack(rows)


def test_forward_matches_the_reference(tiny):
    model, cfg, params, tokens = tiny
    want = ouro_reference.logits(params, tokens, model)
    got = ouro.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert _miss(got, want) < TOL
    # the record the engines and the train step take
    assert model_of(cfg) is ouro.MODEL
    assert set(params["layers"]) == set(ouro.logical_axes(cfg)["layers"])
    assert {"attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm"} <= set(params["layers"])


@pytest.mark.parametrize("use_kernel", [True, False], ids=["kernel", "gathered"])
def test_prefill_then_six_decode_steps_match_the_reference(tiny, use_kernel):
    model, cfg, params, tokens = tiny
    want = ouro_reference.logits(params, tokens, model)
    n_prompt = len(tokens) - 6
    got = _prefill_then_decode(params, tokens, cfg, n_prompt, use_kernel)
    assert got.shape[0] == 7
    assert _miss(got, want[n_prompt - 1:]) < TOL


@pytest.mark.parametrize("branch", ["dense", "flash"])
def test_a_fresh_prefill_over_its_own_rows_is_the_table_prefill(tiny, branch):
    """A prompt that starts at position 0, prefilled as the engine admits it
    (40 tokens live in a bucket of 48, the head on the last live row) and told
    `fresh`: every pass of every layer attends over the rows it has in hand
    and writes them to ITS cache layer, `pass * L + layer`. The reference's
    logits, and the pool the table-reading prefill leaves, through the dense
    [S, S] product and through the flash forward interpreted."""
    model, cfg, params, tokens = tiny
    live, bucket = 40, 48
    toks = np.zeros((2, bucket), np.int32)
    toks[1, :live] = tokens[:live]
    tables = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)
    step = jax.jit(lambda fresh, kernel: ouro.forward_paged(
        params, jnp.asarray(toks), cfg, ouro.init_kv_pool(cfg, 9, BS), tables,
        jnp.zeros(2, jnp.int32), BS, head_rows=jnp.asarray([0, live - 1], jnp.int32),
        fresh=fresh, use_kernel=kernel), static_argnums=(0, 1))
    want, want_pool = step(False, None)
    got, got_pool = step(True, True if branch == "flash" else None)
    assert _miss(got[1, 0], ouro_reference.logits(params, tokens[:live], model)[-1]) < TOL
    assert _miss(got[1], want[1]) < TOL
    # block 0 is the idle row's garbage; every cache layer of the 12 is written
    for name in ("k", "v"):
        assert np.asarray(want_pool[name][:, 1:]).reshape(12, -1).any(axis=1).all()
        assert _miss(got_pool[name][:, 1:], want_pool[name][:, 1:]) < TOL


@pytest.mark.parametrize("case", PAGE_WRITE_CASES)
def test_a_fresh_prefill_s_pages_leave_every_pass_s_cache_layer_the_row_scatter_s(
        monkeypatch, tiny, case):
    """`loop_steps` > 1: every pass of every layer writes its pages to ITS
    cache layer, `pass * L + layer` (the layer index is traced, inside two
    scans), and all 12 hold what the row scatter left, exactly, outside the
    garbage block; the same logits and the same next decode step."""
    _, cfg, params, _ = tiny
    assert cfg.loop_steps > 1 and ouro.init_kv_pool(cfg, 2, 4)["k"].shape[0] == 12
    check_page_write_against_rows(
        monkeypatch, lambda tokens, pool, tables, lengths, **kw: ouro.forward_paged(
            params, tokens, cfg, pool, tables, lengths, **kw),
        lambda blocks, bs: ouro.init_kv_pool(cfg, blocks, bs), cfg.vocab_size, case)


def test_the_engine_with_two_sequences_live_matches_the_reference(tiny):
    """Through `PagedLLMEngine`: two prompts of different lengths admitted one
    after the other and decoded together; the logits each was sampled from
    are the reference's for the prompt and the engine's own tokens."""
    model, cfg, params, tokens = tiny
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=9, prefill_buckets=(16, 32)), params=params, external_step=True)
    seen = []  # (step, inputs, logits)
    prefill, decode = eng._prefill, eng._decode

    def keep_prefill(params, pool, toks, table, start):
        logits, pool = prefill(params, pool, toks, table, start)
        seen.append(("prefill", None, np.asarray(logits)))
        return logits, pool

    def keep_decode(params, pool, last, lengths, tables):
        logits, pool = decode(params, pool, last, lengths, tables)
        seen.append(("decode", np.flatnonzero(eng.active), np.asarray(logits)))
        return logits, pool

    eng._prefill, eng._decode = keep_prefill, keep_decode
    prompts = [list(map(int, tokens[:21])), list(map(int, tokens[21:30]))]
    try:
        futs = [eng.generate(p, 5) for p in prompts]
        for _ in range(20):
            if all(f.done() for f in futs):
                break
            eng.step_once()
        out = [f.result(0) for f in futs]
    finally:
        eng.shutdown()
    assert eng.pool["k"].shape[0] == 12 and eng.model is ouro.MODEL
    assert any(kind == "decode" and len(live) == 2 for kind, live, _ in seen)
    prefills = [l for kind, _, l in seen if kind == "prefill"]
    for slot, (prompt, res) in enumerate(zip(prompts, out)):
        assert len(res.token_ids) == 5
        rows = [prefills[slot][0]] + [  # the prefill hands back the sampled row alone
            l[slot] for kind, live, l in seen if kind == "decode" and slot in live]
        seq = prompt + res.token_ids[:-1]
        want = ouro_reference.logits(params, seq, model)[len(prompt) - 1:]
        assert len(rows) == 5
        assert _miss(np.stack(rows), want) < TOL


def _one_pass_fewer(monkeypatch, params, cfg):
    return params, dataclasses.replace(cfg, loop_steps=cfg.loop_steps - 1)


def _passes_share_one_cache(monkeypatch, params, cfg):
    layer = llama.decoder_layer
    monkeypatch.setattr(llama, "decoder_layer", lambda *a, index=None, **kw: layer(
        *a, index=index % cfg.num_layers, **kw))
    return params, cfg


def _no_norm_between_passes(monkeypatch, params, cfg):
    """The final norm applied before the head alone: `rms_norm` passes x on
    where it is handed the trunk's own `final_norm`, and the head is given a
    copy of it to norm with."""
    rms_norm, lm_head = llama.rms_norm, llama.lm_head
    final = params["final_norm"]
    monkeypatch.setattr(llama, "rms_norm", lambda x, w, eps: x if w is final
                        else rms_norm(x, w, eps))
    monkeypatch.setattr(llama, "lm_head", lambda p, x, c, normed=False: lm_head(
        {**p, "final_norm": p["final_norm"] + 0}, x, c))
    return params, cfg


def _an_output_norm_left_out(monkeypatch, params, cfg):
    layers = {k: v for k, v in params["layers"].items() if k != "mlp_out_norm"}
    return {**params, "layers": layers}, cfg


@pytest.mark.parametrize("wrong", [
    _one_pass_fewer, _passes_share_one_cache, _no_norm_between_passes,
    _an_output_norm_left_out], ids=lambda f: f.__name__.strip("_"))
def test_a_wrong_program_fails_the_comparison(tiny, monkeypatch, wrong):
    """What the comparison must catch, each made of the program itself: a pass
    left out, every pass reading and writing layer l's cache at index l (for
    `pass * L + l`), no norm between passes, a sub-layer's output norm left
    out. Prefill then decode, as the benchmark's check does it; the shared
    cache is invisible to a cache-less forward and to a prefill alone."""
    model, cfg, params, tokens = tiny
    want = ouro_reference.logits(params, tokens, model)
    n_prompt = len(tokens) - 6

    def run():  # eagerly traced anew: the patches are seen
        wrong_params, wrong_cfg = wrong(monkeypatch, params, cfg)
        forward_paged = lambda p, *a, **kw: ouro.forward_paged(
            wrong_params, a[0], wrong_cfg, *a[2:], **kw)
        return _prefill_then_decode(params, tokens, cfg, n_prompt, False, forward_paged)

    got = run()
    assert _miss(got, want[n_prompt - 1:]) > 0.1
    if wrong is _passes_share_one_cache:
        # the prefill's own logits do not show it: within a call every pass
        # reads what it wrote itself
        assert _miss(got[:1], want[n_prompt - 1:n_prompt]) < TOL


def test_a_configuration_without_a_family_is_named_and_a_plain_config_serves_ouro():
    """`model_of` on something that is no family's configuration says so by
    name (not a KeyError or a call of None). A plain `LLMConfig` of Ouro's
    configuration is SERVED by the default builder: the one engine takes any
    family that gives `forward_paged`, and gives the plain forward's greedy
    tokens."""
    from ray_tpu.serve.llm import LLMConfig, build_llm_deployment
    from tests.test_llm_paged import _replica

    with pytest.raises(TypeError, match="no model family's configuration"):
        model_of(object())
    cfg = ouro.OuroConfig.tiny()
    server = _replica(build_llm_deployment(LLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64)))
    try:
        assert type(server.engine) is PagedLLMEngine
        prompt = [int(t) for t in np.random.default_rng(6).integers(1, cfg.vocab_size, 9)]
        got = server({"prompt_ids": prompt, "max_tokens": 5})["token_ids"]
        seq = list(prompt)
        for _ in range(5):
            logits = ouro.forward(server.engine.params, jnp.asarray([seq], jnp.int32), cfg)
            seq.append(int(np.argmax(np.asarray(logits[0, -1]))))
        assert got == seq[len(prompt):]
    finally:
        server.shutdown()
