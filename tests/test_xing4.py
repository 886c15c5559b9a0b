"""models/xing4.py (the DeepSeek-V3 block of models/kimi_k2.py on a residual
of four streams that Sinkhorn-projected hyper-connections mix around every
sub-layer, `llama.HyperConnections` in the one layer and the one trunk) at a
tiny size against the plain reference (benchmarks/reference/xing4_reference.py).
On LOGITS, in float32: the cache-less forward, prefill then decode through
`forward_paged` and through the paged engine, `head_rows`; the stream-mixing
map doubly stochastic after 20 iterations and not after 1; the trunk without
a residual strategy the one-stream trunk it was, bit for bit, for every other
family; the eight shares of the experts adding up to the uncut layer; and
wrong programs that must miss."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness.families import xing4 as family
from benchmarks.reference import kimi_k2_reference, xing4_reference as reference
from ray_tpu.models import kimi_k2, llama, model_of, moe, ouro, xing4
from ray_tpu.serve.llm_paged import PagedLLMConfig, PagedLLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Program and reference compute the same mathematics in float32 in another
# order (vectors an entry of a map against [S, n, n] arrays, absorbed against
# per-head keys, a cache against a full recompute, sorted rows against a dense
# weighted sum): measured 4e-7 to 3e-6 of the logits' size. 2e-5 admits that;
# the wrong programs below miss by 4e-3 (one Sinkhorn iteration) to 0.47.
TOL = 2e-5
BS = 16


@pytest.fixture(scope="module")
def tiny():
    """2 dense + 2 expert layers, hidden 64, four streams, 4 heads of 32 + 16 /
    32, ranks 48 and 128, 16 experts of which the second half is held, 4 a
    token: the benchmark's CPU stand-in of the Xing4 configuration."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "xing4-serve.json")) as f:
        file = json.load(f)
    model = {k: file[k] for k in family.MODEL_KEYS}
    cfg = family.model_config(model, remat=False)
    assert (cfg.first_k_dense, cfg.base.num_layers, cfg.experts.experts_held) == (2, 2, (8, 8))
    assert cfg.hyper == llama.HyperConnections(n=4, sinkhorn_iters=20, eps=1e-6,
                                               clamp=(-30.0, 30.0))
    params = jax.jit(lambda k: xing4.init(cfg, k))(jax.random.PRNGKey(2 ** 31 + 37))
    # norm weights, alphas other than their start, so that one in the wrong place shows
    noisy = lambda i, v: v * (1 + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape))
    for stack in ("lead_layers", "layers"):
        params[stack] = {k: noisy(i, v) if k.endswith(("_norm", "_alpha")) else v
                         for i, (k, v) in enumerate(sorted(params[stack].items()))}
    tokens = np.random.default_rng(0).integers(0, model["vocab_size"], 46)
    return model, cfg, params, tokens


def _miss(got, want) -> float:
    """The benchmark's two measures (`serve_cell.check_against_reference`),
    the larger: rms error / rms logit and max error / max logit."""
    got, want = np.asarray(got), np.asarray(want)
    err = got - want
    return max(float(np.sqrt(np.mean(err ** 2)) / np.sqrt(np.mean(want ** 2))),
               float(np.abs(err).max() / np.abs(want).max()))


TABLES = jnp.asarray([[0, 0, 0, 0], [3, 1, 7, 2]], jnp.int32)


def _step(params, cfg):
    return jax.jit(lambda pool, toks, lengths, **kw: xing4.forward_paged(
        params, toks, cfg, pool, TABLES, lengths, BS, **kw),
        static_argnames=("use_kernel", "fresh"))


def _prefill_then_decode(params, tokens, cfg, n_prompt: int, **decode):
    """Logits of positions n_prompt - 1 .. len(tokens) - 1 of ONE sequence in
    slot 1 of 2 (its pages out of order): a prefill of `n_prompt` tokens with
    `head_rows`, then a token a step; and the pool's counters a step."""
    pool = xing4.init_kv_pool(cfg, 9, BS)
    assert pool["latent"].shape == (cfg.cache_layers, 9, BS, cfg.latent_row)
    step = _step(params, cfg)
    toks = np.zeros((2, n_prompt), np.int32)
    toks[1] = tokens[:n_prompt]
    logits, pool = step(pool, jnp.asarray(toks), jnp.zeros(2, jnp.int32),
                        head_rows=jnp.asarray([0, n_prompt - 1], jnp.int32))
    assert logits.shape == (2, 1, cfg.vocab_size)
    rows, counted = [logits[1, 0]], [jax.tree.map(np.asarray, pool["counters"])]
    for t in range(n_prompt, len(tokens)):
        last = np.zeros((2, 1), np.int32)
        last[1] = tokens[t]
        logits, pool = step(pool, jnp.asarray(last), jnp.asarray([0, t], jnp.int32), **decode)
        rows.append(logits[1, 0])
        counted.append(jax.tree.map(np.asarray, pool["counters"]))
    return np.stack(rows), counted


@pytest.mark.parametrize("path", ["cache-less", "decode-kernel", "decode-gathered",
                                  "prefill-own-rows"])
def test_every_forward_gives_the_reference_s_logits(tiny, path):
    """The cache-less forward at every position; a prefill whose head runs on
    the one row `head_rows` names, then six decode steps through the latent
    kernel (interpreted) or over the gathered view; a fresh prefill over its
    own rows (`kimi_k2.latent_attention`'s branch, which this family gets with
    the function: a 64-token bucket with 18 rows of padding, the flash forward
    interpreted), which is also the table program's; four streams wide
    throughout: all are the reference's logits."""
    model, cfg, params, tokens = tiny
    want = reference.logits(params, tokens, model)
    if path == "prefill-own-rows":
        toks = np.zeros((2, 64), np.int32)
        toks[1, :len(tokens)] = tokens
        run = lambda **kw: _step(params, cfg)(
            xing4.init_kv_pool(cfg, 9, BS), jnp.asarray(toks), jnp.zeros(2, jnp.int32), **kw)
        (got, pool), (by_table, table_pool) = run(fresh=True, use_kernel=True), run()
        assert _miss(got[1, :len(tokens)], want) < TOL and _miss(got, by_table) < TOL
        np.testing.assert_array_equal(np.asarray(pool["latent"][0]),
                                      np.asarray(table_pool["latent"][0]))
        np.testing.assert_allclose(np.asarray(pool["latent"]), np.asarray(table_pool["latent"]),
                                   rtol=1e-5, atol=1e-5)
        return
    if path == "cache-less":
        got = jax.jit(lambda t: xing4.forward(params, t, cfg))(jnp.asarray(tokens)[None])
        assert got.shape == (1, len(tokens), cfg.vocab_size) and _miss(got[0], want) < TOL
        return
    n_prompt = len(tokens) - 6
    got, counted = _prefill_then_decode(params, tokens, cfg, n_prompt,
                                        use_kernel=path == "decode-kernel")
    assert got.shape[0] == 7 and _miss(got, want[n_prompt - 1:]) < TOL
    # the two counters a step: pairs routed to the 8 held of 16 experts over 2
    # expert layers and both slots' tokens, and the maps' residue, float32
    assert 0 < counted[0]["moe_rows"] <= 2 * n_prompt * 4 * 2
    for c in counted:
        assert c["hc_residue"].dtype == np.float32 and 0 < c["hc_residue"] < 1e-5
    assert model_of(cfg) is xing4.MODEL and xing4.MODEL.loss is None
    axes = xing4.logical_axes(cfg)
    assert set(params["layers"]) == set(axes["layers"])
    assert set(params["lead_layers"]) == set(axes["lead_layers"])
    assert params["layers"]["hc_attn_phi"].shape == (2, 4 * 64, 4 + 4 + 16)


@pytest.mark.parametrize("iters, doubly_stochastic", [(20, True), (1, False)])
def test_h_res_is_doubly_stochastic_after_20_iterations_and_not_after_1(iters, doubly_stochastic):
    """`HyperConnections.sinkhorn` on maps of the size a fresh model's are
    (logits normal at 0.35 around a diagonal of 2: `xing4.init`): rows and columns sum to one
    within 1e-5 after the published 20 iterations, and miss by 1e-2 and more
    after 1; the residue it hands the counter IS that distance, and the
    reference's loop over [S, n, n] gives the same map."""
    n, T = 4, 64
    logits = (0.35 * jax.random.normal(jax.random.PRNGKey(3), (n * n, T))
              + 2.0 * jnp.eye(n).reshape(-1, 1))
    hyper = llama.HyperConnections(n=n, sinkhorn_iters=iters)
    rows, residue = jax.jit(hyper.sinkhorn)(logits)
    m = np.stack([np.stack(r) for r in rows])                     # [n, n, T]
    off = max(np.abs(m.sum(axis=1) - 1).max(), np.abs(m.sum(axis=0) - 1).max())
    assert float(residue) == pytest.approx(off, abs=1e-7)
    assert (off < 1e-5) == doubly_stochastic and (off > 1e-2) == (not doubly_stochastic)
    assert (m > 0).all()
    model = {"mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "hc_eps": 1e-6,
             "hc_sinkhorn_iters": iters}
    want = reference.sinkhorn(logits.T.reshape(T, n, n), model)
    np.testing.assert_allclose(np.moveaxis(m, 2, 0), np.asarray(want), rtol=1e-5, atol=1e-7)
    # the clamp: an entry of 1e3 in the exponent would be infinite without it
    big, _ = hyper.sinkhorn(logits.at[0].set(1e3))
    assert np.isfinite(np.stack([np.stack(r) for r in big])).all()


def _one_stream_layer(cfg, x, layer, cache, positions, attention,
                      mlp=llama.dense_mlp, reduce=lambda t: t, index=None, residual=None):
    """`llama.decoder_layer` as it was before a residual strategy existed."""
    assert residual is None
    B, S, _ = x.shape
    eps = cfg.rms_eps
    with jax.named_scope("attn"):
        y = llama.rms_norm(x, layer["attn_norm"], eps)
        o, cache = attention(cfg, y, layer, cache, positions, index)
        o = reduce(o.reshape(B, S, -1) @ layer["wo"])
        if "attn_out_norm" in layer:
            o = llama.rms_norm(o, layer["attn_out_norm"], eps)
        x = x + o
    with jax.named_scope("mlp"):
        y = llama.rms_norm(x, layer["mlp_norm"], eps)
    out, stats = mlp(y, layer)
    with jax.named_scope("mlp"):
        out = reduce(out)
        if "mlp_out_norm" in layer:
            out = llama.rms_norm(out, layer["mlp_out_norm"], eps)
        return x + out, cache, stats


def _family_logits(name: str):
    """(text of the lowered forward, logits) of a family's tiny preset."""
    key, toks = jax.random.PRNGKey(5), jnp.asarray(
        np.random.default_rng(1).integers(0, 256, (2, 24)), jnp.int32)
    if name == "kimi_k2":
        cfg = kimi_k2.KimiK2Config.tiny()
        params = kimi_k2.init(cfg, key)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        fn = lambda p, t: kimi_k2.forward_paged(
            p, t, cfg, kimi_k2.init_kv_pool(cfg, 5, BS), tables, jnp.zeros(2, jnp.int32), BS)[0]
    elif name == "olmoe":
        cfg = dataclasses.replace(moe.MoEConfig.tiny(), qk_norm=True)
        params = moe.init(cfg, key)
        fn = lambda p, t: moe.forward(p, t, cfg)[0]
    else:
        cfg = {"llama": llama.LlamaConfig.tiny, "ouro": ouro.OuroConfig.tiny}[name]()
        params = model_of(cfg).init(cfg, key)
        fn = lambda p, t: llama.forward(p, t, cfg)
    lowered = jax.jit(fn).lower(params, toks)
    return lowered.as_text(), np.asarray(lowered.compile()(params, toks))


@pytest.mark.parametrize("name", ["llama", "olmoe", "ouro", "kimi_k2"])
def test_without_a_residual_strategy_the_trunk_is_the_one_stream_trunk(name, monkeypatch):
    """Every family that brings no `HyperConnections` runs the program it ran:
    its tiny preset through today's layer and through the layer as it was
    before this strategy (`_one_stream_layer`, the parent's body) lowers to the
    same text and gives the same logits, bit for bit."""
    text, logits = _family_logits(name)
    monkeypatch.setattr(llama, "decoder_layer", _one_stream_layer)
    was_text, was = _family_logits(name)
    assert text == was_text
    assert logits.tobytes() == was.tobytes() and np.isfinite(logits).all()
    assert "hc/" not in text


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's tie at this model's cut: the parts of the routed sum that
    the EIGHT shares of the experts give (through the program's `moe_mlp`,
    each share with its own experts' weights), the shared expert counted
    once, add up to what the uncut reference gives for the whole layer."""
    model, cfg, _, _ = tiny
    h, m, E, shares = 64, 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    dense = lambda k, *s: jax.random.normal(k, s, jnp.float32) / math.sqrt(s[-2])
    whole = {"router": dense(ks[0], h, E), "router_bias": 0.1 * jax.random.normal(ks[1], (E,)),
             "e_gate": dense(ks[2], E, h, m), "e_up": dense(ks[3], E, h, m),
             "e_down": dense(ks[4], E, m, h), "s_gate": dense(ks[5], h, m),
             "s_up": dense(ks[6], h, m), "s_down": dense(ks[7], m, h)}
    y = jax.random.normal(ks[8], (1, 40, h), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = kimi_k2_reference.expert_layer(y[0], whole, model, first=0)
        shared = kimi_k2_reference.expert_layer(y[0], {**whole, **{
            k: whole[k][:0] for k in ("e_gate", "e_up", "e_down")}}, model, first=0)
        parts, rows = [], 0
        for first in range(0, E, E // shares):
            share = {k: v[first:first + E // shares] if k.startswith("e_") else v
                     for k, v in whole.items() if not k.startswith("s_")}
            held = dataclasses.replace(cfg.experts, experts_held=(first, E // shares))
            out, stats = moe.moe_mlp(y, share, held, platform="cpu")
            parts.append(out[0])
            rows += int(stats["rows"])
    assert len(parts) == 8 and rows == 40 * 4          # every pair is some share's
    assert _miss(sum(parts) + shared, uncut) < TOL
    assert _miss(sum(parts[:7]) + shared, uncut) > 0.05    # seven shares are not the layer


def _wrong(cfg, params, name):
    hy = cfg.hyper
    if name == "one Sinkhorn iteration":
        return dataclasses.replace(cfg, hyper=dataclasses.replace(hy, sinkhorn_iters=1)), params
    if name == "softmax for sigmoid scoring":
        return dataclasses.replace(cfg, experts=dataclasses.replace(
            cfg.experts, score_func="softmax")), params
    if name == "the shared expert left out":
        return cfg, {**params, "layers": {k: v for k, v in params["layers"].items()
                                          if k not in ("s_gate", "s_up", "s_down")}}

    class Wrong(llama.HyperConnections):
        def read(self, x, layer, sub):
            if name == "the plain residual sum":     # every stream x + F(x)
                return x[:, :, 0], lambda out: x + out[:, :, None], jnp.zeros(())
            u, write, residue = super().read(x, layer, sub)
            # H_post without its factor 2: half of what was written on top of H_res X
            return u, lambda out: write(0.5 * out), residue

    return dataclasses.replace(cfg, hyper=Wrong(n=hy.n)), params


@pytest.mark.parametrize("name, at_least", [
    ("the plain residual sum", 0.05), ("one Sinkhorn iteration", 1e-3),
    ("H_post without its factor 2", 0.1), ("softmax for sigmoid scoring", 0.1),
    ("the shared expert left out", 0.1)])
def test_a_wrong_program_misses_the_reference(tiny, name, at_least):
    """What the chip's check is set to tell apart (the configuration file's
    `check.would_fail`), here in float32 at four layers, where the program
    itself reads 4e-7: measured 0.14, 0.0045, 0.32, 0.20 and 0.47, each over
    `at_least`. One Sinkhorn iteration moves the logits least (its map is off
    by 0.2 and still mixes the same streams): the counter `hc_residue` is what
    shows it."""
    model, cfg, params, tokens = tiny
    want = reference.logits(params, tokens, model)
    wrong_cfg, wrong_params = _wrong(cfg, params, name)
    got = jax.jit(lambda t: xing4.forward(wrong_params, t, wrong_cfg))(jnp.asarray(tokens)[None])
    assert _miss(got[0], want) > at_least


def test_the_engine_s_records_carry_both_counters(tiny):
    """Through `PagedLLMEngine` (`model_of` finds the family's `Model`
    record): two prompts decoded together give the tokens the reference's
    logits choose, and every decode record that read a step carries that
    step's `moe_rows` (an int) and `hc_residue` (a float, kept a float)."""
    from ray_tpu.util import timeline

    model, cfg, params, tokens = tiny
    timeline.clear()
    eng = PagedLLMEngine(PagedLLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=9, prefill_buckets=(16, 32)), params=params)
    prompts = [list(map(int, tokens[:21])), list(map(int, tokens[21:30]))]
    try:
        out = [f.result(120) for f in [eng.generate(p, 5) for p in prompts]]
    finally:
        eng.shutdown()
    for prompt, res in zip(prompts, out):
        want = reference.logits(params, prompt + res.token_ids[:-1], model)
        assert res.token_ids == [int(t) for t in np.argmax(want[len(prompt) - 1:], axis=-1)]
    records = [e[7] for e in timeline.local_events()
               if e[0] == "span" and e[2] == "engine" and isinstance(e[7], dict)]
    read = [r for r in records if "live" in r and "moe_rows" in r]
    assert len(read) >= 3
    for r in read + [r for r in records if "outcome" in r]:
        assert isinstance(r["moe_rows"], int) and r["moe_rows"] > 0
        assert isinstance(r["hc_residue"], float) and 0 < r["hc_residue"] < 1e-5
    assert eng.kv_memory_bytes() == cfg.cache_layers * 9 * BS * cfg.latent_row * 4
