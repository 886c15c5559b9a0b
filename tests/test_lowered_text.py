"""The guard that a PR changed no device program it did not mean to: every
family of one kind of layer, and the serving engine's own steps, lower to the
StableHLO text they lowered to at the commit their line was recorded on,
letter for letter, compared by hash.

How a line is regenerated. A change that MEANS to alter a program replaces
that program's lines and no other, and says why in the comment above the
dictionary (which PR, what the program does now, the hash it had):

    JAX_PLATFORMS=cpu python - <<'EOF'
    from tests.test_lowered_text import hashes, _one_kind_programs, _engine_programs
    for k, v in sorted(hashes({**_one_kind_programs(), **_engine_programs()}).items()):
        print(f'    "{k}": "{v}",')
    EOF

A change that means to alter NONE (a refactor, a `simplicity` PR) runs the same
lines on its parent's tree (`PYTHONPATH=<a clone of the parent>`, this file
copied in) and finds the dictionaries as they stand. The text depends on the
jax version (0.9.0 here) and on nothing of the machine."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import kimi_k2, llama, model_of, moe, ouro

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16

# sha256 (first 16 hex digits) of the StableHLO text that each program lowered
# to at the PARENT of PR 40 (commit 3eb9ea4, `decoder_trunk` one scan over one
# stack, `lead_layers` a special case), made by this file's `_one_kind_programs`
# from that tree. A change that means to alter a family's program replaces
# its lines here, and says why. PR 41 replaced two, `kimi_k2.paged.fresh` and
# `xing4.paged.fresh`: until then the latent family's forward did nothing with
# `fresh` (the lines were its table program's, fb85295f16b0a12c and
# 94ca6dd2f464ef8e); now a fresh prefill attends over the rows in hand under
# `attn/prompt_attend`. The table program, the decode step and `last` are the
# parent's still. PR 43 replaced all six `*.paged.fresh` lines and no other: a
# fresh prefill whose 32 tokens fill two blocks of 16 scatters two whole pages
# a leaf and layer where it scattered 32 rows (`llama.write_pages`; until then
# 9faf4950b73482ea, b9032e5101a13d5c, f354ba46d4742441, 1e13fa5a3563dfaa,
# 10adbe590e3aa7a9, cbaf223401156ccb in the dictionary's order); the decode
# step, the table prefill, `last` (not told `fresh`), losses and gradients are
# the parent's text. PR 44 moved the dictionary here from tests/test_lfm2.py
# (ROADMAP D18) and changed no line of it. PR 46 replaced the six
# `*.paged.decode` lines and no other: at S == 1 the projections that are
# split into heads (`wq`, `wk`, `wv`; the latent family's `w_uq`) hold their
# product's result behind an `optimization_barrier` before the split
# (`llama.project_heads`), so that XLA:TPU gives the heads-first layout to the
# result and reads the weight in place (until then 4d1666b4e529e110,
# 906cc7b9fe5efe59, fd15da110c2c1b9b, e5fc4c4767ca19dc, 7608cd7e4039bd6d,
# 805d238630f2ee06 in the dictionary's order); every program at S > 1 (the
# table prefill, the fresh one, `last`) and every loss and gradient is the
# parent's text.
PARENT_TEXT = {
    "llama.paged.decode": "1fe969061e80fc29",
    "llama.paged.prefill": "fd8459cf291092a0",
    "llama.paged.fresh": "e4819840a997529d",
    "llama.paged.last": "af6f1abee32d97ef",
    "llama.loss": "0fb9d6ad11474b55",
    "llama.grad": "1351d9f4c3001991",
    "moe.paged.decode": "b4cbd8e0616a0260",
    "moe.paged.prefill": "a695e7509cb75b45",
    "moe.paged.fresh": "b179923a4d114772",
    "moe.paged.last": "0492ef996a21c340",
    "moe.loss": "03710b5fb6249ca2",
    "moe.grad": "66721486a6cbfac2",
    "olmoe.paged.decode": "202959ac7e3159f5",
    "olmoe.paged.prefill": "db9bf0e36bc145f6",
    "olmoe.paged.fresh": "82027e3150d180ae",
    "olmoe.paged.last": "656dd949f7c87504",
    "olmoe.loss": "3d7e9ee7a5ae9813",
    "olmoe.grad": "a450411da25cf0a8",
    "ouro.paged.decode": "e076f47bbb470aa3",
    "ouro.paged.prefill": "5fe0d29dd78c063f",
    "ouro.paged.fresh": "6932f8c8b2bf340b",
    "ouro.paged.last": "f8df10e5b4953e6f",
    "kimi_k2.paged.decode": "d2362407b299555d",
    "kimi_k2.paged.prefill": "fb85295f16b0a12c",
    "kimi_k2.paged.fresh": "1da06d0983b09b0f",
    "kimi_k2.paged.last": "46bab8d6b05745d6",
    "xing4.paged.decode": "9dc47410ce02f9d6",
    "xing4.paged.prefill": "94ca6dd2f464ef8e",
    "xing4.paged.fresh": "a83c509a1b1686ef",
    "xing4.paged.last": "28834a2fee161468",
}

# The serving engine's own jitted steps (`serve/llm_paged.py`), as
# `PagedLLMEngine._init_backend` builds them for the tiny Llama: recorded at
# the PARENT of PR 44 (commit 0cac273, `PagedLLMEngine` a subclass of the slot
# engine) by `_engine_programs` run on that tree. PR 44 folded the two engine
# classes into one and moved the steps' callers; these say it changed no step.
# PR 46 replaced `engine.decode` and no other (9a54b835ebc1bab2 until then):
# the decode step's three projections hold their results, as above; both
# prefill programs, `pick` and `carry` are the text they were.
ENGINE_TEXT = {
    "engine.decode": "6d694d3df493ef66",
    "engine.prefill.own_rows": "d5b6793788239c97",
    "engine.prefill.table": "c0f9ac373d237b85",
    "engine.pick": "44ef53bffaab689a",
    "engine.carry": "db07961115f2a985",
}


def _one_kind_programs() -> dict:
    """{name: lowered StableHLO text}: every family of ONE kind of layer at
    its tiny preset, its paged forward four ways (a decode step, a prefill
    through the table, one over its own rows, one with `head_rows`) and, where
    it trains, its loss and gradient."""
    from benchmarks.harness.families import xing4 as xing4_family

    with open(os.path.join(ROOT, "benchmarks", "tests", "fixtures", "tiny",
                           "xing4-serve.json")) as f:
        file = json.load(f)
    cfgs = {"llama": llama.LlamaConfig.tiny(), "moe": moe.MoEConfig.tiny(),
            "olmoe": dataclasses.replace(moe.MoEConfig.tiny(), qk_norm=True),
            "ouro": ouro.OuroConfig.tiny(), "kimi_k2": kimi_k2.KimiK2Config.tiny(),
            "xing4": xing4_family.model_config(
                {k: file[k] for k in xing4_family.MODEL_KEYS}, remat=False)}
    sds, i32, out = jax.ShapeDtypeStruct, jnp.int32, {}
    for name, cfg in cfgs.items():
        model = model_of(cfg)
        params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
        pool = jax.eval_shape(lambda: model.init_kv_pool(cfg, 9, BS))
        for tag, B, S, kw in (("decode", 3, 1, {}), ("prefill", 1, 32, {}),
                              ("fresh", 1, 32, {"fresh": True}), ("last", 1, 32, {"last": True})):
            def step(params, pool, tokens, tables, lengths, rows, kw=kw):
                kw = {"head_rows": rows} if kw.get("last") else kw
                return model.forward_paged(params, tokens, cfg, pool, tables, lengths, BS,
                                           platform="cpu", **kw)
            out[f"{name}.paged.{tag}"] = jax.jit(step).lower(
                params, pool, sds((B, S), i32), sds((B, 4), i32), sds((B,), i32),
                sds((B,), i32)).as_text()
        if model.loss is not None and name != "ouro":
            loss = lambda p, t, y: model.loss(p, t, y, cfg, None)[0]
            args = (params, sds((2, 16), i32), sds((2, 16), i32))
            out[f"{name}.loss"] = jax.jit(loss).lower(*args).as_text()
            out[f"{name}.grad"] = jax.jit(jax.grad(loss)).lower(*args).as_text()
    return out


def _engine_programs() -> dict:
    """{name: lowered StableHLO text} of the steps an engine of the tiny Llama
    runs (B = 3, a 32-token prefill bucket, 4 blocks of 16 a sequence, greedy),
    built by the functions `PagedLLMEngine._init_backend` builds them with."""
    from ray_tpu.serve.llm_paged import carry_step, paged_step, pick_step

    cfg = llama.LlamaConfig.tiny()
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: llama.init_kv_pool(cfg, 9, BS))
    prefill = lambda **kw: paged_step(
        "prefill", cfg, BS, "cpu", "last", table_first=True, **kw).lower(
        params, pool, sds((1, 32), i32), sds((1, 4), i32), sds((2,), i32)).as_text()
    return {
        "engine.decode": paged_step("decode", cfg, BS, "cpu", 0).lower(
            params, pool, sds((3, 1), i32), sds((3,), i32), sds((3, 4), i32)).as_text(),
        "engine.prefill.own_rows": prefill(fresh=True),
        "engine.prefill.table": prefill(),
        "engine.pick": pick_step(0.0).lower(
            sds((3, cfg.vocab_size), jnp.float32), {},
            jax.eval_shape(lambda: jax.random.PRNGKey(0))).as_text(),
        "engine.carry": carry_step().lower(sds((3, 1), i32), sds((3, 1), i32)).as_text(),
    }


def hashes(programs: dict) -> dict:
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in programs.items()}


@pytest.fixture(scope="module")
def lowered():
    return hashes(_one_kind_programs())


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_with_one_kind_of_layer_the_trunk_lowers_to_the_parent_s_text(lowered, name):
    """`decoder_trunk(runs=)`, `gqa_attention`'s per-head norm, `paged_attend`
    and the mixer's scope changed no program that was there: Mistral's,
    OLMoE's, Ouro's, Kimi's and Xing's tiny presets lower to the StableHLO
    text they lowered to on the parent, letter for letter (`lead_layers` is
    now two runs and still the same text)."""
    assert lowered[name] == PARENT_TEXT[name]


@pytest.fixture(scope="module")
def lowered_engine():
    return hashes(_engine_programs())


@pytest.mark.parametrize("name", sorted(ENGINE_TEXT))
def test_the_engine_s_steps_lower_to_the_parent_s_text(lowered_engine, name):
    """The decode step, a prefill's two programs, `pick` and `carry` are the
    text they were when `PagedLLMEngine` still subclassed the slot engine."""
    assert lowered_engine[name] == ENGINE_TEXT[name]
