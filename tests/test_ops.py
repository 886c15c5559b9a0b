"""Pallas kernel tests (interpret mode on CPU; compiled on TPU)."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.flash_attention import flash_attention


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, D = 1, 128, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    dense = llama.attention(q, k, v, causal=causal)
    flash = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash), atol=2e-5)


def test_flash_gqa_broadcast():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, D = 1, 64, 16
    q = jax.random.normal(ks[0], (B, S, 8, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))
    dense = llama.attention(q, k, v, causal=True)
    flash = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_dense(causal):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    B, S, H, D = 1, 96, 2, 16  # 96 also exercises the pad-to-block path
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    cot = jax.random.normal(ks[3], (B, S, H, D))

    def loss(fn, *args):
        return jnp.sum(fn(*args) * cot)

    dq_d, dk_d, dv_d = jax.grad(
        lambda q, k, v: loss(lambda *a: llama.attention(*a, causal=causal), q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    dq_f, dk_f, dv_f = jax.grad(
        lambda q, k, v: loss(
            lambda *a: flash_attention(*a, causal=causal, block_q=32, block_k=32),
            q, k, v),
        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq_d), np.asarray(dq_f), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk_d), np.asarray(dk_f), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv_d), np.asarray(dv_f), atol=1e-4)


def test_flash_grads_gqa():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, D = 1, 64, 16
    q = jax.random.normal(ks[0], (B, S, 8, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))

    def mk(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_d = jax.grad(mk(lambda *a: llama.attention(*a, causal=True)), argnums=(0, 1, 2))(q, k, v)
    g_f = jax.grad(mk(lambda *a: flash_attention(*a, causal=True, block_q=32, block_k=32)),
                   argnums=(0, 1, 2))(q, k, v)
    for d, f in zip(g_d, g_f):
        np.testing.assert_allclose(np.asarray(d), np.asarray(f), atol=2e-4)


def test_flash_as_llama_attn_fn():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
    ref = llama.forward(params, tokens, cfg)
    out = llama.forward(params, tokens, cfg,
                        attn_fn=lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=5e-4)


# ---- tiles from the shapes, operands in the input's dtype, K/V by group

from ray_tpu.ops import flash_attention as fa  # noqa: E402

BF16_EPS = 2.0 ** -8


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("S", [1000, 1024, 4096, 8192])
def test_choose_tiles_divide_fit_and_keep_the_layout(S, D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    S_pad = fa.padded_len(S)
    assert S_pad >= S and S_pad % 128 == 0 and S_pad - S < 128
    for kernel in ("fwd", "dq", "dkv"):
        bq, bk = fa.choose_tiles(S, D, itemsize, kernel)
        assert S_pad % bq == 0 and S_pad % bk == 0
        # a block's last two dims are (8k or 16k, 128m): tile edges are the
        # sublane dim of a [tile, D] block and the lane dim of a [1, tile] row
        assert bq % 128 == 0 and bk % 128 == 0
        assert fa.tile_vmem_bytes(kernel, bq, bk, D, itemsize) <= fa.VMEM_BUDGET
        # and they are large: 128 x 128 at S = 4096 is 528 live tiles a head
        live = len(fa._live_tiles(S_pad, bq, bk, True))
        assert live <= 3 * (S_pad // 1024) ** 2 + 1, (bq, bk, live)


def test_choose_tiles_short_sequence_is_one_tile():
    assert fa.padded_len(100) == 112 and fa.padded_len(128) == 128
    assert fa.choose_tiles(100, 64, 2, "fwd") == (112, 112)
    # a length whose padded form has no large divisor keeps a multiple of 128
    assert fa.choose_tiles(1100, 128, 2, "dkv") == (384, 384)


def _gqa_case(S, dtype=jnp.bfloat16, Hq=8, Hkv=2, D=16, seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, S, Hq, D), dtype),
            jax.random.normal(ks[1], (1, S, Hkv, D), dtype),
            jax.random.normal(ks[2], (1, S, Hkv, D), dtype),
            jax.random.normal(ks[3], (1, S, Hq, D), jnp.float32))


def _assert_close_to_float32_dense(q, k, v, w, causal, **blocks):
    """Forward and all three gradients against llama.attention taken in
    float32, at chip_smoke.py's tolerances: 4 bf16 eps of max|v| forward,
    8 bf16 eps of the largest reference entry for each gradient."""
    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, **blocks)
        return (o.astype(jnp.float32) * w).sum(), o

    def dense_loss(q, k, v):
        o = llama.attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                            causal=causal)
        return (o * w).sum(), o

    (_, o_f), g_f = jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True)(q, k, v)
    (_, o_d), g_d = jax.value_and_grad(dense_loss, (0, 1, 2), has_aux=True)(q, k, v)
    assert o_f.dtype == q.dtype and all(g.dtype == q.dtype for g in g_f)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(o_f, np.float32), np.asarray(o_d),
                               rtol=0, atol=4 * BF16_EPS * vmax)
    for name, gf, gd in zip(("dq", "dk", "dv"), g_f, g_d):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gd), rtol=0,
            atol=8 * BF16_EPS * float(jnp.max(jnp.abs(gd))), err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("S", [2048, 1100], ids=["S2048", "S1100-pads"])
def test_flash_default_tiles_bf16_gqa(S, causal):
    """DEFAULT tiles (1024 x 1024 at 2048; 384 x 384 over 1152 at 1100, whose
    last key tile is part padding), bf16 operands, g = 4."""
    _assert_close_to_float32_dense(*_gqa_case(S), causal)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("bq, bk", [(64, 32), (32, 64)], ids=["q>k", "k>q"])
def test_flash_unequal_tiles_bf16_gqa(bq, bk, causal):
    """A query tile larger than a key tile and the reverse, at a length that
    pads (160 -> 192): the live-tile tables, the diagonal-only masks and the
    padded-end mask are all crossed, with the group's dK/dV summed in-kernel."""
    _assert_close_to_float32_dense(*_gqa_case(160), causal, block_q=bq, block_k=bk)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_live_tiles_cover_exactly_the_live_entries(causal):
    bq, bk, S = 64, 32, 192
    tiles = set(fa._live_tiles(S, bq, bk, causal))
    for qi in range(S // bq):
        for ki in range(S // bk):
            has_live = not causal or ki * bk <= qi * bq + bq - 1
            assert ((qi, ki) in tiles) == has_live
    assert len(tiles) == (12 if causal else 18)


# ---- the forward at two widths (q/k of one, v and o of another), and `scale=`

def _dense_two_widths(q, k, v, scale):
    """Causal attention in float32 with q/k of one width and v of another:
    scores x `scale`, softmax, the weighted values; K/V by group."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    B, S, Hq, D = q.shape
    g = Hq // k.shape[2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, S, -1, g, D), k) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, Hq, v.shape[3])


@pytest.mark.parametrize("D, Dv, Hq, Hkv, scale, dtype", [
    (192, 128, 4, 4, 192 ** -0.5 * 1.4159 ** 2, jnp.bfloat16),   # Kimi's head, g = 1
    (192, 128, 4, 2, 0.1, jnp.float32),
    (96, 64, 4, 1, None, jnp.bfloat16),                           # grouped K/V
    (96, 64, 2, 2, 0.2, jnp.float32),
    (48, 32, 4, 4, None, jnp.float32),                            # the tiny preset's
    (64, 64, 4, 2, 0.3, jnp.float32),                             # `scale=` at one width
    (128, 128, 2, 2, 0.05, jnp.bfloat16),
    (64, 64, 4, 2, "grad", jnp.float32),                          # one width still differentiates
    (192, 128, 2, 2, "grad", jnp.float32),                        # two do not
], ids=["192/128-bf16-g1", "192/128-f32-g2", "96/64-bf16-g4", "96/64-f32-g1", "48/32-f32",
        "64/64-scale", "128/128-scale", "64/64-grad", "192/128-grad-raises"])
def test_flash_forward_takes_v_of_another_width_and_a_scale(D, Dv, Hq, Hkv, scale, dtype):
    """q and k [., S, ., D] beside v [., S, ., Dv] -> o [., S, ., Dv], against
    the dense product in float32 with the same `scale` (None: 1/sqrt(D)),
    causal, at S = 160 with 64 x 32 tiles (pads to 192: a tile edge, the
    diagonal and the padded end are all crossed). Differentiating two widths
    raises the plain error; one width with a scale gives the dense gradients."""
    ks = jax.random.split(jax.random.PRNGKey(D + Dv), 3)
    q = jax.random.normal(ks[0], (1, 160, Hq, D), dtype)
    k = jax.random.normal(ks[1], (1, 160, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (1, 160, Hkv, Dv), dtype)
    blocks = dict(block_q=64, block_k=32)
    if scale == "grad":
        loss = lambda f: lambda *a: (f(*a).astype(jnp.float32) ** 2).sum()
        flash = loss(lambda *a: flash_attention(*a, scale=0.2, **blocks))
        if D != Dv:
            with pytest.raises(NotImplementedError, match="one head width only.*192 wide, v 128"):
                jax.grad(flash)(q, k, v)
            return
        want = jax.grad(loss(lambda *a: _dense_two_widths(*a, 0.2)), (0, 1, 2))(q, k, v)
        for got, ref in zip(jax.grad(flash, (0, 1, 2))(q, k, v), want):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4, rtol=2e-4)
        return
    o = flash_attention(q, k, v, scale=scale, **blocks)
    assert o.shape == (1, 160, Hq, Dv) and o.dtype == dtype
    want = _dense_two_widths(q, k, v, D ** -0.5 if scale is None else scale)
    vmax = float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    atol = 4 * BF16_EPS * vmax if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(want), rtol=0, atol=atol)


def test_choose_tiles_counts_both_widths():
    """The forward's tiles at Kimi's 2,048 bucket, 192 / 128 in bfloat16: the
    192-wide blocks count as 256 lanes, 1024 x 1024 still fits the budget (11.5
    MiB of 12), and at one width the count is what it was."""
    assert fa.tile_vmem_bytes("fwd", 1024, 1024, 128, 2) == int(10.5 * 2 ** 20)
    assert fa.tile_vmem_bytes("fwd", 1024, 1024, 128, 2, 128) == int(10.5 * 2 ** 20)
    assert fa.tile_vmem_bytes("fwd", 1024, 1024, 192, 2, 128) == int(11.5 * 2 ** 20)
    assert fa.choose_tiles(2048, 192, 2, "fwd", 128) == (1024, 1024)
    for kernel in ("fwd", "dq", "dkv"):
        assert fa.choose_tiles(4096, 128, 2, kernel, 128) == fa.choose_tiles(4096, 128, 2, kernel)


# sha256 (first 16 hex digits) of what `flash_attention` at ONE width and
# `scale=None` traced and lowered to at the PARENT of PR 41 (commit 6699c67,
# before the forward took a second width and a scale), made by this file's
# `_one_width_programs` from that tree: `call`, the traced program with the
# kernel NOT interpreted (the `pallas_call`'s name, grid, block specs, scratch
# and body, as the chip's compiler gets them); `fwd` and `grad`, the StableHLO
# text of the forward and of all three gradients, the kernels interpreted;
# `train`, the gradient of a family's loss through it. A change that means to
# alter one of them replaces its line here, and says why.
PARENT_FLASH = {
    "flash.call.128": "11a936a4951d4cb7",
    "flash.fwd.128": "b2f709854d5fb2ab",
    "flash.grad.128": "c51b08585a2ac0fc",
    "flash.call.64": "a6de75c2e5998dfc",
    "flash.fwd.64": "e31d806c353c065c",
    "flash.grad.64": "52aef46eca1420a9",
    "llama.train": "4c7b91d145a1abdc",
    "olmoe.train": "6654e7abe641e3dd",
}


def _one_width_programs() -> dict:
    from ray_tpu.models import model_of, moe

    sds, out = jax.ShapeDtypeStruct, {}
    for D in (128, 64):
        q, kv = sds((1, 256, 4, D), jnp.bfloat16), sds((1, 256, 2, D), jnp.bfloat16)
        fwd = functools.partial(flash_attention, causal=True, interpret=True)
        out[f"flash.fwd.{D}"] = jax.jit(fwd).lower(q, kv, kv).as_text()
        out[f"flash.grad.{D}"] = jax.jit(jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), (0, 1, 2))).lower(q, kv, kv).as_text()
        out[f"flash.call.{D}"] = str(jax.make_jaxpr(functools.partial(
            flash_attention, causal=True, interpret=False))(q, kv, kv))
    attn = functools.partial(flash_attention, interpret=True)
    for name, cfg in (("llama", llama.LlamaConfig.tiny()),
                      ("olmoe", dataclasses.replace(moe.MoEConfig.tiny(), qk_norm=True))):
        model = model_of(cfg)
        params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
        loss = lambda p, t, y: model.loss(p, t, y, cfg, attn)[0]
        tok = sds((2, 32), jnp.int32)
        out[f"{name}.train"] = jax.jit(jax.grad(loss)).lower(params, tok, tok).as_text()
    return out


@pytest.fixture(scope="module")
def one_width_programs():
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16]
            for k, v in _one_width_programs().items()}


@pytest.mark.parametrize("name", sorted(PARENT_FLASH))
def test_at_one_width_the_flash_kernels_are_the_parent_s_text(one_width_programs, name):
    """ADAPT, by what the code observes (v's width): with v as wide as q and k
    and no `scale`, the function traces the `pallas_call` it traced and lowers
    to the text it lowered to before it took either, at 128- and 64-wide
    heads, forward and backward, and so do the train steps' gradients of
    Llama and OLMoE through it."""
    assert one_width_programs[name] == PARENT_FLASH[name]


# ---- the banded forward: `flash_attention(window=)` (models/laguna.py's window layers)

def _dense_window(q, k, v, window):
    """Causal attention in float32 where query i sees key j iff i - j < window."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    B, S, Hq, D = q.shape
    g = Hq // k.shape[2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, S, -1, g, D), k) / np.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -1e30)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, Hq, D)


@pytest.mark.parametrize("S, window, bq, bk, g", [
    (256, 64, 64, 64, 9),        # a window of whole tiles, Laguna's window group
    (256, 64, 32, 64, 6),        # the key tile larger, its full group
    (300, 100, 128, 128, 9),     # neither the length nor the window is whole tiles
    (384, 50, 128, 64, 6),       # a window under a tile: every band tile is masked
    (256, 1, 64, 64, 1),         # a query sees its own key alone
    (192, 1000, 64, 64, 3),      # a window longer than the sequence: the triangle
    (1100, 512, None, None, 9),  # the cell's window under DEFAULT tiles, a padded end
], ids=["whole-tiles", "k>q", "ragged", "under-a-tile", "window-1", "longer-than-S", "default"])
def test_flash_window_is_the_dense_band(S, window, bq, bk, g):
    """The banded forward, interpreted, against a dense masked softmax in
    float32: tiles wholly behind the band are not visited, the tiles its far
    edge crosses are masked, and a row whose first visited tile holds none of
    its keys is put right by the tiles that follow."""
    ks = jax.random.split(jax.random.PRNGKey(S + window), 3)
    D = 128 if bq is None else 32
    q = jax.random.normal(ks[0], (1 if bq is None else 2, S, 2 * g, D))
    k, v = (jax.random.normal(key, (q.shape[0], S, 2, D)) for key in ks[1:])
    got = fa.flash_attention(q, k, v, window=window, block_q=bq, block_k=bk, interpret=True)
    want = _dense_window(q, k, v, window)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(llama.attention(q, k, v, window=window) - want).max()) < 1e-5


def test_flash_window_live_tiles_are_the_band_and_its_tiles_keep_to_the_window():
    bq, bk, S, W = 64, 32, 256, 80
    tiles = set(fa._live_tiles(S, bq, bk, True, W))
    for qi in range(S // bq):
        for ki in range(S // bk):
            live = any(j <= i and i - j < W for i in range(qi * bq, qi * bq + bq)
                       for j in range(ki * bk, ki * bk + bk))
            assert ((qi, ki) in tiles) == live, (qi, ki)
    assert tiles < set(fa._live_tiles(S, bq, bk, True))
    # at the cell's shapes: 512 x 512 under a window of 512, 31 tiles a head at
    # 8,192 where the triangle under 1,024 x 1,024 has 36 of four times the size
    assert fa.window_tiles(8192, 512, 128, 2) == fa.window_tiles(2048, 512, 128, 2) == (512, 512)
    assert len(fa._live_tiles(8192, 512, 512, True, 512)) == 31
    assert len(fa._live_tiles(8192, 1024, 1024, True)) == 36
    assert fa.window_tiles(1100, 512, 128, 2) == (384, 384) and fa.window_tiles(64, 512, 32, 4) == (64, 64)


def test_flash_window_has_no_backward_and_no_meaning_without_causal():
    q = k = v = jnp.ones((1, 64, 2, 32))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: fa.flash_attention(q, k, v, window=16, interpret=True).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, causal=False, window=16, interpret=True)
