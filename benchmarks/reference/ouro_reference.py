"""The plain reference for the Ouro-2.6B configuration.

A straightforward float32 `jax.numpy` forward pass of the looped decoder as
ISSUE 31 writes its equations down, with two Python loops (passes, layers), no
cache, no kernel, no batching trick and no bfloat16: every matrix product runs
under `default_matmul_precision("highest")`. With `x_0 = embed[tokens]`, for
pass `r = 1..total_ut_steps` and layer `l = 1..num_hidden_layers` (the SAME
layers in every pass):

    a = Attn_l(RMSNorm(x; attn_norm_l))      full causal attention, 16 heads
                                             of 128, rotate-half rotary
                                             positions on q and k, no grouping
    x = x + RMSNorm(a; attn_out_norm_l)
    m = SwiGLU_l(RMSNorm(x; mlp_norm_l))     (silu(y @ gate) * (y @ up)) @ down
    x = x + RMSNorm(m; mlp_out_norm_l)
  after the last layer of EVERY pass:        x = RMSNorm(x; final_norm)
    logits = x_after_the_last_pass @ lm_head

No cache: each pass recomputes its keys and values from its own input, which
is what "a cache of its own for every (pass, layer)" must reproduce. It
shares nothing with `ray_tpu/models/` but the NAMES of the weight tensors: it
is given the program's seeded bfloat16 weights and upcasts them one layer at a
time (a whole float32 copy would not fit beside the engine).

Departures, if the published code differs. `config.json` gives the sizes,
`total_ut_steps: 4` and `early_exit_threshold: 1`; it does NOT give, and this
file ASSUMES from the family's public description: (1) the second RMSNorm on
each sub-layer's output ("sandwich" normalisation); (2) the final norm applied
at the end of every pass, so that the normed state feeds the next pass, and
not only before the head; (3) keys and values of each pass kept apart (here:
recomputed). The exit gate (a `hidden -> 1` map on each pass's output) does
not enter the logits at `early_exit_threshold: 1` and is not computed.
Weights are random, so only logits and losses are compared, never sampled
tokens.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, D]; position s rotates pair (i, i + D/2) by s * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("n_heads", "head_dim", "theta", "eps"))
def _block(x, layer, *, n_heads, head_dim, theta, eps):
    """One decoder block on one sequence x [S, hidden], float32 throughout."""
    S = x.shape[0]
    w = {k: v.astype(F32) for k, v in layer.items()}
    y = _rms_norm(x, w["attn_norm"], eps)
    q = _rope((y @ w["wq"]).reshape(S, n_heads, head_dim), theta)
    k = _rope((y @ w["wk"]).reshape(S, n_heads, head_dim), theta)
    v = (y @ w["wv"]).reshape(S, n_heads, head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(qkv):
        qh, kh, vh = qkv  # [S, D] each
        s = (qh @ kh.T) / math.sqrt(head_dim)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))         # [H, S, D]
    a = o.transpose(1, 0, 2).reshape(S, n_heads * head_dim) @ w["wo"]
    x = x + _rms_norm(a, w["attn_out_norm"], eps)
    y = _rms_norm(x, w["mlp_norm"], eps)
    m = (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
    return x + _rms_norm(m, w["mlp_out_norm"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, *, eps):
    return _rms_norm(x, w.astype(F32), eps)


@jax.jit
def _head(x, lm_head):
    return x @ lm_head.astype(F32)


def logits(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> float32 logits [S, vocab]."""
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the Ouro reference has one KV head a query head")
    eps = model["rms_norm_eps"]
    kw = dict(n_heads=model["num_attention_heads"], head_dim=model["head_dim"],
              theta=float(model["rope_theta"]), eps=eps)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for _ in range(model["total_ut_steps"]):
            for l in range(model["num_hidden_layers"]):
                x = _block(x, {k: v[l] for k, v in params["layers"].items()}, **kw)
            x = _norm(x, params["final_norm"], eps=eps)
        head = params["embed"].T if model["tie_word_embeddings"] else params["lm_head"]
        return _head(x, head)


def loss(params: dict, tokens, targets, model: dict) -> float:
    """Mean next-token cross-entropy of ONE sequence, float32."""
    z = logits(params, tokens, model)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, jnp.asarray(targets)[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))
