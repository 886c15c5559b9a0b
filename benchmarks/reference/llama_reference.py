"""The plain reference for the Mistral-7B-v0.3 configurations.

A straightforward float32 `jax.numpy` forward pass of the architecture as
published (`MistralForCausalLM`: pre-norm decoder blocks of grouped-query
attention with rotary position embeddings in the rotate-half convention,
RMSNorm, a SwiGLU MLP, untied output head, no sliding window in v0.3), with
no kernel, no cache, no batching trick and no bfloat16: every matrix product
runs under `default_matmul_precision("highest")`, which on a TPU is what
makes a float32 product a float32 product. It shares nothing with
`ray_tpu/models/llama.py` but the NAMES of the weight tensors, because it is
given the program's own seeded bfloat16 weights and upcasts them one layer
at a time (a whole float32 copy would not fit beside the engine).

Departures from the published description: none in the mathematics. Weights
are random, so only logits and losses are compared, never sampled tokens.
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


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "theta", "eps"))
def _block(x, layer, *, n_heads, n_kv, head_dim, theta, eps):
    """One decoder block on one sequence x [S, hidden], float32 throughout."""
    S = x.shape[0]
    w = {k: v.astype(F32) for k, v in layer.items()}
    y = _rms_norm(x, w["attn_norm"], eps)
    q = _rope((y @ w["wq"]).reshape(S, n_heads, head_dim), theta)
    k = _rope((y @ w["wk"]).reshape(S, n_kv, head_dim), theta)
    v = (y @ w["wv"]).reshape(S, n_kv, head_dim)
    group = n_heads // n_kv
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_kv_head(qkv):  # the query heads that share one KV head
        qg, kh, vh = qkv  # [S, group, D], [S, D], [S, D]
        s = jnp.einsum("qgd,kd->gqk", qg, kh) / math.sqrt(head_dim)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vh)

    o = jax.lax.map(one_kv_head, (
        q.reshape(S, n_kv, group, head_dim).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))        # [n_kv, S, group, D]
    o = o.transpose(1, 0, 2, 3).reshape(S, n_heads * head_dim)
    x = x + o @ w["wo"]
    y = _rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, lm_head, *, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def logits(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> float32 logits [S, vocab]."""
    kw = dict(n_heads=model["num_attention_heads"],
              n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
              theta=float(model["rope_theta"]), eps=model["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for l in range(model["num_hidden_layers"]):
            layer = {k: v[l] for k, v in params["layers"].items()}
            x = _block(x, layer, **kw)
        head = params["embed"].T if model["tie_word_embeddings"] else params["lm_head"]
        return _head(x, params["final_norm"], head, eps=model["rms_norm_eps"])


def loss(params: dict, tokens, targets, model: dict) -> float:
    """Mean next-token cross-entropy of ONE sequence, float32."""
    z = logits(params, tokens, model)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, jnp.asarray(targets)[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - gold))
