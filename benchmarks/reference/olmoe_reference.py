"""The plain reference for the OLMoE-1B-7B configurations.

A straightforward float32 `jax.numpy` pass of the architecture as published
(`OlmoeForCausalLM`, allenai/OLMoE-1B-7B-0125-Instruct): pre-norm decoder
blocks of multi-head attention whose projected query and key are RMS-normed
over their WHOLE width (2,048, a learned weight of that width) before the
split into heads and before rotary position embeddings (rotate-half), and a
sparse MLP: a router over 64 SwiGLU experts of width 1,024, softmax in
float32 over all 64, the 8 largest probabilities taken as they are
(`norm_topk_prob` false: not renormalised), no shared expert, no capacity;
RMSNorm, untied output head. One sequence, no kernel, no cache, no sort and
no bfloat16: every expert is applied to every token and a mask keeps the
tokens that chose it, and every matrix product runs under
`default_matmul_precision("highest")`, which on a TPU is what makes a float32
product a float32 product. It shares nothing with `ray_tpu/models/moe.py` but
the NAMES of the weight tensors, because it is given the program's own seeded
bfloat16 weights; it upcasts them one layer and one expert at a time (a whole
float32 copy of 1.46 B parameters would not fit beside the train state).

The objective is what the train step reports as `loss`: the mean next-token
cross-entropy plus `router_aux_loss_coef` times the load-balancing loss
`sum over layers of E * sum_e f_e * P_e` (`f_e` the share of the `S * 8`
choices that went to expert `e`, `P_e` the mean over tokens of `p[:, e]`: the
OLMoE paper's L_LB, per layer). No z-loss.

Departures from the published description: none in the mathematics. (The
`transformers` implementation computes the same L_LB over the concatenated
layers and scales it by `top_k`; the paper's per-layer form is used, as
ISSUE 27 fixes it, and the coefficient is listed under `assumed`.) Weights
are random, so logits, losses and chosen experts are compared, never tokens.
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
def _attention(x, layers, l, *, n_heads, head_dim, theta, eps):
    """x [S, hidden] plus the attention of layer `l` (all heads have their
    own keys and values: num_key_value_heads = num_attention_heads)."""
    S = x.shape[0]
    w = {k: layers[k][l].astype(F32) for k in
         ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
    y = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm(y @ w["wq"], w["q_norm"], eps).reshape(S, n_heads, head_dim)
    k = _rms_norm(y @ w["wk"], w["k_norm"], eps).reshape(S, n_heads, head_dim)
    v = (y @ w["wv"]).reshape(S, n_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(qkv):
        qh, kh, vh = qkv                                       # [S, D] each
        s = (qh @ kh.T) / math.sqrt(head_dim)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    o = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return x + o.transpose(1, 0, 2).reshape(S, n_heads * head_dim) @ w["wo"]


@partial(jax.jit, static_argnames=("top_k", "renormalise", "eps"))
def _experts(x, layers, l, *, top_k, renormalise, eps):
    """x [S, hidden] plus the expert layer `l`; also the layer's
    load-balancing term and the chosen experts [S, top_k]."""
    S = x.shape[0]
    E = layers["router"].shape[-1]
    y = _rms_norm(x, layers["mlp_norm"][l].astype(F32), eps)
    p = jax.nn.softmax(y @ layers["router"][l].astype(F32), axis=-1)     # [S, E]
    top_p, top_e = jax.lax.top_k(p, top_k)
    if renormalise:
        top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    chosen = top_e[:, :, None] == jnp.arange(E)[None, None, :]           # [S, k, E]
    weight = (top_p[:, :, None] * chosen).sum(axis=1)                    # [S, E]

    def add_expert(e, out):
        gate, up, down = (layers[k][l, e].astype(F32) for k in ("e_gate", "e_up", "e_down"))
        return out + weight[:, e][:, None] * ((jax.nn.silu(y @ gate) * (y @ up)) @ down)

    out = jax.lax.fori_loop(0, E, add_expert, jnp.zeros_like(x))
    share = chosen.sum(axis=(0, 1)).astype(F32) / (S * top_k)            # f_e
    return x + out, E * (share * p.mean(axis=0)).sum(), top_e


def objective(params: dict, tokens, targets, model: dict):
    """The objective of ONE sequence (tokens, targets [S]) and its parts:
    (loss, {"nll", "aux", "experts" [L, S, k], "logits" [S, vocab]}).
    Traceable: `jax.grad` of its first output gives the reference gradients."""
    eps = model["rms_norm_eps"]
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        aux, experts = 0.0, []
        for l in range(model["num_hidden_layers"]):
            x = _attention(x, layers, l, n_heads=model["num_attention_heads"],
                           head_dim=model["head_dim"],
                           theta=float(model["rope_theta"]), eps=eps)
            x, aux_l, chosen = _experts(
                x, layers, l, top_k=model["num_experts_per_tok"],
                renormalise=model["norm_topk_prob"], eps=eps)
            aux = aux + aux_l
            experts.append(chosen)
        head = params["embed"].T if model["tie_word_embeddings"] else params["lm_head"]
        z = _rms_norm(x, params["final_norm"].astype(F32), eps) @ head.astype(F32)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, jnp.asarray(targets)[:, None], axis=-1)[:, 0]
    nll = jnp.mean(logz - gold)
    return nll + model["router_aux_loss_coef"] * aux, {
        "nll": nll, "aux": aux, "experts": jnp.stack(experts), "logits": z}


def loss(params: dict, tokens, targets, model: dict) -> float:
    """What the train step reports as `loss` for ONE sequence, float32."""
    return float(objective(params, tokens, targets, model)[0])
