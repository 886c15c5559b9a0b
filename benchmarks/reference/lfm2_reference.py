"""The plain reference for the LFM2-8B-A1B configuration (`model_type`
`lfm2_moe`), as ISSUE 40 writes its equations down.

A straightforward float32 `jax.numpy` forward pass of ONE sequence, layer by
layer in a Python loop, the whole sequence at once, with no cache, no state,
no kernel, no sort and no bfloat16: every matrix product runs under
`default_matmul_precision("highest")`. With `h = embed[tokens]` (H wide),
`eps = norm_eps`, every matrix without bias, in layer l of `layer_types`:

    h <- h + Mixer_l(RMSNorm(h; operator_norm))
    h <- h + FFN_l(RMSNorm(h; ffn_norm))

  a `conv` layer's mixer, on y [S, H]:
    [B | C | x] = y W_in                     W_in [H, 3 H], thirds in that order
    u = B * x                                elementwise
    c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t    `conv_L_cache` = 3 taps, depthwise,
                                             causal, u zero before position 0:
                                             written as the sum of three shifted
                                             copies of u
    out = (C * c) W_out
  a `full_attention` layer's mixer: heads of `hidden_size / num_attention_heads`
    lanes, `num_key_value_heads` of them keys and values;
    q, k = each head RMS-normalised over its own lanes with ONE learned weight
      of that width (q_layernorm, k_layernorm), BEFORE rope
    rope at `rope_theta` on all lanes; causal softmax at 1 / sqrt(lanes); W_o
  the first `num_dense_layers` layers' FFN: SwiGLU at `intermediate_size`
  every later layer's FFN:
    s = sigmoid(y W_r)                       all router outputs, float32
    chosen = the `num_experts_per_tok` largest of s + b   (b: expert_bias,
                                             chooses, never weighs)
    w = s[chosen] / (sum(s[chosen]) + 1e-6) x routed_scaling_factor
    out = sum over chosen e HELD HERE of w_e SwiGLU_e(y)   no shared expert

and `logits = RMSNorm(h; embedding_norm) @ embed^T` (tied).

The SHARE. The reference is given what the chip holds: the experts [first,
first + count) of each layer (`num_experts` of the file is the count,
`share.rank` says which). It routes over ALL router outputs and leaves out
what the absent experts would have added, as the deployment's chip does
before its exchange (model-configs guide, section 4). Every held expert runs
densely on every token, one after another, and is weighted by a [tokens,
experts] matrix that is zero where the expert was not chosen.

It shares nothing with `ray_tpu/models/` but the NAMES and layouts of the
weight tensors: one stacked tree a kind of layer (`conv_dense`, `conv_moe`,
`attn_moe`: the mixer and the FFN a layer has), a layer's place in its tree
the count of its kind before it; `w_in` [H, 3 H], `conv_w` [3, H] (tap j
multiplies u_{t - 2 + j}), `wo`, `wq`/`wk`/`wv`, `q_norm`/`k_norm` [lanes],
the experts' leaves [count, ...]. It is given the program's seeded bfloat16
weights and upcasts them piece by piece inside each layer's compiled block.

Departures from the published code: (1) rotary lanes are half-split (pair
(i, i + D/2)), not interleaved: a fixed permutation of W_q's and W_k's
columns within a head, immaterial with seeded weights; (2) the order of
W_in's thirds is the published [B | C | x].
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACK = {"conv": "conv", "full_attention": "attn"}
WIDE = ("e_gate", "e_up", "e_down", "w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, D]; position s rotates pair (i, i + D/2) by s theta^(-2i/D)."""
    S, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (jnp.arange(S, dtype=F32)[:, None] * inv[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(y, w, taps=(0, 1, 2), gated: bool = True):
    """The gated short convolution on normalised y [S, H]. `taps` says which
    weight multiplies u two back, one back and at the position (the tests
    tell it apart from its reversal and from a dropped tap: None), `gated`
    whether C gates the output."""
    gate_in, gate_out, x = jnp.split(y @ w["w_in"], 3, axis=-1)
    u = gate_in * x
    S = u.shape[0]
    padded = jnp.concatenate([jnp.zeros((2, u.shape[1]), u.dtype), u])
    c = sum(w["conv_w"][tap] * padded[back:back + S]
            for back, tap in enumerate(taps) if tap is not None)
    return ((gate_out * c) if gated else c) @ w["wo"]


def attention(y, w, model: dict, norm: str = "per_head"):
    """Grouped-query attention on normalised y [S, H], dense and causal, a
    query head at a time. `norm` "per_head" is the model's; the others are
    what the tests tell it apart from."""
    S = y.shape[0]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps, theta = model["hidden_size"] // nh, model["norm_eps"], float(model["rope_theta"])
    q, k = y @ w["wq"], y @ w["wk"]
    v = (y @ w["wv"]).reshape(S, nkv, d)
    if norm == "whole_vector":     # one norm over all heads' lanes, the weight tiled
        q = _rms_norm(q, jnp.tile(w["q_norm"], nh), eps)
        k = _rms_norm(k, jnp.tile(w["k_norm"], nkv), eps)
    q, k = q.reshape(S, nh, d), k.reshape(S, nkv, d)
    if norm == "per_head":
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    if norm == "after_rope":
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = nh // nkv

    def one_head(args):
        qh, head = args                       # [S, d], the query head's number
        kh = jax.lax.dynamic_index_in_dim(k, head // group, axis=1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, head // group, axis=1, keepdims=False)
        s = (qh @ kh.T) / jnp.sqrt(F32(d))
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ vh

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), jnp.arange(nh)))    # [nh, S, d]
    return o.transpose(1, 0, 2).reshape(S, nh * d) @ w["wo"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def route(y, w, model: dict, score: str = "sigmoid", bias: str = "selects"):
    """y [S, H] -> the [S, router outputs] matrix of the weights a token gives
    each expert: zero but at its chosen ones."""
    logits = y @ w["router"]
    k = model["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    b = w["router_bias"] if model.get("use_expert_bias", True) else 0.0
    chosen = jax.lax.top_k(s + b, k)[1]
    picked = jnp.take_along_axis(s + b if bias == "weighs" else s, chosen, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-6)
    picked = picked * model["routed_scaling_factor"]
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def expert_layer(y, w, model: dict, first: int, **routing):
    """The expert layer on normalised y [S, H] for the experts [first, first
    + count) that `w` holds (count = `w["e_gate"].shape[0]`): those experts'
    part of the routed sum, one expert after another (a loop over experts
    that adds each one's weighted output), so that one expert's float32 copy
    is all that lives at once."""
    count = w["e_gate"].shape[0]
    weights = route(y, w, model, **routing)[:, first:first + count]      # [S, count]

    def add_expert(out, e):   # out + this expert's output, weighted a token
        weight, *matrices = e
        return out + weight[:, None] * _swiglu(y, *(t.astype(F32) for t in matrices)), None

    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (weights.T, w["e_gate"], w["e_up"], w["e_down"]))[0]


def first_expert(model: dict) -> int:
    """The first expert of this chip's share: `share.rank` x the count held."""
    return model.get("share", {}).get("rank", 0) * model["num_experts"]


def layer_places(model: dict) -> list:
    """(the stacked tree a layer's weights are in, its place there, its mixer,
    whether its FFN is dense) of every layer, in order."""
    seen, out = {}, []
    for i, kind in enumerate(model["layer_types"]):
        dense = i < model["num_dense_layers"]
        stack = f"{STACK[kind]}_{'dense' if dense else 'moe'}"
        out.append((stack, seen.get(stack, 0), kind, dense))
        seen[stack] = seen.get(stack, 0) + 1
    return out


@partial(jax.jit, static_argnames=("model_json", "first", "kind", "dense"))
def _block(x, stack, l, *, model_json, first, kind, dense):
    """Layer `l` of a stacked tree on one sequence x [S, H], float32
    throughout. The layer is taken out of the stacked bfloat16 weights INSIDE
    the compiled block and the wide matrices are upcast one at a time."""
    model = json.loads(model_json)   # a static argument has to hash
    layer = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in stack.items()}
    w = {k: v if k in WIDE else v.astype(F32) for k, v in layer.items()}
    eps = model["norm_eps"]
    y = _rms_norm(x, w["attn_norm"], eps)
    x = x + (short_conv(y, w) if kind == "conv" else attention(y, w, model))
    y = _rms_norm(x, w["mlp_norm"], eps)
    if dense:
        return x + _swiglu(y, *(w[k].astype(F32) for k in ("w_gate", "w_up", "w_down")))
    return x + expert_layer(y, w, model, first)


@partial(jax.jit, static_argnames=("blocks",))
def head(x, final_norm, embed, eps, blocks: int = 8):
    """x [S, H] -> float32 logits [S, V] through the final norm and the TIED
    head, `blocks` slices of the vocabulary at a time (the float32 copy of the
    whole embedding would be half a gigabyte beside the engine's weights)."""
    y = _rms_norm(x, final_norm.astype(F32), eps)
    V = embed.shape[0]
    blocks = blocks if V % blocks == 0 else 1
    cut = embed.reshape(blocks, V // blocks, -1)
    out = jax.lax.map(lambda e: y @ e.astype(F32).T, cut)                 # [blocks, S, V / blocks]
    return out.transpose(1, 0, 2).reshape(y.shape[0], V)


def hidden(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> the residual after the last layer [S, H]."""
    model_json, first = json.dumps(model, sort_keys=True), first_expert(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for stack, place, kind, dense in layer_places(model):
            x = _block(x, params[stack], jnp.int32(place), model_json=model_json,
                       first=first, kind=kind, dense=dense)
        return x


def logits(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> float32 logits [S, V]."""
    if not model.get("tie_word_embeddings", True):
        raise ValueError("the LFM2 reference's head is the embedding, tied")
    with jax.default_matmul_precision("highest"):
        return head(hidden(params, tokens, model), params["final_norm"], params["embed"],
                    model["norm_eps"])
