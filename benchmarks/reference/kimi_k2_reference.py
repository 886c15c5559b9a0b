"""The plain reference for the Kimi-K2.7-Code configuration (`model_type`
`kimi_k2`, the DeepSeek-V3 block), as ISSUE 33 writes its equations down.

A straightforward float32 `jax.numpy` forward pass of ONE sequence, layer by
layer in a Python loop, with no cache, no kernel, no absorbed projection, no
sort and no bfloat16: every matrix product runs under
`default_matmul_precision("highest")`. With `x = embed[tokens]`, in every
layer (`rms_norm_eps` 1e-5, pre-norm, residual adds):

  latent attention, H heads:
    c_q = RMSNorm(y W_dq)                          [q_lora_rank]
    [q_nope | q_rope]_h = c_q W_uq                 [nope | rope] a head
    [c_kv | k_r] = y W_dkv ; c_kv = RMSNorm(c_kv)  [kv_lora_rank | rope]
    k_nope_h = c_kv W_uk[h]^T ; v_h = c_kv W_uv[h]
    rope on q_rope_h and on k_r (ONE k_r a token, every head's), YaRN's
      blended inverse frequencies; cos and sin scaled by
      m(mscale) / m(mscale_all_dim) (1 at the published values)
    p_h = softmax(scale (q_nope_h k_nope_h^T + q_rope_h k_r^T), causal),
      scale = (nope + rope)^-0.5 x m(mscale_all_dim)^2, m(a) = 0.1 a ln(factor) + 1
    a = concat_h(p_h v_h) W_o
  the first `first_k_dense_replace` layers: SwiGLU of `intermediate_size`
  every later layer, the expert layer:
    s = sigmoid(y W_r)                             all router outputs
    chosen = the `num_experts_per_tok` largest of s + b    (b: correction bias,
                                                   chooses, never weights)
    w = s[chosen] / sum(s[chosen]) x routed_scaling_factor
    out = shared(y) + sum over chosen e HELD HERE of w_e expert_e(y)

and `logits = RMSNorm(x; final_norm) @ lm_head`.

The SHARE. The reference is given what the chip holds: the experts
[first, first + count) of each layer (`n_routed_experts` of the file is the
count, `share.rank` says which), and a slice of the vocabulary (embedding and
head rows). It routes over ALL router outputs and leaves out what the absent
experts would have added, as the deployment's chip does before its exchange
(model-configs guide, section 4). Every held expert is computed densely for
every token and weighted by a [tokens, experts] matrix that is zero where the
expert was not chosen: no sort, no grouping.

It shares nothing with `ray_tpu/models/` but the NAMES and layouts of the
weight tensors (`w_uk` [H, nope, rank], `w_uv` [H, rank, v]; the experts'
leaves [count, ...]): it is given the program's seeded bfloat16 weights and
upcasts them piece by piece inside each layer's compiled block (an expert
layer's float32 copy would be 2.7 GB beside the engine's 12.4).

Departures from the published code: (1) rotary lanes are half-split (pair
(i, i + D/2)), not interleaved (pair (2i, 2i + 1)): a fixed permutation of
`W_uq`'s and `W_dkv`'s rotary columns, immaterial with seeded weights;
(2) `n_group` = `topk_group` = 1, so the group-limited step chooses among all
experts and is not written; (3) the vision tower is not instantiated.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inverse_frequencies(dim: int, theta: float, scaling: dict | None) -> list:
    """[dim / 2] floats, in plain Python. Pair i turns `theta^(-2i/dim)`
    radians a position; pairs that turn more than `beta_fast` times within
    `original_max_position_embeddings` keep that, pairs that turn fewer than
    `beta_slow` times turn `factor` times slower, and between the two pair
    indices a linear ramp blends the two."""
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not scaling:
        return plain
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]

    def pair_that_turns(times):
        return dim * math.log(original / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scaling["beta_slow"])), dim - 1)
    out = []
    for i, f in enumerate(plain):
        slow = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / factor * slow + f * (1.0 - slow))
    return out


def softmax_scale(model: dict) -> float:
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    sc = model.get("rope_scaling")
    if sc:
        scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return scale


def _rope(x, inv, cs_scale):
    """x [S, ..., D]; position s rotates pair (i, i + D/2) by s * inv[i]."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    ang = ang.reshape(S, *([1] * (x.ndim - 2)), half)
    cos, sin = jnp.cos(ang) * cs_scale, jnp.sin(ang) * cs_scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, model: dict):
    """x [S, hidden] -> x + the latent attention sub-layer, float32."""
    S = x.shape[0]
    nope, rd = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    sc = model.get("rope_scaling")
    inv = yarn_inverse_frequencies(rd, float(model["rope_theta"]), sc)
    cs = yarn_mscale(sc["factor"], sc["mscale"]) / yarn_mscale(
        sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    scale = softmax_scale(model)
    y = _rms_norm(x, w["attn_norm"], eps)
    c_q = _rms_norm(y @ w["w_dq"], w["q_a_norm"], eps)
    q = (c_q @ w["w_uq"]).reshape(S, -1, nope + rd)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv, cs)
    ckv = y @ w["w_dkv"]
    c_kv = _rms_norm(ckv[:, :rank], w["kv_a_norm"], eps)
    k_r = _rope(ckv[:, rank:], inv, cs)                       # [S, rope]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(args):
        qn, qr, w_uk, w_uv = args           # [S, nope], [S, rope], [nope, rank], [rank, v]
        k_nope, v = c_kv @ w_uk.T, c_kv @ w_uv
        s = (qn @ k_nope.T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v

    o = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                               w["w_uk"], w["w_uv"]))         # [H, S, v]
    return x + o.transpose(1, 0, 2).reshape(S, -1) @ w["wo"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def route(y, w, model: dict, score: str = "sigmoid"):
    """y [S, hidden] -> the [S, router outputs] matrix of the weights a token
    gives each expert: zero but at its chosen ones."""
    logits = y @ w["router"]
    k = model["num_experts_per_tok"]
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        chosen = jax.lax.top_k(s + w["router_bias"], k)[1]
    else:   # what the tests tell it apart from
        s = jax.nn.softmax(logits, axis=-1)
        chosen = jax.lax.top_k(s, k)[1]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    picked = picked * model["routed_scaling_factor"]
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def expert_layer(y, w, model: dict, first: int, shared: bool = True,
                 score: str = "sigmoid"):
    """The expert layer on normalised y [S, hidden] for the experts
    [first, first + count) that `w` holds (count = `w["e_gate"].shape[0]`):
    the shared expert (if `shared`) and those experts' part of the routed
    sum. Every held expert runs densely on every token."""
    count = w["e_gate"].shape[0]
    weights = route(y, w, model, score)[:, first:first + count]    # [S, count]
    # one expert after another (`lax.map`), so that one expert's float32 copy
    # is all that lives at once
    each = jax.lax.map(lambda e: _swiglu(y, *(t.astype(F32) for t in e)),
                       (w["e_gate"], w["e_up"], w["e_down"]))       # [count, S, hidden]
    out = jnp.einsum("se,esh->sh", weights, each)
    if shared:
        out = out + _swiglu(y, w["s_gate"], w["s_up"], w["s_down"])
    return out


def first_expert(model: dict) -> int:
    """The first expert of this chip's share: `share.rank` x the count held."""
    return model.get("share", {}).get("rank", 0) * model["n_routed_experts"]


@partial(jax.jit, static_argnames=("model_json", "first", "dense"))
def _block(x, stack, l, *, model_json, first, dense):
    """Layer `l` of a stack of layers on one sequence x [S, hidden], float32
    throughout. The layer is taken out of the stacked bfloat16 weights INSIDE
    the compiled block, and the wide matrices (an expert's, a column block of
    the dense layer's) are upcast one at a time, so that no second copy of a
    layer lives beside the engine's weights."""
    model = json.loads(model_json)   # a static argument has to hash
    layer = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in stack.items()}
    wide = ("e_gate", "e_up", "e_down", "w_gate", "w_up", "w_down")
    w = {k: v if k in wide else v.astype(F32) for k, v in layer.items()}
    x = attention(x, w, model)
    y = _rms_norm(x, w["mlp_norm"], model["rms_norm_eps"])
    if dense:
        # in column blocks of one expert's width
        width = model["intermediate_size"]
        n = max(width // model["moe_intermediate_size"], 1)
        n = n if width % n == 0 else 1
        blocks = (w["w_gate"].reshape(-1, n, width // n).transpose(1, 0, 2),
                  w["w_up"].reshape(-1, n, width // n).transpose(1, 0, 2),
                  w["w_down"].reshape(n, width // n, -1))
        return x + jax.lax.map(lambda b: _swiglu(y, *(t.astype(F32) for t in b)),
                               blocks).sum(axis=0)
    return x + expert_layer(y, w, model, first)


@jax.jit
def _head(x, final_norm, lm_head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ lm_head.astype(F32)


def logits(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> float32 logits [S, the vocabulary slice]."""
    model_json, first = json.dumps(model, sort_keys=True), first_expert(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for name, dense in (("lead_layers", True), ("layers", False)):
            stack = params[name]
            for l in range(jax.tree.leaves(stack)[0].shape[0]):
                x = _block(x, stack, jnp.int32(l), model_json=model_json, first=first,
                           dense=dense)
        return _head(x, params["final_norm"], params["lm_head"], model["rms_norm_eps"])
