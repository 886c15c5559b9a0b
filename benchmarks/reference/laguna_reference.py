"""The plain reference for the Laguna-S-2.1 configuration (`model_type`
`laguna`), as ISSUE 48 writes its equations down.

A straightforward float32 `jax.numpy` forward pass of ONE sequence, layer by
layer in a Python loop, the whole sequence at once, with no cache, no ring, no
kernel, no sort and no bfloat16: every matrix product runs under
`default_matmul_precision("highest")`. With `h = embed[tokens]` (H wide), `eps
= rms_norm_eps`, every matrix without bias, in layer l:

    h <- h + Attention_l(RMSNorm(h; attn_norm))
    h <- h + MLP_l(RMSNorm(h; mlp_norm))

  attention, on y [S, H], with H_l = `num_attention_heads_per_layer[l]` query
  heads over `num_key_value_heads` key-value heads of `head_dim` lanes (query
  head h reads key-value head h // (H_l / Hkv)):
    q, k = each head RMS-normalised over its own lanes with ONE learned weight
      of that width (`q_norm`, `k_norm`), before the rotation       [assumed (c)]
    rope by `rope_parameters[layer_types[l]]`: the first `partial_rotary_factor
      x head_dim` lanes turn and the rest pass through; `rope_type` default:
      inverse frequencies theta^(-2i/rot); yarn: those divided by `factor` where
      a frequency turns fewer than `beta_slow` times in `original_max_position_
      embeddings`, kept where it turns more than `beta_fast` times, a linear
      ramp over the pair index between; cos and sin times `attention_factor`
    scores q k^T / sqrt(head_dim); query i sees key j iff j <= i and, where
      `layer_types[l]` is `sliding_attention`, i - j < `sliding_window`
    o[:, h, :] *= sigmoid(y Wg)[:, h]      Wg [H, H_l] (`w_head_gate`)  [assumed (a)]
    out = concat(o) Wo
  MLP, `mlp_layer_types[l]` dense: (silu(y Wg') * (y Wu)) Wd at `intermediate_size`
  sparse:
    p = softmax(y W_r)                     all router outputs, float32  [assumed (b)]
    chosen = the `num_experts_per_tok` largest of p
    w = p[chosen] / sum(p[chosen]) x `moe_routed_scaling_factor`   (`norm_topk_prob`)
    out = sum over chosen e HELD HERE of w_e SwiGLU_e(y)  at `moe_intermediate_size`
          + SwiGLU_shared(y) at `shared_expert_intermediate_size`, on every
            token, added as it is                                       [assumed (d)]

and `logits = RMSNorm(h; final_norm) @ lm_head` (untied).

The SHARE. The reference is given what the chip holds: the experts [first,
first + count) of each layer (`num_experts` of the file is the count,
`share.rank` says which). It routes over ALL `share.router_outputs` and leaves
out what the absent experts would have added, as the deployment's chip does
before its exchange (model-configs guide, section 4). Every held expert runs
densely on every token, one after another, and is weighted by a [tokens,
experts] matrix that is zero where the expert was not chosen.

It shares nothing with `ray_tpu/models/` but the NAMES and layouts of the
weight tensors: one stacked tree a kind of layer (`lead`: full attention and a
dense MLP; `full`: full attention and experts; `win`: sliding attention and
experts), a layer's place in its tree the count of its kind before it. It is
given the program's seeded bfloat16 weights and upcasts them piece by piece
inside each layer's compiled block.

Departures from the published code: rotary lanes are half-split (pair (i, i +
rot/2) of the rotated part), not interleaved: a fixed permutation of W_q's and
W_k's columns within a head, immaterial with seeded weights. What the config
does not key is marked `assumed` above, (a) to (d), each one line here.

`wrong` computes a WRONG program on purpose (`WRONG`): the departures that the
configuration file's `check.would_fail` and `tests/test_laguna.py` hold the
comparison against.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
WIDE = ("e_gate", "e_up", "e_down", "w_gate", "w_up", "w_down")
WRONG = ("window_ignored", "window_511", "window_513", "ropes_swapped",
         "no_attention_factor", "full_rotary", "no_gate", "fewer_heads",
         "no_shared_expert", "sigmoid_router", "no_qk_norm")
SCORE_FUNC = "softmax"          # assumed (b)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def inv_freq(rope: dict, rot: int):
    """float32 [rot / 2]: a kind of layer's inverse frequencies over its `rot`
    rotated lanes, YaRN-blended where `rope_type` says."""
    theta = float(rope["rope_theta"])
    base = theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    if rope["rope_type"] != "yarn":
        return base
    original = rope["original_max_position_embeddings"]

    def pair_that_turns(times):   # the pair index whose frequency turns `times` times
        return rot * math.log(original / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return base / rope["factor"] * ramp + base * (1.0 - ramp)


def _rope(x, rope: dict, wrong: str | None):
    """x [S, heads, D]: position s turns pair (i, i + rot/2) of the first `rot`
    lanes by s x inv_freq[i]; cos and sin carry `attention_factor`."""
    S, d = x.shape[0], x.shape[-1]
    rot = d if wrong == "full_rotary" else int(d * rope["partial_rotary_factor"])
    factor = 1.0 if wrong == "no_attention_factor" else float(rope.get("attention_factor", 1.0))
    ang = (jnp.arange(S, dtype=F32)[:, None] * inv_freq(rope, rot)[None, :])[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(y, w, model: dict, layer_type: str, wrong: str | None = None):
    """Grouped-query attention of one kind on normalised y [S, H], dense, a
    query head at a time."""
    S = y.shape[0]
    nkv, d, eps = model["num_key_value_heads"], model["head_dim"], model["rms_norm_eps"]
    nh = w["wq"].shape[1] // d
    q = (y @ w["wq"]).reshape(S, nh, d)
    k = (y @ w["wk"]).reshape(S, nkv, d)
    v = (y @ w["wv"]).reshape(S, nkv, d)
    if wrong != "no_qk_norm":
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)   # assumed (c)
    kinds = ("full_attention", "sliding_attention")
    rope_of = layer_type if wrong != "ropes_swapped" else kinds[1 - kinds.index(layer_type)]
    rope = model["rope_parameters"][rope_of]
    q, k = _rope(q, rope, wrong), _rope(k, rope, wrong)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = j <= i
    if layer_type == "sliding_attention" and wrong != "window_ignored":
        window = model["sliding_window"] + {"window_511": -1, "window_513": 1}.get(wrong, 0)
        seen &= i - j < window
    group = nh // nkv

    def one_head(args):
        qh, head = args                       # [S, d], the query head's number
        kh = jax.lax.dynamic_index_in_dim(k, head // group, axis=1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, head // group, axis=1, keepdims=False)
        s = (qh @ kh.T) / jnp.sqrt(F32(d))
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), jnp.arange(nh)))    # [nh, S, d]
    o = o.transpose(1, 0, 2)
    if wrong != "no_gate":
        o = o * jax.nn.sigmoid(y @ w["w_head_gate"])[:, :, None]         # assumed (a)
    if wrong == "fewer_heads" and layer_type == "sliding_attention":
        # a window layer run at the FULL layers' head count: the rest add nothing
        o = o * (jnp.arange(nh) < model["num_attention_heads"])[None, :, None]
    return o.reshape(S, nh * d) @ w["wo"]


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate.astype(F32)) * (y @ up.astype(F32))) @ down.astype(F32)


def dense_mlp(y, w, blocks: int = 8):
    """The dense layer's SwiGLU, `blocks` slices of its width at a time."""
    m = w["w_gate"].shape[1]
    blocks = blocks if m % blocks == 0 else 1
    cols = lambda t: t.reshape(t.shape[0], blocks, m // blocks).transpose(1, 0, 2)
    rows = w["w_down"].reshape(blocks, m // blocks, -1)
    add = lambda out, e: (out + _swiglu(y, *e), None)
    return jax.lax.scan(add, jnp.zeros_like(y), (cols(w["w_gate"]), cols(w["w_up"]), rows))[0]


def route(y, w, model: dict, wrong: str | None = None):
    """y [S, H] -> the [S, router outputs] matrix of the weights a token gives
    each expert: zero but at its chosen ones."""
    logits = y @ w["router"]
    sigmoid = (SCORE_FUNC == "sigmoid") != (wrong == "sigmoid_router")
    s = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    picked, chosen = jax.lax.top_k(s, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        picked = picked / picked.sum(axis=-1, keepdims=True)
    picked = picked * model["moe_routed_scaling_factor"]
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def expert_layer(y, w, model: dict, first: int, wrong: str | None = None):
    """The expert layer on normalised y [S, H] for the experts [first, first +
    count) that `w` holds: those experts' part of the routed sum, one expert
    after another, and the shared expert, which every token takes."""
    count = w["e_down"].shape[0]
    weights = route(y, w, model, wrong)[:, first:first + count]           # [S, count]

    def add_expert(out, e):   # out + this expert's output, weighted a token
        weight, gate, up, down = e
        return out + weight[:, None] * _swiglu(y, gate, up, down), None

    out = jax.lax.scan(add_expert, jnp.zeros_like(y),
                       (weights.T, w["e_gate"], w["e_up"], w["e_down"]))[0]
    if wrong == "no_shared_expert":
        return out
    return out + _swiglu(y, w["s_gate"], w["s_up"], w["s_down"])          # assumed (d)


def first_expert(model: dict) -> int:
    """The first expert of this chip's share: `share.rank` x the count held."""
    return model.get("share", {}).get("rank", 0) * model["num_experts"]


def layer_places(model: dict) -> list:
    """(the stacked tree a layer's weights are in, its place there, its
    `layer_types` entry) of every layer, in order."""
    seen, out = {}, []
    n = model["num_hidden_layers"]   # the published lists stay whole in a cut file
    for layer_type, mlp in zip(model["layer_types"][:n], model["mlp_layer_types"][:n]):
        stack = ("lead" if mlp == "dense" else
                 "full" if layer_type == "full_attention" else "win")
        out.append((stack, seen.get(stack, 0), layer_type))
        seen[stack] = seen.get(stack, 0) + 1
    return out


@partial(jax.jit, static_argnames=("model_json", "first", "layer_type", "wrong"))
def _layer(x, stack, l, *, model_json, first, layer_type, wrong):
    """Layer `l` of a stacked tree on one sequence x [S, H], float32
    throughout. The layer is taken out of the stacked bfloat16 weights INSIDE
    the compiled function and the wide matrices are upcast a piece at a time."""
    model = json.loads(model_json)   # a static argument has to hash
    layer = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in stack.items()}
    w = {k: v if k in WIDE else v.astype(F32) for k, v in layer.items()}
    eps = model["rms_norm_eps"]
    x = x + attention(_rms_norm(x, w["attn_norm"], eps), w, model, layer_type, wrong)
    y = _rms_norm(x, w["mlp_norm"], eps)
    if "w_gate" in w:
        return x + dense_mlp(y, w)
    return x + expert_layer(y, w, model, first, wrong)


@partial(jax.jit, static_argnames=("blocks",))
def head(x, final_norm, lm_head, eps, blocks: int = 8):
    """x [S, H] -> float32 logits [S, V] through the final norm and the untied
    head, `blocks` slices of the vocabulary at a time."""
    y = _rms_norm(x, final_norm.astype(F32), eps)
    V = lm_head.shape[1]
    blocks = blocks if V % blocks == 0 else 1
    cut = lm_head.reshape(-1, blocks, V // blocks).transpose(1, 0, 2)
    out = jax.lax.map(lambda e: y @ e.astype(F32), cut)                   # [blocks, S, V / blocks]
    return out.transpose(1, 0, 2).reshape(y.shape[0], V)


def hidden(params: dict, tokens, model: dict, wrong: str | None = None):
    """tokens [S] of ONE sequence -> the residual after the last layer [S, H]."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"`wrong` is one of {WRONG}, not {wrong!r}")
    model_json, first = json.dumps(model, sort_keys=True), first_expert(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for stack, place, layer_type in layer_places(model):
            x = _layer(x, params[stack], jnp.int32(place), model_json=model_json,
                       first=first, layer_type=layer_type, wrong=wrong)
        return x


def logits(params: dict, tokens, model: dict, wrong: str | None = None):
    """tokens [S] of ONE sequence -> float32 logits [S, V]."""
    if model.get("tie_word_embeddings", False):
        raise ValueError("the Laguna reference's head is its own matrix, untied")
    with jax.default_matmul_precision("highest"):
        return head(hidden(params, tokens, model, wrong), params["final_norm"],
                    params["lm_head"], model["rms_norm_eps"])
