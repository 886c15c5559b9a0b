"""The plain reference for the NVIDIA-Nemotron-3-Nano-30B-A3B configuration
(`model_type` `nemotron_h`), as ISSUE 45 writes its equations down.

A straightforward float32 `jax.numpy` forward pass of ONE sequence, block by
block in a Python loop, with no cache, no page, no chunk, no kernel, no sort
and no bfloat16: every matrix product runs under
`default_matmul_precision("highest")`, and the state-space recurrence is a
`lax.scan` over TOKENS. With `h = embed[tokens]` (H wide), `eps =
layer_norm_epsilon`, no bias but the convolution's, block l of
`hybrid_override_pattern` is ONE sub-layer:

    h <- h + f_l(RMSNorm(h; norm_l))

  `M`, Mamba-2, on y [S, H]:
    [z | xBC | dt] = y W_in                 d_inner | d_inner + 2 G N | heads
    xBC <- silu(sum_j w_j xBC_{t-(K-1-j)} + b)   K = `conv_kernel` taps,
                                            depthwise, causal, zero before 0
    [x | B | C] = xBC                       x [heads, P], B and C [G, N]; head
                                            i reads group i // (heads / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)        a head each
    state [P, N] a head, from zero:  s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T
    y_t = s_t C_t + D x_t
    g = RMSNorm over each of G groups of d_inner / G lanes of (y_t * silu(z_t)),
        one learned weight of d_inner
    out = g W_out
  `*`, attention: `num_attention_heads` query heads of `head_dim` over
    `num_key_value_heads`, causal softmax at 1 / sqrt(head_dim), NO rotary
    rotation (the family applies no positional embedding), W_o
  `E`, experts:
    s = sigmoid(y W_r)                       all router outputs, float32
    chosen = the `num_experts_per_tok` largest of s + b   (b chooses, never weighs)
    w = s[chosen] / (sum(s[chosen]) + 1e-20) x `routed_scaling_factor`
    out = sum over chosen e HELD HERE of w_e relu(y U_e)^2 D_e
          + relu(y U_s)^2 D_s                the shared expert, unweighted

and `logits = RMSNorm(h; final norm) @ W_head` (untied).

The SHARE. The reference is given what the chip holds: the experts [first,
first + count) of each layer (`n_routed_experts` of the file is the count,
`share.rank` says which). It routes over ALL `share.router_outputs` and
leaves out what the absent experts would have added, masking them itself.
Every held expert runs densely on every token, one after another, weighted by
a [tokens, experts] matrix that is zero where the expert was not chosen.

It shares nothing with `ray_tpu/models/` but the NAMES and layouts of the
weight tensors: one stacked tree a kind of block (`mamba`, `attn`,
`experts`), a block's place in its tree the count of its kind before it;
`w_in`, `conv_w` [K, channels] (tap j multiplies the input K - 1 - j back),
`conv_b`, `dt_bias`, `A_log`, `D`, `gate_norm`, `wo`; `wq`/`wk`/`wv`;
`router`, `router_bias`, `e_up_t` [count, m, H] (an expert's up-projection,
transposed) and `e_down` [count, m, H], `s_up`/`s_down`. It
is given the program's seeded bfloat16 weights and upcasts them piece by
piece inside each block's compiled function. `wrong` names ONE departure
from the equations (the tests and the cell's `would_fail` tell each apart).
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACK = {"M": "mamba", "*": "attn", "E": "experts"}
WIDE = ("e_up_t", "e_down")
WRONG = ("tap_dropped", "b_c_swapped", "silu_experts", "no_shared_expert",
         "gate_norm_whole", "rotary")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _rope(x, theta):
    """x [S, heads, D]; position s rotates pair (i, i + D/2) by s theta^(-2i/D)."""
    S, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (jnp.arange(S, dtype=F32)[:, None] * inv[None, :])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mamba(y, w, model: dict, wrong: str | None = None):
    """The Mamba-2 mixer on normalised y [S, H]: the recurrence one token at a
    time, every head's [P, N] state carried through a scan over the sequence."""
    S = y.shape[0]
    H, P, N = model["mamba_num_heads"], model["mamba_head_dim"], model["ssm_state_size"]
    G, K, eps = model["n_groups"], model["conv_kernel"], model["layer_norm_epsilon"]
    d_in = H * P
    z, xbc, dt = jnp.split(y @ w["w_in"], [d_in, 2 * d_in + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), xbc.dtype), xbc])
    taps = [j for j in range(K) if not (wrong == "tap_dropped" and j == K - 2)]
    xbc = jax.nn.silu(sum(w["conv_w"][j] * padded[j:j + S] for j in taps) + w["conv_b"])
    x, b, c = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    if wrong == "b_c_swapped":
        b, c = c, b
    x = x.reshape(S, H, P)
    b = jnp.repeat(b.reshape(S, G, N), H // G, axis=1)                   # [S, H, N]
    c = jnp.repeat(c.reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                               # [S, H]
    a = -jnp.exp(w["A_log"])

    def token(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    out = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (x, b, c, dt))[1]
    out = (out + w["D"][:, None] * x).reshape(S, d_in) * jax.nn.silu(z)
    if wrong == "gate_norm_whole":
        g = _rms_norm(out, w["gate_norm"], eps)
    else:
        g = _rms_norm(out.reshape(S, G, d_in // G), w["gate_norm"].reshape(G, -1), eps)
    return g.reshape(S, d_in) @ w["wo"]


def attention(y, w, model: dict, wrong: str | None = None):
    """Grouped-query attention on normalised y [S, H], dense and causal, a
    query head at a time, with no rotation of q or k."""
    S = y.shape[0]
    nh, nkv, d = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    q = (y @ w["wq"]).reshape(S, nh, d)
    k = (y @ w["wk"]).reshape(S, nkv, d)
    v = (y @ w["wv"]).reshape(S, nkv, d)
    if wrong == "rotary":
        q, k = _rope(q, float(model["rope_theta"])), _rope(k, float(model["rope_theta"]))
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


def route(y, w, model: dict):
    """y [S, H] -> the [S, router outputs] matrix of the weights a token gives
    each expert: zero but at its chosen ones."""
    s = jax.nn.sigmoid(y @ w["router"])
    chosen = jax.lax.top_k(s + w["router_bias"], model["num_experts_per_tok"])[1]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if model["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)
    picked = picked * model["routed_scaling_factor"]
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def expert_layer(y, w, model: dict, first: int, wrong: str | None = None):
    """The expert block on normalised y [S, H] for the experts [first, first +
    count) that `w` holds: those experts' part of the routed sum, one expert
    after another, and the shared expert, which every token takes."""
    act = jax.nn.silu if wrong == "silu_experts" else _relu2
    count = w["e_down"].shape[0]
    weights = route(y, w, model)[:, first:first + count]                 # [S, count]

    def add_expert(out, e):   # out + this expert's output, weighted a token
        weight, up, down = e
        return out + weight[:, None] * (act(y @ up.astype(F32).T) @ down.astype(F32)), None

    out = jax.lax.scan(add_expert, jnp.zeros_like(y), (weights.T, w["e_up_t"], w["e_down"]))[0]
    if wrong == "no_shared_expert":
        return out
    return out + act(y @ w["s_up"]) @ w["s_down"]


def first_expert(model: dict) -> int:
    """The first expert of this chip's share: `share.rank` x the count held."""
    return model.get("share", {}).get("rank", 0) * model["n_routed_experts"]


def block_places(model: dict) -> list:
    """(the stacked tree a block's weights are in, its place there, its
    letter) of every block, in order."""
    seen, out = {}, []
    for letter in model["hybrid_override_pattern"]:
        out.append((STACK[letter], seen.get(letter, 0), letter))
        seen[letter] = seen.get(letter, 0) + 1
    return out


@partial(jax.jit, static_argnames=("model_json", "first", "letter", "wrong"))
def _block(x, stack, l, *, model_json, first, letter, wrong):
    """Block `l` of a stacked tree on one sequence x [S, H], float32
    throughout. The block is taken out of the stacked bfloat16 weights INSIDE
    the compiled function and the experts' matrices are upcast one at a time."""
    model = json.loads(model_json)   # a static argument has to hash
    layer = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in stack.items()}
    w = {k: v if k in WIDE else v.astype(F32) for k, v in layer.items()}
    eps = model["layer_norm_epsilon"]
    if letter == "E":
        return x + expert_layer(_rms_norm(x, w["mlp_norm"], eps), w, model, first, wrong)
    y = _rms_norm(x, w["attn_norm"], eps)
    return x + (mamba if letter == "M" else attention)(y, w, model, wrong)


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
    """tokens [S] of ONE sequence -> the residual after the last block [S, H]."""
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"`wrong` is one of {WRONG}, not {wrong!r}")
    model_json, first = json.dumps(model, sort_keys=True), first_expert(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        for stack, place, letter in block_places(model):
            x = _block(x, params[stack], jnp.int32(place), model_json=model_json,
                       first=first, letter=letter, wrong=wrong)
        return x


def logits(params: dict, tokens, model: dict, wrong: str | None = None):
    """tokens [S] of ONE sequence -> float32 logits [S, V]."""
    if model.get("tie_word_embeddings", False):
        raise ValueError("the Nemotron-H reference's head is its own matrix, untied")
    with jax.default_matmul_precision("highest"):
        return head(hidden(params, tokens, model, wrong), params["final_norm"],
                    params["lm_head"], model["layer_norm_epsilon"])
