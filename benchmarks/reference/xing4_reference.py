"""The plain reference for the Xing4.0-29B-A4B configuration (`model_type`
`xing4_0`): the DeepSeek-V3 block on a residual of n = `hc_mult` streams that
manifold-constrained hyper-connections mix around every sub-layer
(arXiv:2512.24880), as ISSUE 37 writes the layer down.

A straightforward float32 `jax.numpy` forward pass of ONE sequence, a layer at
a time in a Python loop, with no cache, no kernel, no absorbed projection, no
sort, no batching and no bfloat16: every matrix product runs under
`default_matmul_precision("highest")`, and the Sinkhorn projection is a Python
loop of `hc_sinkhorn_iters`. With `X_0 [n, C]` the token's embedding copied n
times, for EACH sub-layer F of each layer (attention; then the dense MLP in
the first `first_k_dense_replace` layers, the expert layer after), with that
sub-layer's own `phi [n C, n + n + n^2]` (columns [pre | post | res]), three
scalars `alpha` and biases `b [n + n + n^2]`:

    x~      = RMSNorm(vec(X))                       [n C], eps `hc_eps`, no weight
    H~_pre  = alpha_pre  (x~ phi_pre)  + b_pre      [n]
    H~_post = alpha_post (x~ phi_post) + b_post     [n]
    H~_res  = alpha_res  mat(x~ phi_res) + b_res    [n, n], row-major
    H_pre   = sigmoid(H~_pre) ;  H_post = 2 sigmoid(H~_post)
    M       = exp(clip(H~_res, clamp_min, clamp_max)); `hc_sinkhorn_iters` times:
              M <- M / (rowsum(M) + hc_eps) ; M <- M / (colsum(M) + hc_eps)
    u       = H_pre X                               [C], the sub-layer's input
    X'      = M X + H_post^T F(u)                   [n, C]

and `logits = RMSNorm(sum_i X_L[i]; final_norm) @ lm_head`.

F for attention is `kimi_k2_reference`'s latent attention (its docstring has
the equations: they hold letter for letter at this model's sizes, softmax
scale `(nope + rope)^-0.5 x m^2`, `m = 0.1 ln(factor) + 1`) WITHOUT its
residual add: `concat_h(p_h v_h) W_o` of `y = RMSNorm(u; attn_norm)`. F for
the expert layer is that reference's `expert_layer` of `RMSNorm(u; mlp_norm)`:
sigmoid scores over all router outputs, the correction bias choosing and not
weighting, the top `num_experts_per_tok` renormalised and scaled, the shared
expert, and of the routed sum the part of the experts HELD HERE.

The SHARE: as `kimi_k2_reference`'s. The reference is given what the chip
holds (the experts [first, first + count) of each layer, `share.rank` says
which; the vocabulary is whole here), routes over all router outputs and
leaves out what the absent experts would have added.

ASSUMED, because no key of the published config decides it (the configuration
file's `assumed` says the same):
(1) the embedding is copied into the n streams, and the head reads their SUM
    through the final norm (the hyper-connections paper's);
(2) the three maps of a sub-layer are computed from the streams BEFORE that
    sub-layer, in float32, the `res` part reshaped row-major, rows normalised
    first;
(3) the pre-norm of a sub-layer (`attn_norm`, `mlp_norm`) is applied to the
    mixed input u, inside F;
(4) a sub-layer's three projections are stored as one matrix, columns
    [pre | post | res], and its three `alpha` as one vector: a layout;
(5) the next-token-prediction module (`num_nextn_predict_layers`) is not made:
    it is a training objective and an optional draft, and where it reads and
    writes the n streams is in no key of the config;
(6) of `kimi_k2_reference`: rotary lanes half-split, `n_group` = `topk_group` =
    1 so no group step.
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.reference import kimi_k2_reference as block

F32 = jnp.float32
HEAD_BLOCK = 8192   # vocabulary columns of the head upcast at once


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def sinkhorn(logits, model: dict, iters: int | None = None):
    """[S, n, n] -> exp(clip(.)) with rows, then columns, divided by their
    sums (+ `hc_eps`), `hc_sinkhorn_iters` times: a Python loop."""
    m = jnp.exp(jnp.clip(logits, model["mhc_h_res_clamp_min"], model["mhc_h_res_clamp_max"]))
    for _ in range(model["hc_sinkhorn_iters"] if iters is None else iters):
        m = m / (m.sum(axis=-1, keepdims=True) + model["hc_eps"])
        m = m / (m.sum(axis=-2, keepdims=True) + model["hc_eps"])
    return m


def hyper_maps(X, w, name: str, model: dict, iters: int | None = None):
    """X [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n]) of the
    sub-layer `name` ("attn" | "mlp") whose weights `w` holds."""
    S, n, C = X.shape
    flat = X.reshape(S, n * C)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + model["hc_eps"])
    phi, alpha, b = (w[f"hc_{name}_{part}"].astype(F32) for part in ("phi", "alpha", "bias"))
    pre = alpha[0] * (xt @ phi[:, :n]) + b[:n]
    post = alpha[1] * (xt @ phi[:, n:2 * n]) + b[n:2 * n]
    res = alpha[2] * (xt @ phi[:, 2 * n:]).reshape(S, n, n) + b[2 * n:].reshape(n, n)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), sinkhorn(res, model, iters)


def around(X, w, name: str, model: dict, F):
    """One sub-layer on the streams: X' = H_res X + H_post^T F(H_pre X)."""
    h_pre, h_post, h_res = hyper_maps(X, w, name, model)
    u = jnp.einsum("sn,snc->sc", h_pre, X)
    return jnp.einsum("sij,sjc->sic", h_res, X) + h_post[:, :, None] * F(u)[:, None, :]


def attention_out(u, w, model: dict):
    """u [S, hidden] -> the latent attention sub-layer's output (its pre-norm,
    projections, rotation, causal softmax a head, `W_o`), no residual."""
    S = u.shape[0]
    nope, rd = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    sc = model.get("rope_scaling")
    inv = block.yarn_inverse_frequencies(rd, float(model["rope_theta"]), sc)
    cs = block.yarn_mscale(sc["factor"], sc["mscale"]) / block.yarn_mscale(
        sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    scale = block.softmax_scale(model)
    y = _rms_norm(u, w["attn_norm"], eps)
    c_q = _rms_norm(y @ w["w_dq"], w["q_a_norm"], eps)
    q = (c_q @ w["w_uq"]).reshape(S, -1, nope + rd)
    q_nope, q_rope = q[..., :nope], block._rope(q[..., nope:], inv, cs)
    ckv = y @ w["w_dkv"]
    c_kv = _rms_norm(ckv[:, :rank], w["kv_a_norm"], eps)
    k_r = block._rope(ckv[:, rank:], inv, cs)                  # [S, rope], every head's
    causal = jnp.tril(jnp.ones((S, S), bool))

    def one_head(args):
        qn, qr, w_uk, w_uv = args     # [S, nope], [S, rope], [nope, rank], [rank, v]
        s = (qn @ (c_kv @ w_uk.T).T + qr @ k_r.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ (c_kv @ w_uv)

    o = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                               w["w_uk"], w["w_uv"]))          # [H, S, v]
    return o.transpose(1, 0, 2).reshape(S, -1) @ w["wo"]


def dense_mlp(y, w, model: dict):
    """SwiGLU of `intermediate_size` in column blocks of one expert's width,
    upcast one at a time."""
    width = model["intermediate_size"]
    n = max(width // model["moe_intermediate_size"], 1)
    n = n if width % n == 0 else 1
    blocks = (w["w_gate"].reshape(-1, n, width // n).transpose(1, 0, 2),
              w["w_up"].reshape(-1, n, width // n).transpose(1, 0, 2),
              w["w_down"].reshape(n, width // n, -1))
    return jax.lax.map(lambda b: block._swiglu(y, *(t.astype(F32) for t in b)),
                       blocks).sum(axis=0)


@partial(jax.jit, static_argnames=("model_json", "first", "dense"))
def _layer(X, stack, l, *, model_json, first, dense):
    """Layer `l` of a stack on one sequence's streams X [S, n, hidden],
    float32 throughout. The layer is taken out of the stacked bfloat16 weights
    INSIDE the compiled block and the wide matrices are upcast one at a time,
    so that no second copy of a layer lives beside the engine's weights."""
    model = json.loads(model_json)   # a static argument has to hash
    layer = {k: jax.lax.dynamic_index_in_dim(v, l, keepdims=False)
             for k, v in stack.items()}
    wide = ("e_gate", "e_up", "e_down", "w_gate", "w_up", "w_down")
    w = {k: v if k in wide else v.astype(F32) for k, v in layer.items()}
    eps = model["rms_norm_eps"]
    X = around(X, w, "attn", model, lambda u: attention_out(u, w, model))
    mlp = (partial(dense_mlp, w=w, model=model) if dense
           else partial(block.expert_layer, w=w, model=model, first=first))
    return around(X, w, "mlp", model, lambda u: mlp(_rms_norm(u, w["mlp_norm"], eps)))


@jax.jit
def _head(X, final_norm, lm_head, eps):
    """The streams' sum through the final norm and the head, the head's
    columns upcast `HEAD_BLOCK` at a time (whole, its float32 copy would be
    1.9 GB beside an engine that leaves 1.5)."""
    x = _rms_norm(X.sum(axis=1), final_norm.astype(F32), eps)
    V = lm_head.shape[1]
    step = HEAD_BLOCK if V % HEAD_BLOCK == 0 else V
    cols = jax.lax.map(
        lambda i: x @ jax.lax.dynamic_slice_in_dim(lm_head, i * step, step, axis=1).astype(F32),
        jnp.arange(V // step))                                 # [V / step, S, step]
    return cols.transpose(1, 0, 2).reshape(x.shape[0], V)


def logits(params: dict, tokens, model: dict):
    """tokens [S] of ONE sequence -> float32 logits [S, vocabulary]."""
    model_json, first = json.dumps(model, sort_keys=True), block.first_expert(model)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], model["hc_mult"], x.shape[1]))
        for name, dense in (("lead_layers", True), ("layers", False)):
            stack = params[name]
            for l in range(jax.tree.leaves(stack)[0].shape[0]):
                X = _layer(X, stack, jnp.int32(l), model_json=model_json, first=first,
                           dense=dense)
        return _head(X, params["final_norm"], params["lm_head"], model["rms_norm_eps"])
