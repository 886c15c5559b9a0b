"""Sparse mixture-of-experts decoder (OLMoE, Mixtral): dropless routing by sort.

The Llama backbone (`llama.decoder_layer`: pre-norm attention with rotary
positions, optionally OLMoE's RMSNorm over the whole projected query and key)
with, as its MLP strategy, in every block `num_experts` SwiGLU experts of which
each token uses `top_k`:

    p = softmax(float32(y @ router))          over all experts
    the top_k largest p and their experts     (weights renormalised to sum to
                                               one only if `norm_topk_prob`)
    out[t] = sum_j p[t, j] * (silu(y[t] @ gate[e_j]) * (y[t] @ up[e_j])) @ down[e_j]

Every (token, choice) is computed: there is no capacity and nothing is
dropped. Routing is done by SORT: the `T * top_k` choices are sorted by expert
(stable), the rows of `y` are gathered in that order, the rows an expert
received are counted (`group_sizes`), the three expert products run as grouped
matrix products over the sorted rows (`ops/grouped_matmul.py`: Pallas kernels
on a TPU, `jax.lax.ragged_dot` elsewhere), and the results are weighted and
summed back in token order. Both row movements are gathers in both directions
of autodiff (a permutation's transpose is its inverse), never a scatter-add.

The auxiliary load-balancing loss is, per layer and summed over layers,
`E * sum_e f_e * P_e` (`f_e` the share of the `T * top_k` choices that went to
expert `e`, `P_e` the mean of `p[:, e]`), times `router_aux_coeff`.

What a configuration may change of that (`models/kimi_k2.py` changes all of
it, OLMoE and Mixtral none):

- `score_func` "sigmoid": `s = sigmoid(float32(y @ router))`, the top_k are
  chosen by `s + router_bias` (the layer's `[E]` correction bias, which
  chooses and never weights) and weighted by `s` itself;
- `routed_scaling`: the weights, after the renormalisation, times a constant;
- a shared expert, where the layer holds `s_gate`, `s_up`, `s_down`: one
  SwiGLU that every token takes, added unweighted (scope `moe/shared`);
- experts that are NOT gated: where a layer holds no `e_gate` an expert is
  two products, `act(y @ up[e]) @ down[e]`, with the up-projection held
  TRANSPOSED as `e_up_t` [E, m, H] (an `[E, H, m]` parameter whose m is no
  whole number of lane tiles is laid out H-minor by the TPU, and the kernel
  would be handed a copy of it a step), and the shared expert likewise
  where it holds no `s_gate`; `activation` names `act` ("relu2": the squared
  ReLU of `models/nemotron_h.py`), which a gated expert applies to its gate;
- `experts_held` = (first, count): this chip's SHARE of the experts. The
  router scores and chooses over ALL `num_experts`; the layer holds the
  weights of experts [first, first + count) alone, keeps the (token, choice)
  pairs whose expert it holds and computes those experts' part of the sum.
  What the absent experts would have added is left out, and that partial
  result goes on: on one chip the layer runs without the exchange that would
  bring the other shares in, and nothing stands in for it. A share MOVES the
  rows it keeps, as the exchange would hand it only those: the held pairs
  sort first, and a share of under a quarter of the T * k pairs gathers,
  multiplies, masks and sums back `held_rows_trip` rows at a time (what an
  even router sends it and a quarter more, in whole row tiles; static) in
  `_held_rows`: one trip where the router sends it that or less, as many as
  hold every held pair where it sends more, so nothing is dropped at any
  routing, no array of T * k rows is built, and what a layer costs follows
  the pairs it holds. Where four even shares reach T * k (few tokens, or a
  large share) the layer is the text it is without a share, with the rows
  in no group masked.

With an `expert` mesh axis of more than one device the layer takes the dense
path it takes off the TPU (a Mosaic kernel cannot be partitioned) and leaves
the placement to XLA; experts spread over chips with an explicit all-to-all
are a later four-chip issue (ROADMAP R2(c)).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, llama
from ray_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul
from ray_tpu.ops.platform import target_platform


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    # attention, widths, depth, remat; its intermediate_size is ONE expert's width
    base: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    num_experts: int = 8
    top_k: int = 2
    norm_topk_prob: bool = False      # renormalise the top_k weights to sum to one
    qk_norm: bool = False             # RMSNorm over the whole projected q and k
    router_aux_coeff: float = 0.01
    score_func: str = "softmax"       # or "sigmoid", chosen with the layer's `router_bias`
    routed_scaling: float = 1.0       # the top_k weights times this
    norm_topk_eps: float = 0.0        # added to the renormalisation's sum (lfm2: 1e-6)
    activation: str = "silu"          # of `ACTIVATIONS`: the experts' and the shared expert's
    # (first, count): the experts whose weights this chip holds, of
    # `num_experts` that the router chooses over; None: all of them
    experts_held: tuple | None = None

    @property
    def vocab_size(self) -> int:   # what an engine asks of any configuration
        return self.base.vocab_size

    @staticmethod
    def tiny() -> "MoEConfig":
        return MoEConfig(base=llama.LlamaConfig.tiny(), num_experts=4, top_k=2)

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        return MoEConfig(
            base=llama.LlamaConfig(
                vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                rope_theta=1e6,
            ),
            num_experts=8, top_k=2, norm_topk_prob=True,
        )

    @staticmethod
    def olmoe_1b_7b() -> "MoEConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct as its config.json has it."""
        return MoEConfig(
            base=llama.LlamaConfig(
                vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
                max_seq_len=4096, rope_theta=10000.0, rms_eps=1e-5,
            ),
            num_experts=64, top_k=8, norm_topk_prob=False,
            qk_norm=True,
        )


_DENSE_MLP = ("w_gate", "w_up", "w_down")   # every block is MoE: experts replace these


def logical_axes(cfg: MoEConfig) -> dict:
    """Param sharding tree: experts lead with the `expert` axis."""
    ax = llama.logical_axes(cfg.base)
    layers = {k: v for k, v in ax["layers"].items() if k not in _DENSE_MLP}
    layers.update({
        "router": (None, None, None),
        "e_gate": (None, "expert", "embed_fsdp", "mlp"),
        "e_up": (None, "expert", "embed_fsdp", "mlp"),
        "e_down": (None, "expert", "mlp", "embed_fsdp"),
    })
    if cfg.qk_norm:
        layers.update(q_norm=(None, None), k_norm=(None, None))
    return {**ax, "layers": layers}


def init(cfg: MoEConfig, key: jax.Array) -> dict:
    base = cfg.base
    # the dense MLP's weights are never made (width 0): for mixtral-8x7b they
    # would be 5.6 B dead parameters
    params = llama.init(dataclasses.replace(base, intermediate_size=0), key)
    layers = {k: v for k, v in params["layers"].items() if k not in _DENSE_MLP}
    h, m, L, E = base.hidden_size, base.intermediate_size, base.num_layers, cfg.num_experts
    ks = jax.random.split(jax.random.fold_in(key, 7), 4)

    def dense(k, fan_in, *shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32) / math.sqrt(fan_in)).astype(base.dtype)

    layers["router"] = dense(ks[0], h, L, h, E)
    layers["e_gate"] = dense(ks[1], h, L, E, h, m)
    layers["e_up"] = dense(ks[2], h, L, E, h, m)
    layers["e_down"] = dense(ks[3], m, L, E, m, h)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, base.num_heads * base.hd), jnp.float32)
        layers["k_norm"] = jnp.ones((L, base.num_kv_heads * base.hd), jnp.float32)
    return {**params, "layers": layers}


@jax.custom_vjp
def _permute(x, perm, inverse):
    """x[perm] for a permutation `perm` of x's rows whose inverse is
    `inverse`: the cotangent is gathered back through the inverse, where
    autodiff of a plain gather would scatter-add."""
    return x[perm]


_permute.defvjp(lambda x, perm, inverse: (x[perm], (perm, inverse)),
                lambda res, g: (g[res[1]], None, None))


@jax.custom_vjp
def _dispatch(yt, order, inverse):
    """Rows of yt [T, H] in the order of the sorted choices: choice `c`
    (token `c // k`) lands in row `inverse[c]`. The cotangent of a token is
    the sum over its k rows, gathered through the inverse."""
    return yt[order // (order.shape[0] // yt.shape[0])]


def _dispatch_bwd(res, g):
    order, inverse, T = res
    return g[inverse].reshape(T, -1, g.shape[-1]).sum(axis=1), None, None


_dispatch.defvjp(lambda yt, order, inverse: (_dispatch(yt, order, inverse),
                                             (order, inverse, yt.shape[0])),
                 _dispatch_bwd)


COMPACTS_OVER_EVEN = 4   # a share compacts where the pairs are over this many even shares
TRIP_OVER_EVEN = (5, 4)  # and moves an even share and a quarter more a trip


def held_rows_trip(pairs: int, count: int, num_experts: int) -> int:
    """The rows a share of `count` of `num_experts` experts moves at once of
    a layer's `pairs` (token, choice) pairs: what an evenly spread router
    sends it and a quarter more (a layer's router favours or slights a share
    by that much, and a second trip costs what ~1,000 rows do), in whole row
    tiles of the grouped products; or all of them, where
    `COMPACTS_OVER_EVEN` even shares (in whole tiles) reach `pairs` and the
    layer is not worth compacting. From shapes alone, so it is static where
    the layer is traced."""
    even = -(-pairs * count // num_experts)
    tiles = lambda rows: -(-rows // ROW_TILE) * ROW_TILE
    if tiles(COMPACTS_OVER_EVEN * even) >= pairs:
        return pairs
    over, under = TRIP_OVER_EVEN
    return tiles(-(-over * even // under))


def _held_rows(yt, order, top_p, group_sizes, rows, trip: int, products):
    """A share's part of the routed sum, moving the rows it computes: the
    `rows` held pairs lie first in `order` (sorted by expert), and they are
    taken `trip` at a time. A trip gathers its pairs' tokens [trip, H], runs
    `products` over them with the groups' sizes clipped to the trip, and
    adds its rows to their tokens as ONE product on the MXU,
    `W [T, trip] @ ys [trip, H]` with `W[t, c]` the weight of pair c where
    it is token t's (the same bf16 x bf16 products as a weighted sum over k,
    accumulated in float32; a scatter-add of the rows is serial on a TPU).
    That product is 2 x T x H operations a row moved, as many as an expert's
    own where T is its width, so the trip stays near the even share and is
    no multiple of it (PERF.md section 6, PR 47). One trip unless the router
    sends this share more than `trip` pairs; then as many as hold them all
    (the carried sum rounded once a trip), so nothing is dropped at any
    routing, and no array of T * k rows is ever built. -> (out [T, H], the
    rows gathered: trips * trip). Not differentiable (the trip count is
    data): no caller trains a share."""
    T, k = top_p.shape
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    order = jnp.pad(order, (0, -order.shape[0] % trip))
    weights = top_p.reshape(T * k)
    trips = (rows + trip - 1) // trip

    def one(i, out):
        lo = i * trip
        with jax.named_scope("moe/dispatch"):
            pairs = jax.lax.dynamic_slice(order, (lo,), (trip,))
            tokens = pairs // k
            xs = yt[tokens]                                      # [trip, H]
        with jax.named_scope("moe/experts"):
            sizes = jnp.clip(ends - lo, 0, trip) - jnp.clip(starts - lo, 0, trip)
            # rows past the held pairs are in no group: `grouped_matmul`
            # computes nothing for them and says nothing of what they hold
            live = lo + jnp.arange(trip) < rows
            ys = jnp.where(live[:, None], products(xs, sizes), 0)
        with jax.named_scope("moe/combine"):
            w = jnp.where(tokens == jnp.arange(T)[:, None], weights[pairs], 0)
            return out + jnp.dot(w.astype(yt.dtype), ys,
                                 preferred_element_type=jnp.float32).astype(out.dtype)

    return jax.lax.fori_loop(0, trips, one, jnp.zeros_like(yt)), trips * trip


def router_logits(yt, router_w):
    """[T, H] x [H, E] -> float32 [T, E]: operands as they are (bfloat16 in
    training), accumulated AND returned in float32, so that the softmax and
    the choice of experts see no rounding of the logits."""
    return jnp.dot(yt, router_w, preferred_element_type=jnp.float32)


# a gated expert's three, or an un-gated one's two (`e_up_t`, `e_down`)
_EXPERT_LEAVES = ("e_gate", "e_up", "e_up_t", "e_down")
ACTIVATIONS = {"silu": jax.nn.silu, "relu2": lambda t: jnp.square(jax.nn.relu(t))}


def unstacked_experts(layers: dict) -> tuple[dict, dict]:
    """(`layers` without the experts' leaves and with `stack_index`, the
    experts' leaves [L, E, ...]) of a scan-stacked `layers` tree. A scan that
    hands each layer its slice of the experts' weights makes a COPY of them a
    layer and a step for the Pallas products to read (a custom call takes no
    fused slice: three copies of 352 MB a layer, 30% of a decode step's device
    time at Kimi's sizes; PERF.md section 6, PR 33). A cached forward scans
    the first tree and closes `moe_mlp(stacked=)` over the second, which the
    products then read in place."""
    n = jax.tree.leaves(layers)[0].shape[0]
    rest = {k: v for k, v in layers.items() if k not in _EXPERT_LEAVES}
    return ({**rest, "stack_index": jnp.arange(n, dtype=jnp.int32)},
            {k: layers[k] for k in _EXPERT_LEAVES if k in layers})


def moe_mlp(y, layer, cfg: MoEConfig, platform: str | None = None,
            stacked: dict | None = None, live=None):
    """The expert layer on normalised activations y [B, S, H] -> ([B, S, H],
    {"aux": the layer's load-balancing term, "load": rows each expert
    received over the mean T * k / E, [E], "experts": the chosen experts
    [T, k]}). `platform` goes to `grouped_matmul` (kernel on "tpu", dense
    otherwise; None: from the operands' placement). With `cfg.experts_held`
    the stats are of the experts held here: "load" [count], "rows" (the pairs
    routed to them, which is what the products are computed for), "moved"
    (the rows the dispatch gathered for them: `held_rows_trip` a trip, or
    T * k) and "experts"; no "aux" (the share serves). With `stacked` (`unstacked_experts`)
    the experts' weights are EVERY layer's, [L, E, ...], and this layer's are
    those at `layer["stack_index"]`: the products run over all L * E matrices
    with every other layer's groups empty, so nothing is sliced out. With
    `live` (bool [B, S]; a share's layer) the rows that are not live, a
    bucket's padding, go to NO expert held here: they all hold one token and so
    choose the same experts, and where those are held they alone can fill a
    trip and force a second one (PERF.md section 6, PR 48); the shared expert
    still runs over them, and what a padding row holds is never read."""
    B, S, H = y.shape
    E, k, T = cfg.num_experts, cfg.top_k, B * S
    yt = y.reshape(T, H)
    held = cfg.experts_held
    with jax.named_scope("moe/route"):
        logits = router_logits(yt, layer["router"])
        if cfg.score_func == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)                  # float32
            top_p, top_e = jax.lax.top_k(probs, k)                   # [T, k]
        elif cfg.score_func == "sigmoid":
            probs = jax.nn.sigmoid(logits)
            top_e = jax.lax.top_k(probs + layer["router_bias"], k)[1]
            top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        else:
            raise ValueError(f"unknown score_func {cfg.score_func!r}")
        if cfg.norm_topk_prob:
            total = top_p.sum(axis=-1, keepdims=True)
            top_p = top_p / (total + cfg.norm_topk_eps if cfg.norm_topk_eps else total)
        if cfg.routed_scaling != 1.0:
            top_p = top_p * cfg.routed_scaling
        choice_e = top_e.reshape(T * k)
        if held is not None:
            # a pair whose expert lives elsewhere sorts past every group held
            # here, in no group, and weighs nothing
            E = held[1]
            mine = (choice_e >= held[0]) & (choice_e < held[0] + E)
            if live is not None:
                mine &= jnp.repeat(live.reshape(T), k)
            choice_e = jnp.where(mine, choice_e - held[0], E)
            top_p = jnp.where(mine.reshape(T, k), top_p, 0.0)
        order = jnp.argsort(choice_e, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32), unique_indices=True)
        group_sizes = (choice_e[:, None] == jnp.arange(E, dtype=choice_e.dtype)
                       ).sum(axis=0, dtype=jnp.int32)            # [E]

    act = ACTIVATIONS[cfg.activation]

    def products(xs, sizes):
        """The grouped products over rows sorted by group (three of a gated
        expert, two of one that holds no `e_gate`): xs [M, H] with `sizes`
        [E] rows a group held -> [M, H]."""
        # under "dots" remat a Pallas call is no saveable dot: the backward
        # pass runs these three again. Saving their outputs by name was
        # measured (PERF.md section 6, PR 27): 2% faster at equal batch, but
        # it costs the memory of 3 of the 5 sequences a chip holds without.
        experts = layer
        if stacked is not None:
            n = stacked["e_down"].shape[0]
            experts = {k: v.reshape(n * E, *v.shape[2:]) for k, v in stacked.items()}
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), jnp.int32), sizes, (layer["stack_index"] * E,))
        gmm = partial(grouped_matmul, group_sizes=sizes, platform=platform)
        if "e_gate" in experts:
            hidden = act(gmm(xs, experts["e_gate"])) * gmm(xs, experts["e_up"])
        else:
            hidden = act(gmm(xs, experts["e_up_t"], transpose_rhs=True))
        return gmm(hidden, experts["e_down"])

    trip = T * k if held is None else held_rows_trip(T * k, held[1], cfg.num_experts)
    if trip == T * k:
        with jax.named_scope("moe/dispatch"):
            xs = _dispatch(yt, order, inverse)                   # [T * k, H]
        with jax.named_scope("moe/experts"):
            ys = products(xs, group_sizes)                       # [T * k, H]
            if held is not None:
                # rows past the held pairs are in no group: `grouped_matmul`
                # computes nothing for them and says nothing of what they hold
                rows = group_sizes.sum()
                ys = jnp.where(jnp.arange(T * k)[:, None] < rows, ys, 0)
        with jax.named_scope("moe/combine"):
            per_choice = _permute(ys, inverse, order).reshape(T, k, H)
            out = (per_choice * top_p[..., None].astype(y.dtype)).sum(axis=1)
        moved = jnp.int32(T * k)
    else:
        rows = group_sizes.sum()
        out, moved = _held_rows(yt, order, top_p, group_sizes, rows, trip, products)
    if "s_up" in layer:
        with jax.named_scope("moe/shared"):
            if "s_gate" in layer:
                hidden = act(yt @ layer["s_gate"]) * (yt @ layer["s_up"])
            else:
                hidden = act(yt @ layer["s_up"])
            out = out + hidden @ layer["s_down"]
    share = group_sizes.astype(jnp.float32) / (T * k)            # f_e
    if held is not None:
        stats = {"load": share * cfg.num_experts, "rows": rows, "moved": moved,
                 "experts": top_e}
    else:
        stats = {"aux": E * (share * probs.mean(axis=0)).sum(), "load": share * E,
                 "experts": top_e}
    return out.reshape(B, S, H), stats


def forward(params, tokens, cfg: MoEConfig, attn_fn=None, positions=None, mesh=None):
    """Token ids [B, S] -> (float32 logits [B, S, V], the layers' stats
    stacked: "aux" [L], "load" [L, E], "experts" [L, B * S, k]).

    `attn_fn` as `llama.forward`'s. `mesh` is the mesh the computation is
    sharded over, if the caller knows it: it decides kernel or dense for the
    expert products (None: from the placement of the arguments)."""
    platform = target_platform(tokens, params["embed"], mesh=mesh)
    if mesh is not None and dict(mesh.shape).get("expert", 1) > 1:
        platform = "spmd"   # experts over devices: the dense path, see the docstring
    logits, _, stats = llama.decoder_trunk(
        params, tokens, cfg.base, llama.plain_attend(attn_fn),
        partial(moe_mlp, cfg=cfg, platform=platform), positions=positions)
    return logits, stats


def loss_fn(params, tokens, targets, cfg: MoEConfig, attn_fn=None, mesh=None):
    """(objective, scalars): next-token cross-entropy (targets -100 = ignore)
    plus `router_aux_coeff` times the summed auxiliary loss; the scalars are
    `nll`, `aux_loss` (unscaled) and `router_load_max` (over layers and
    experts, the rows an expert received over the mean)."""
    logits, stats = forward(params, tokens, cfg, attn_fn, mesh=mesh)
    nll = llama.next_token_loss(logits, targets)
    aux = stats["aux"].sum()
    return nll + cfg.router_aux_coeff * aux, {
        "nll": nll, "aux_loss": aux, "router_load_max": stats["load"].max()}


def forward_paged(params, tokens, cfg: MoEConfig, pool, tables, lengths,
                  block_size: int, platform: str | None = None, **kw):
    """`llama.forward_paged` with the expert layer as its MLP strategy, the
    experts' weights read in place as every cached forward reads them
    (`unstacked_experts`); the scan's slice of them is the train step's."""
    layers, stacked = unstacked_experts(params["layers"])
    return llama.forward_paged(
        {**params, "layers": layers}, tokens, cfg.base, pool, tables, lengths, block_size,
        platform=platform,
        mlp=partial(moe_mlp, cfg=cfg, platform=platform, stacked=stacked), **kw)


def init_kv_pool(cfg: MoEConfig, num_blocks: int, block_size: int) -> dict:
    return llama.init_kv_pool(cfg.base, num_blocks, block_size)


# what train/spmd.py and the paged engines take of a model
# (ray_tpu/models/__init__.py)
MODEL = Model(init=init, logical_axes=logical_axes, loss=loss_fn,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
