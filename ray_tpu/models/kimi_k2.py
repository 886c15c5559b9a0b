"""Kimi-K2 (moonshotai; `model_type` `kimi_k2`, the DeepSeek-V3 block): latent
attention, sigmoid-routed experts beside a shared expert, dense layers first.

Built FROM the one layer and the one trunk (`llama.decoder_layer` in
`llama.decoder_trunk`), with an attention strategy and an MLP strategy of its
own and a second, shorter stack of leading dense layers (`lead_layers`):

    c_q = RMSNorm(y W_dq)                       [q_lora_rank]
    [q_nope | q_rope] = c_q W_uq                per head, [nope | rope]
    [c_kv | k_r] = y W_dkv ; c_kv = RMSNorm(c_kv)     [kv_lora_rank | rope]
    q_rope, k_r = rope(.)                       ONE k_r a token, every head's
    [k_nope | v] = c_kv [W_uk | W_uv]           per head
    o = softmax(scale (q_nope . k_nope + q_rope . k_r), causal) v ;  out = o W_o

Rope is YaRN's (`yarn_inv_freq`: the blended inverse frequencies; `mscale`
equals `mscale_all_dim`, so cos and sin are not scaled) and `scale` is
`(nope + rope)^-0.5 x m^2`, `m = 0.1 mscale_all_dim ln(factor) + 1`. The rotary
lanes are in the repo's half-split order (`llama.rope`), a fixed permutation
of the published interleaved order's weight columns.

The cache is the LATENT: a token's row is `[c_kv | k_r | zeros]`, `kv_lora_rank
+ rope` values in whole 128-lane tiles (576 in 640), one leaf `[L, NB, BS,
row]`, token-major and written in place as `llama.init_kv_pool`'s. Three paths
attend, and all multiply the SAME tensors `w_uk` [Hq, nope, rank] and `w_uv`
[Hq, rank, v]:

- a prefill that continues a cached prefix (S > 1): the gathered latent view
  of the sequence's pages is up-projected to per-head keys and values and
  attended per head, in chunks of heads;
- a fresh prefill (S > 1, every sequence at position 0): the S latent rows in
  hand are up-projected and attended causally over themselves, nothing read
  back: `ops/flash_attention.py`'s forward on 192-wide q/k beside 128-wide v
  from S = 1,024 up on a TPU, the dense product over [S, S] under that;
- decode (S == 1), the projection ABSORBED: `q_lat = q_nope W_uk^T` (rank a
  head), scores `q_lat . c_kv + q_rope . k_r`, `o_lat = p c_kv`, `o = o_lat
  W_uv`. Per-head keys are never formed: every head reads the one shared row
  (`ops/paged_attention.py::latent_decode_attention` on a TPU, the same
  arithmetic over the gathered view elsewhere).

The expert layers are `moe.moe_mlp`'s: sigmoid scores, the `noaux_tc`
correction bias choosing and not weighting, the top_k renormalised and scaled,
one shared expert, and this chip's SHARE of the experts (`experts_held`). The
groups of `n_group` / `topk_group` are 1 at the published sizes (no group
step), and `model_config` refuses anything else. The vision tower of the
published checkpoints takes no part in a text request and is not made.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models import Model, llama, moe
from ray_tpu.ops.platform import target_platform


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    # hidden, heads, vocabulary, norm eps, dtype; `intermediate_size` is the
    # leading DENSE layers' width and `num_layers` the EXPERT layers alone
    base: llama.LlamaConfig = dataclasses.field(default_factory=llama.LlamaConfig.tiny)
    # router and experts; its base's `intermediate_size` is ONE expert's width
    experts: moe.MoEConfig = dataclasses.field(default_factory=moe.MoEConfig.tiny)
    first_k_dense: int = 1
    shared_experts: int = 1           # the shared expert is this many experts wide
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # YaRN: factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale_all_dim (`mscale` equal to it, so cos and sin are not scaled);
    # None: plain rope at the base's theta
    yarn: tuple | None = None

    @property
    def vocab_size(self) -> int:   # what an engine asks of any configuration
        return self.base.vocab_size

    @property
    def cache_layers(self) -> int:
        return self.first_k_dense + self.base.num_layers

    @property
    def latent_row(self) -> int:
        """A token's row of the pool: latent and shared key in whole tiles."""
        return llama.pool_head_dim(self.kv_lora_rank + self.qk_rope_head_dim)

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.yarn is not None:
            scale *= yarn_mscale(self.yarn[0], self.yarn[4]) ** 2
        return scale

    @staticmethod
    def tiny() -> "KimiK2Config":  # for tests: every mechanism, small
        base = llama.LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=2,
            num_heads=4, num_kv_heads=4, max_seq_len=128, rope_theta=50000.0,
            rms_eps=1e-5, dtype=jnp.float32, remat=False)
        experts = moe.MoEConfig(
            base=dataclasses.replace(base, intermediate_size=32), num_experts=16,
            top_k=4, norm_topk_prob=True, score_func="sigmoid", routed_scaling=2.827,
            experts_held=(4, 8))
        return KimiK2Config(base=base, experts=experts, first_k_dense=1,
                            q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=32,
                            qk_rope_head_dim=16, v_head_dim=32,
                            yarn=(64.0, 16, 32.0, 1.0, 1.0))


# ---------------------------------------------------------------- rope
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: tuple | None):
    """float32 [dim / 2]: rope's inverse frequencies, YaRN-blended: a
    frequency that turns more than `beta_fast` times in the original context
    is kept, one that turns fewer than `beta_slow` times is divided by
    `factor`, and a linear ramp over the pair index lies between."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return extra
    factor, original, beta_fast, beta_slow = yarn[:4]

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


# ---------------------------------------------------------------- params
_ATTN_AXES = {
    "attn_norm": (None, None),
    "w_dq": (None, "embed_fsdp", None),
    "q_a_norm": (None, None),
    "w_uq": (None, None, "heads"),
    "w_dkv": (None, "embed_fsdp", None),
    "kv_a_norm": (None, None),
    "w_uk": (None, "heads", None, None),
    "w_uv": (None, "heads", None, None),
    "wo": (None, "heads", "embed_fsdp"),
    "mlp_norm": (None, None),
}


def logical_axes(cfg: KimiK2Config) -> dict:
    dense = {k: (None, "embed_fsdp", "mlp") for k in ("w_gate", "w_up")}
    dense["w_down"] = (None, "mlp", "embed_fsdp")
    experts = {
        "router": (None, None, None), "router_bias": (None, None),
        "e_gate": (None, "expert", "embed_fsdp", "mlp"),
        "e_up": (None, "expert", "embed_fsdp", "mlp"),
        "e_down": (None, "expert", "mlp", "embed_fsdp"),
        "s_gate": (None, "embed_fsdp", "mlp"), "s_up": (None, "embed_fsdp", "mlp"),
        "s_down": (None, "mlp", "embed_fsdp"),
    }
    return {"embed": ("vocab", "embed_fsdp"), "final_norm": (None,),
            "lm_head": ("embed_fsdp", "vocab"),
            "lead_layers": {**_ATTN_AXES, **dense},
            "layers": {**_ATTN_AXES, **experts}}


def init(cfg: KimiK2Config, key: jax.Array) -> dict:
    """Scaled-normal weights (`llama.init`'s), scan-stacked: `lead_layers`
    [first_k_dense, ...] and `layers` [num_layers, ...]; the experts' leaves
    hold the experts held here alone. The correction bias is seeded, normal
    at 0.1: half the spread of the sigmoid scores (0.2 at unit-normal
    logits), so it changes which experts are chosen for most tokens without
    deciding the choice alone."""
    base, ex = cfg.base, cfg.experts
    h, nh, dt = base.hidden_size, base.num_heads, base.dtype
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank

    def dense(key, fan_in, *shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def attn(key, L):
        ks = jax.random.split(key, 6)
        return {
            "attn_norm": jnp.ones((L, h), jnp.float32),
            "w_dq": dense(ks[0], h, L, h, qr),
            "q_a_norm": jnp.ones((L, qr), jnp.float32),
            "w_uq": dense(ks[1], qr, L, qr, nh * (nope + rd)),
            "w_dkv": dense(ks[2], h, L, h, kr + rd),
            "kv_a_norm": jnp.ones((L, kr), jnp.float32),
            "w_uk": dense(ks[3], kr, L, nh, nope, kr),
            "w_uv": dense(ks[4], kr, L, nh, kr, vd),
            "wo": dense(ks[5], nh * vd, L, nh * vd, h),
            "mlp_norm": jnp.ones((L, h), jnp.float32),
        }

    def swiglu(key, L, width, names, lead=()):
        ks = jax.random.split(key, 3)
        return {names[0]: dense(ks[0], h, L, *lead, h, width),
                names[1]: dense(ks[1], h, L, *lead, h, width),
                names[2]: dense(ks[2], width, L, *lead, width, h)}

    k_embed, k_head, k_lead, k_layers = jax.random.split(key, 4)
    kl = jax.random.split(k_lead, 2)
    lead = {**attn(kl[0], cfg.first_k_dense),
            **swiglu(kl[1], cfg.first_k_dense, base.intermediate_size,
                     ("w_gate", "w_up", "w_down"))}
    ke = jax.random.split(k_layers, 5)
    L, m = base.num_layers, ex.base.intermediate_size
    held = ex.experts_held[1] if ex.experts_held else ex.num_experts
    layers = {
        **attn(ke[0], L),
        "router": dense(ke[1], h, L, h, ex.num_experts),
        "router_bias": 0.1 * jax.random.normal(ke[2], (L, ex.num_experts), jnp.float32),
        **swiglu(ke[3], L, m, ("e_gate", "e_up", "e_down"), lead=(held,)),
        **swiglu(ke[4], L, cfg.shared_experts * m, ("s_gate", "s_up", "s_down")),
    }
    return {"embed": dense(k_embed, h, base.vocab_size, h),
            "final_norm": jnp.ones((h,), jnp.float32),
            "lm_head": dense(k_head, h, h, base.vocab_size),
            "lead_layers": lead, "layers": layers}


# ---------------------------------------------------------------- attention
# float32 scores a chunk of heads may hold at once in a prefill: 256 MiB
SCORES_AT_ONCE = 2 ** 26


def _head_chunks(heads: int, q_len: int, k_len: int) -> int:
    """Into how many chunks of heads the prefill's attention is cut so that a
    chunk's float32 scores stay under `SCORES_AT_ONCE` (a 2,048-token prefill
    of 64 heads would hold 1 GiB of them at once beside the weights)."""
    chunks = 1
    while heads % (2 * chunks) == 0 and heads // chunks * q_len * k_len > SCORES_AT_ONCE:
        chunks *= 2
    return chunks


def latent_attention(cfg: KimiK2Config, tables, lengths, blk_idx, blk_off,
                     block_size: int, use_kernel: bool, interpret: bool,
                     fresh: bool = False):
    """The attention strategy over the paged latent pool (`decoder_layer`'s
    `attention`): the projections, the rotation, the write of the tokens'
    latent rows at (layer, blk_idx, blk_off) and the read, one of three:
    absorbed through the table at S == 1; at S > 1 when `fresh` (every
    sequence starts at position 0: `forward_paged`) up-projected from the S
    rows IN HAND and attended causally over them, the flash forward kernel
    with `use_kernel`, the dense float32-softmax product over [S, S]
    without; otherwise up-projected from the gathered table in chunks of
    heads. Scopes: `attn/latent_write`, then `attn/latent_read` (the decode
    kernel inside it) with `attn/absorb` (the two per-head products around
    it), or `attn/prompt_attend` (it reads no pool, so it is not
    `latent_read`'s)."""
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, row = cfg.kv_lora_rank, cfg.latent_row
    inv_freq = yarn_inv_freq(rd, cfg.base.rope_theta, cfg.yarn)
    scale = cfg.softmax_scale
    eps = cfg.base.rms_eps
    B, max_blocks = tables.shape

    def as_row(latent, rotary):  # [..., rank], [..., rope] -> the pool's row layout
        t = jnp.concatenate([latent, rotary], axis=-1)
        return jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, row - rank - rd)])

    def may_see(positions, k_len):  # [B, 1, S, k_len]: key t is at or before query s
        kpos = jax.lax.broadcasted_iota(jnp.int32, (B, k_len), 1)
        return kpos[:, None, None, :] <= positions[:, None, :, None]

    def attend(scores, valid, values, out):  # float32 softmax between two einsums
        p = jax.nn.softmax(jnp.where(valid, scores.astype(jnp.float32) * scale,
                                     -1e30), axis=-1)
        return jnp.einsum(out, p.astype(values.dtype), values)

    def up_project(c, w_uk, w_uv):  # latent rows -> per-head keys' nope lanes, values
        return jnp.einsum("btc,hdc->bthd", c, w_uk), jnp.einsum("btc,hcd->bthd", c, w_uv)

    def attention(_, y, layer, pool, positions, index):
        S = y.shape[1]
        c_q = llama.rms_norm(y @ layer["w_dq"], layer["q_a_norm"], eps)
        q = llama.project_heads(c_q, layer["w_uq"], nope + rd)
        q_nope, q_rope = q[..., :nope], llama.rope(q[..., nope:], positions, None, inv_freq)
        ckv = y @ layer["w_dkv"]
        c_kv = llama.rms_norm(ckv[..., :rank], layer["kv_a_norm"], eps)
        # ONE rotated key a token, every head's
        k_r = llama.rope(ckv[:, :, None, rank:], positions, None, inv_freq)[:, :, 0]
        with jax.named_scope("latent_write"):
            lat, new = pool["latent"], as_row(c_kv, k_r)
            if llama.writes_pages(fresh, S, block_size):
                # whole pages: lat[layer, tables[b, j]] = row[b, j * BS:(j + 1) * BS]
                lat = llama.write_pages(lat, index, tables, new, block_size)
            else:
                # a row scatter: lat[layer, blk_idx[b,s], blk_off[b,s]] = row[b,s]
                lat = lat.at[index, blk_idx, blk_off].set(new.astype(lat.dtype))

        def gathered():  # the sequences' pages as one view, and who may see what
            view = lat[index, tables].reshape(B, max_blocks * block_size, row)
            valid = may_see(positions, view.shape[1])
            return view[..., :rank], view[..., rank:rank + rd], valid

        def per_head(args):  # a chunk of heads: up-project, attend
            qn, qr, w_uk, w_uv = args
            k_nope, v = up_project(c, w_uk, w_uv)
            return attend(jnp.einsum("bshd,bthd->bhst", qn, k_nope)
                          + jnp.einsum("bshr,btr->bhst", qr, kr),
                          valid, v, "bhst,bthd->bshd")

        if S == 1:
            with jax.named_scope("absorb"):
                q_lat = jnp.einsum("bshd,hdc->bshc", q_nope, layer["w_uk"])
            with jax.named_scope("latent_read"):
                if use_kernel:
                    from ray_tpu.ops.paged_attention import latent_decode_attention

                    o_lat = latent_decode_attention(
                        as_row(q_lat, q_rope)[:, 0], lat, tables, lengths + 1,
                        layer=index, rank=rank, scale=scale, interpret=interpret)[:, None]
                else:
                    c, kr, valid = gathered()
                    o_lat = attend(jnp.einsum("bshc,btc->bhst", q_lat, c)
                                   + jnp.einsum("bshr,btr->bhst", q_rope, kr),
                                   valid, c, "bhst,btc->bshc")
            with jax.named_scope("absorb"):
                o = jnp.einsum("bshc,hcd->bshd", o_lat.astype(y.dtype), layer["w_uv"])
            return o, {"latent": lat}
        if fresh:
            # every key a row may see is a row of this call, as the pool holds
            # it; a bucket's padding lies after the live rows, which under the
            # causal mask never see it. Every head at once: no chunk, no stack
            with jax.named_scope("prompt_attend"):
                c, kr = c_kv.astype(lat.dtype), k_r.astype(lat.dtype)
                if use_kernel:
                    from ray_tpu.ops.flash_attention import flash_attention

                    k_nope, v = up_project(c, layer["w_uk"], layer["w_uv"])
                    k = jnp.concatenate(  # the one rotated key under every head
                        [k_nope, jnp.broadcast_to(kr[:, :, None], (*k_nope.shape[:3], rd))],
                        axis=-1)
                    o = flash_attention(jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
                                        causal=True, scale=scale, interpret=interpret)
                else:
                    valid = may_see(positions, S)
                    o = per_head((q_nope, q_rope, layer["w_uk"], layer["w_uv"]))
            return o, {"latent": lat}
        with jax.named_scope("latent_read"):
            c, kr, valid = gathered()
            heads = q.shape[2]
            chunks = _head_chunks(heads, S, c.shape[1])
            if chunks == 1:
                o = per_head((q_nope, q_rope, layer["w_uk"], layer["w_uv"]))
            else:
                cut = lambda t, axis: jnp.moveaxis(
                    t.reshape(*t.shape[:axis], chunks, heads // chunks,
                              *t.shape[axis + 1:]), axis, 0)
                o = jax.lax.map(per_head, (cut(q_nope, 2), cut(q_rope, 2),
                                           cut(layer["w_uk"], 0), cut(layer["w_uv"], 0)))
                o = jnp.moveaxis(o, 0, 2).reshape(B, S, heads, vd)
        return o, {"latent": lat}

    return attention


# ---------------------------------------------------------------- serving
def init_kv_pool(cfg: KimiK2Config, num_blocks: int, block_size: int) -> dict:
    """The paged LATENT pool: ONE page-shaped leaf `latent` [L, NB, BS, row],
    token-major (`llama.init_kv_pool`'s contract: a page is one contiguous
    run, block 0 the garbage block), `row` = `kv_lora_rank + qk_rope_head_dim`
    in whole 128-lane tiles (576 values in 640), L the leading dense and the
    expert layers together. Beside it `counters` (the `Model` record's
    contract: an engine reports them in its records and moves only the
    pages): `moe_rows`, the (token, choice) pairs the last forward routed to
    experts held here, summed over layers, and `moe_moved`, the rows its
    expert layers gathered for them (`moe.held_rows_trip` a layer, as many
    trips of it as hold the pairs where a router sent the share more)."""
    shape = (cfg.cache_layers, num_blocks, block_size, cfg.latent_row)
    return {"latent": jnp.zeros(shape, dtype=cfg.base.dtype),
            "counters": {"moe_rows": jnp.zeros((), jnp.int32),
                         "moe_moved": jnp.zeros((), jnp.int32)}}


def forward_paged(params, tokens, cfg: KimiK2Config, pool: dict, tables, lengths,
                  block_size: int, use_kernel: bool | None = None,
                  platform: str | None = None, head_rows=None, fresh: bool = False,
                  residual: llama.HyperConnections | None = None):
    """`llama.forward_paged`'s contract over the latent pool: tokens [B, S]
    append at positions [lengths, lengths + S) -> (logits, the updated pool).
    Which rows a layer's queries read, three ways, as `llama.forward_paged`'s:
    the decode step (S == 1) the live pages through the latent kernel,
    absorbed; a prefill told `fresh` (every sequence starts at position 0: a
    fact the CALLER states, statically, the engine from its span) the S rows
    it has just computed, up-projected in hand and attended causally, nothing
    read back from the pool, no table gathered, no column past S scored
    (`ops/flash_attention.py` takes the 192-wide q/k beside the 128-wide v
    since PR 41); everything else (a prompt that continues a cached prefix,
    the speculative window) the gathered table in chunks of heads.
    `use_kernel` reads through the family's kernels, interpreted off the TPU:
    the latent decode kernel at S == 1, the flash forward over a fresh
    prompt's rows; its default is a TPU's, S == 1 or `llama.flash_pays` (from
    the 1,024 bucket up; the dense float32-softmax product over [S, S] under
    that). `residual` is the trunk's (a family that runs this block on several
    streams: `models/xing4.py`), and with it the pool's counters gain
    `hc_residue`."""
    B, S = tokens.shape
    if platform is None:
        platform = target_platform(tokens, pool["latent"])
    if use_kernel is None:
        use_kernel = (llama.flash_pays(S, platform) if fresh and S > 1
                      else S == 1 and platform == "tpu")
    positions, blk_idx, blk_off = llama.page_rows(tables, lengths, S, block_size)
    attention = latent_attention(cfg, tables, lengths, blk_idx, blk_off, block_size,
                                 use_kernel, platform != "tpu", fresh)
    # the experts' weights stay where they are: the scan hands a layer its
    # index into them, not a slice (`moe.unstacked_experts`)
    layers, stacked = moe.unstacked_experts(params["layers"])
    logits, cache, stats = llama.decoder_trunk(
        {**params, "layers": layers}, tokens, cfg.base, attention,
        partial(moe.moe_mlp, cfg=cfg.experts, platform=platform, stacked=stacked),
        cache={"latent": pool["latent"]}, positions=positions, head_rows=head_rows,
        residual=residual)
    counters = {"moe_rows": stats["rows"].sum().astype(jnp.int32),
                "moe_moved": stats["moved"].sum().astype(jnp.int32)}
    if residual is not None:
        counters["hc_residue"] = stats["hc_residue"].max()
    return logits, {**cache, "counters": counters}


# it serves paged, and does not train here: at 16 bytes a parameter four
# expert layers of a chip's share do not fit a chip (PERF.md section 4)
MODEL = Model(init=init, logical_axes=logical_axes, loss=None,
              forward_paged=forward_paged, init_kv_pool=init_kv_pool)
