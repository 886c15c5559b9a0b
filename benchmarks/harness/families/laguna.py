"""The Laguna family (poolside Laguna-S-2.1, `model_type` `laguna`: 48 layers
in periods of one full-attention and three sliding-window layers, 48 query
heads in a full layer and 72 in a window layer over 8 key-value heads of 128,
a window of 512 keys, a rotation a kind of layer, a sigmoid gate a head on the
attention's output, a dense SwiGLU in layer 0 and in every other layer 256
softmax-routed experts of 1,024 of which a token takes 10 beside one shared
expert, an untied head): `ray_tpu/models/laguna.py` served by the paged engine
through the program's `Model` record. The configuration file holds ONE CHIP'S
SHARE of a stated deployment (`share`): `num_hidden_layers` is the first
pipeline stage's layers (the per-layer lists stay whole, as published, and the
first `num_hidden_layers` entries are read), `num_experts` the experts held
here of `share.router_outputs` that the router chooses over and `vocab_size`
this chip's rows of the embedding and the head. It serves only
(`laguna.MODEL.loss` is None: a windowed backward is ROADMAP R2), so it has no
`train_state_and_step`. See the package docstring for what a family module
holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record
from benchmarks.harness.families.llama import serve_app  # noqa: F401
from benchmarks.harness.families.ouro import kv_pool_blocks  # noqa: F401

MODEL_KEYS = ("attention_bias", "decoder_sparse_step", "gating", "gating_types", "head_dim",
              "hidden_size", "intermediate_size", "layer_types", "max_position_embeddings",
              "mlp_layer_types", "mlp_only_layers", "moe_apply_router_weight_on_input",
              "moe_intermediate_size", "moe_routed_scaling_factor",
              "moe_router_logit_softcapping", "norm_topk_prob", "num_attention_heads",
              "num_attention_heads_per_layer", "num_experts", "num_experts_per_tok",
              "num_hidden_layers", "num_key_value_heads", "rms_norm_eps", "rope_parameters",
              "shared_expert_intermediate_size", "sliding_window", "tie_word_embeddings",
              "vocab_size", "torch_dtype", "share")
FULL, WINDOW = "full_attention", "sliding_attention"


def layers_of(m: dict, layer_type: str | None = None, mlp: str | None = None) -> list:
    """The indices of the layers held here (the first `num_hidden_layers` of
    the published lists) that are of `layer_type` and whose MLP is `mlp`."""
    return [l for l in range(m["num_hidden_layers"])
            if layer_type in (None, m["layer_types"][l])
            and mlp in (None, m["mlp_layer_types"][l])]


def heads_of(m: dict, layer_type: str) -> int:
    """A kind of layer's query heads: `num_attention_heads_per_layer`, which
    must say one number a kind."""
    counts = {m["num_attention_heads_per_layer"][l] for l in layers_of(m, layer_type)}
    if len(counts) != 1:
        raise SystemExit(f"benchmark: {layer_type} layers of {sorted(counts)} query heads: "
                         f"LagunaConfig has one count a kind of layer")
    return counts.pop()


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `LagunaConfig`. A program without the family
    (any before PR 48) ends here, by name."""
    import dataclasses

    import jax.numpy as jnp

    try:
        from ray_tpu.models import laguna, llama, moe
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'laguna' needs `ray_tpu.models.laguna` (sliding-window "
            "layers among full ones: a ring of `sliding_window` rows a sequence in the paged "
            "pool beside the full layers' pages a token, `llama.window_attend`; the banded "
            "flash forward, `flash_attention(window=)`; the window decode kernel; a head "
            "count and a rotation a kind of layer and a gate a head in `llama.gqa_attention`): "
            "this program has none, so it cannot serve Laguna through build_openai_app -> "
            "PagedLLMEngine") from None
    n = model["num_hidden_layers"]
    dense = layers_of(model, mlp="dense")
    refuse = {
        "attention_bias": model["attention_bias"],
        "tie_word_embeddings": model["tie_word_embeddings"],
        "gating": model["gating"] != "per-head",
        "gating_types": set(model["gating_types"][:n]) != {"per_head"},
        "moe_apply_router_weight_on_input": model["moe_apply_router_weight_on_input"],
        "moe_router_logit_softcapping": model["moe_router_logit_softcapping"] != 0,
        "decoder_sparse_step": model["decoder_sparse_step"] != 1,
        "mlp_only_layers": sorted(model["mlp_only_layers"]) != dense,
        "mlp_layer_types": dense != list(range(len(dense))) or any(
            model["layer_types"][l] != FULL for l in dense),
        "layer_types": set(model["layer_types"][:n]) - set(laguna.KINDS),
        "num_attention_heads_per_layer": heads_of(model, FULL) != model["num_attention_heads"],
    }
    if any(refuse.values()):
        raise SystemExit(
            f"benchmark: LagunaConfig has no other {sorted(k for k, v in refuse.items() if v)} "
            f"than the published Laguna-S-2.1's (no bias, an untied head, a per-head gate on "
            f"every layer, router weights on the experts' outputs, no soft cap, every layer "
            f"after the leading dense full-attention ones sparse, one of "
            f"{sorted(laguna.KINDS)} a layer, `num_attention_heads` the full layers' count)")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    d = model["head_dim"]

    def rope(layer_type):
        r = model["rope_parameters"][layer_type]
        rot = int(d * r["partial_rotary_factor"])
        yarn = ((float(r["factor"]), r["original_max_position_embeddings"],
                 float(r["beta_fast"]), float(r["beta_slow"]))
                if r["rope_type"] == "yarn" else None)
        return laguna.Rope(float(r["rope_theta"]), None if rot == d else rot, yarn,
                           float(r.get("attention_factor", 1.0)))

    share = model["share"]
    held, total = model["num_experts"], share["router_outputs"]
    base = llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=model["intermediate_size"], num_layers=n,
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
        head_dim=d, max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_parameters"][FULL]["rope_theta"]),
        rms_eps=model["rms_norm_eps"], tie_embeddings=False, dtype=dtype, **extra)
    experts = moe.MoEConfig(
        base=dataclasses.replace(base, intermediate_size=model["moe_intermediate_size"]),
        num_experts=total, top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"], score_func="softmax",      # assumed (b)
        routed_scaling=float(model["moe_routed_scaling_factor"]),
        experts_held=None if held == total else (share["rank"] * held, held))
    return laguna.LagunaConfig(
        base=base, experts=experts, layer_types=tuple(model["layer_types"][:n]),
        window_heads=heads_of(model, WINDOW), window=model["sliding_window"],
        num_dense_layers=len(dense), shared_width=model["shared_expert_intermediate_size"],
        rope_full=rope(FULL), rope_window=rope(WINDOW))


def seeded_params(cfg, seed: int):
    """The program's own `laguna.init` (the residual conditioned as PR 37
    found necessary), jitted once: weights are made on the device in the type
    they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import laguna

    return jax.jit(partial(laguna.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions for this architecture

def attention_params(m: dict, layer_type: str) -> int:
    """An attention layer of a kind: q and o at the kind's head count, k and
    v, the gate a head, the two head norms and the layer's two norms."""
    h, d, nh = m["hidden_size"], m["head_dim"], heads_of(m, layer_type)
    return (2 * h * nh * d + 2 * h * m["num_key_value_heads"] * d + h * nh + 2 * d + 2 * h)


def expert_params(m: dict) -> int:
    """One routed expert: a gated MLP's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["shared_expert_intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["share"]["router_outputs"]


def params_here(m: dict) -> dict:
    """Weights this chip holds, by part: what the configuration file's memory
    arithmetic is reckoned from. The head is its own matrix (untied)."""
    h = m["hidden_size"]
    sparse = len(layers_of(m, mlp="sparse"))
    return {"embedding": h * m["vocab_size"], "head": h * m["vocab_size"] + h,
            "attention_full": len(layers_of(m, FULL)) * attention_params(m, FULL),
            "attention_window": len(layers_of(m, WINDOW)) * attention_params(m, WINDOW),
            "dense_mlp": len(layers_of(m, mlp="dense")) * 3 * h * m["intermediate_size"],
            "routers": sparse * router_params(m), "shared_experts": sparse * shared_params(m),
            "experts_held": sparse * m["num_experts"] * expert_params(m)}


def experts_touched(m: dict, batch: float) -> float:
    """Of the experts held here, how many at least one of `batch` tokens
    chooses if the router spreads evenly: each token takes
    `num_experts_per_tok` of `share.router_outputs`."""
    miss = 1.0 - m["num_experts_per_tok"] / m["share"]["router_outputs"]
    return m["num_experts"] * (1.0 - miss ** batch)


def pool_row(m: dict) -> int:
    """Values in a token's K (or V) row, of the paged pool and of a ring
    alike: every key-value head in whole 128-lane tiles."""
    return m["num_key_value_heads"] * -(-m["head_dim"] // 128) * 128


def ring_bytes(m: dict) -> int:
    """One window layer's ring a SEQUENCE: `sliding_window` K rows and as many
    V rows, whatever the sequence's length: 2,097,152 B at the published sizes."""
    return 2 * m["sliding_window"] * pool_row(m) * shapes._itemsize(m)


def window_pool_pages(config: dict) -> int:
    """Rings of the engine's pool that a sequence can be given: one a slot
    (the pool's `num_sequences` less the garbage ring)."""
    return config["engine"]["max_batch_size"]


def paged_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Paged decode attention over one decode step, the FULL layers alone
    (the kernel `paged_attention_decode` runs in no other): each reads the
    keys and values of the `context_tokens` tokens the live sequences hold and
    reads and writes one query and output row a slot. HBM bandwidth bounds it."""
    L, hq, d = len(layers_of(m, FULL)), heads_of(m, FULL), m["head_dim"]
    item = shapes._itemsize(m)
    kv = 2 * context_tokens * pool_row(m) * item
    qo = 2 * batch * hq * d * item
    return {"flops": L * 2 * 2 * context_tokens * hq * d, "bytes": L * (kv + qo)}


def window_attention_step(m: dict, rows: float, rings: float) -> dict:
    """The window decode kernel's calls of one decode step
    (`paged_attention_window`, one a window layer): the `rows` ring rows that
    are live over the `rings` live sequences (the sum of `min(length,
    sliding_window)`: the pool's own counters `win_rows` and `win_rings`, which
    each `decode` record carries), of K and as many of V, and a query and an
    output row a ring. HBM bandwidth bounds it."""
    L, hq, d = len(layers_of(m, WINDOW)), heads_of(m, WINDOW), m["head_dim"]
    item = shapes._itemsize(m)
    kv = 2 * rows * pool_row(m) * item
    qo = 2 * rings * hq * d * item
    return {"flops": L * 2 * 2 * rows * hq * d, "bytes": L * (kv + qo)}


def flash_window_prefill(m: dict, tokens: int) -> dict:
    """The banded flash forward of ONE prefill of `tokens` live tokens, the
    window layers alone (`flash_attention_window`): query i meets `min(i + 1,
    sliding_window)` keys, two products of 2 x head_dim operations a (query,
    key) pair a head; q and o read and written once at the window layers'
    head count, k and v read once. The band's operations only: what a tile
    computes beyond the band raises the time and never the count. Compute
    bounds it."""
    L, hq, d, W = (len(layers_of(m, WINDOW)), heads_of(m, WINDOW), m["head_dim"],
                   m["sliding_window"])
    full = min(tokens, W)
    pairs = full * (full + 1) // 2 + max(tokens - W, 0) * W
    item = shapes._itemsize(m)
    moved = tokens * item * (2 * hq * d + 2 * m["num_key_value_heads"] * d)
    return {"flops": L * 4 * hq * d * pairs, "bytes": L * moved}


def decode_stream_step(m: dict, context_tokens: float, batch: int) -> dict:
    """What ONE decode step must stream from HBM: every attention layer's,
    router's and shared expert's matrices and the dense layer's once, the head
    once (the embedding is a lookup of `batch` rows), of the held experts
    those that `batch` rows touch (`experts_touched`: 25.5 of 32 at 40 rows),
    the live context's K and V rows of the full layers
    (`paged_attention_step`) and the live rings of the window layers
    (`window_attention_step`; the step's counters give the SUM of the live
    lengths and not each, so the rings' live rows are taken as `min(context
    tokens, batch x sliding_window)`: exact where every live sequence is past
    the window, as in the cell, whose shortest prompt is twice it). FLOPs: 2 a weight and row in the matrices a row
    goes through (of the experts, its share of its 10) and attention's. HBM
    bandwidth bounds it at decode batch sizes."""
    here = params_here(m)
    sparse = len(layers_of(m, mlp="sparse"))
    fixed = (here["head"] + here["attention_full"] + here["attention_window"]
             + here["dense_mlp"] + here["routers"] + here["shared_experts"])
    touched = sparse * experts_touched(m, batch) * expert_params(m)
    routed = (sparse * m["num_experts_per_tok"] * expert_params(m)
              * m["num_experts"] / m["share"]["router_outputs"])
    full = paged_attention_step(m, context_tokens, batch)
    window = window_attention_step(
        m, min(context_tokens, batch * m["sliding_window"]), batch)
    return {"flops": 2 * batch * (fixed + routed) + full["flops"] + window["flops"],
            "bytes": (fixed + touched) * shapes._itemsize(m) + full["bytes"] + window["bytes"]}
