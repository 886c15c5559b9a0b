"""The Nemotron-H family (NVIDIA-Nemotron-3-Nano-30B-A3B, `model_type`
`nemotron_h`: 52 blocks of ONE sub-layer each in the order
`hybrid_override_pattern` spells, 23 Mamba-2 mixers of 64 heads of 64 with a
128-wide state over 8 groups and a 4-tap convolution, 6 grouped-query
attention blocks of 32 query over 2 key-value heads of 128 with no rotary
rotation, 23 expert blocks of 128 sigmoid-routed un-gated relu^2 experts of
which a token takes 6 beside one shared expert, an untied head):
`ray_tpu/models/nemotron_h.py` served by the paged engine through the
program's `Model` record. The configuration file holds ONE CHIP'S SHARE of a
stated deployment (`share`): `n_routed_experts` is the experts held here of
`share.router_outputs` that the router chooses over and `vocab_size` this
chip's rows of the embedding and the head; depth is whole. It serves only
(`nemotron_h.MODEL.loss` is None: the scan's backward is ROADMAP R6), so it
has no `train_state_and_step`. See the package docstring for what a family
module holds.
"""

from __future__ import annotations

from benchmarks.harness import shapes
from benchmarks.harness.families import seeded_key
# the app is the Llama family's: `build_openai_app(PagedLLMConfig(...))` takes
# any family's configuration since the engines read the `Model` record
from benchmarks.harness.families.llama import serve_app  # noqa: F401
from benchmarks.harness.families.ouro import kv_pool_blocks  # noqa: F401

MODEL_KEYS = ("attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim",
              "hidden_size", "hybrid_override_pattern", "intermediate_size",
              "layer_norm_epsilon", "mamba_head_dim", "mamba_hidden_act", "mamba_num_heads",
              "mamba_proj_bias", "max_position_embeddings", "mlp_bias", "mlp_hidden_act",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_group",
              "n_groups", "n_routed_experts", "n_shared_experts", "norm_eps",
              "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
              "num_hidden_layers", "num_key_value_heads", "rope_theta",
              "routed_scaling_factor", "sliding_window", "ssm_state_size",
              "tie_word_embeddings", "time_step_floor", "time_step_max", "time_step_min",
              "topk_group", "use_bias", "use_conv_bias", "vocab_size", "torch_dtype", "share")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `NemotronHConfig`. A program without the
    family (any before PR 45) ends here, by name."""
    import dataclasses

    import jax.numpy as jnp

    try:
        from ray_tpu.models import llama, moe, nemotron_h
    except ImportError:
        raise SystemExit(
            "benchmark: the family 'nemotron_h' needs `ray_tpu.models.nemotron_h` (a "
            "block that is one sub-layer in `llama.decoder_layer`; a Mamba-2 mixer "
            "whose state is a page a SEQUENCE in the paged pool, `Model."
            "sequence_leaves`; un-gated relu^2 experts in `moe.moe_mlp`; attention "
            "without rotary rotation): this program has none, so it cannot serve "
            "Nemotron-H through build_openai_app -> PagedLLMEngine") from None
    refuse = {
        "attention_bias": model["attention_bias"], "mamba_proj_bias": model["mamba_proj_bias"],
        "mlp_bias": model["mlp_bias"], "use_bias": model["use_bias"],
        "use_conv_bias": not model["use_conv_bias"],
        "mlp_hidden_act": model["mlp_hidden_act"] != "relu2",
        "mamba_hidden_act": model["mamba_hidden_act"] != "silu",
        "n_group": model["n_group"] != 1, "topk_group": model["topk_group"] != 1,
        "n_shared_experts": model["n_shared_experts"] != 1,
        "tie_word_embeddings": model["tie_word_embeddings"],
        "sliding_window": model["sliding_window"] is not None,
        "hybrid_override_pattern": (
            len(model["hybrid_override_pattern"]) != model["num_hidden_layers"]
            or set(model["hybrid_override_pattern"]) - set(nemotron_h.STACKS)),
    }
    if any(refuse.values()):
        raise SystemExit(
            f"benchmark: NemotronHConfig has no other {sorted(k for k, v in refuse.items() if v)} "
            f"than the published Nemotron-3-Nano-30B-A3B's (no bias but the convolution's, "
            f"relu2 experts, silu in the mixer, no expert-group limit, one shared expert, "
            f"an untied head, no sliding window, one of {sorted(nemotron_h.STACKS)} a block)")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    share = model["share"]
    held, total = model["n_routed_experts"], share["router_outputs"]
    base = llama.LlamaConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        intermediate_size=0, num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"], num_kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"], max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), rms_eps=model["layer_norm_epsilon"],
        tie_embeddings=False, dtype=dtype, **extra)
    experts = moe.MoEConfig(
        base=dataclasses.replace(base, intermediate_size=model["moe_intermediate_size"]),
        num_experts=total, top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"], score_func="sigmoid",
        routed_scaling=float(model["routed_scaling_factor"]), norm_topk_eps=1e-20,
        activation="relu2",
        experts_held=None if held == total else (share["rank"] * held, held))
    return nemotron_h.NemotronHConfig(
        base=base, experts=experts, pattern=model["hybrid_override_pattern"],
        shared_width=model["moe_shared_expert_intermediate_size"],
        mamba_heads=model["mamba_num_heads"], mamba_head_dim=model["mamba_head_dim"],
        state_size=model["ssm_state_size"], n_groups=model["n_groups"],
        conv_kernel=model["conv_kernel"], chunk_size=model["chunk_size"],
        time_step_min=model["time_step_min"], time_step_max=model["time_step_max"],
        time_step_floor=model["time_step_floor"])


def seeded_params(cfg, seed: int):
    """The program's own `nemotron_h.init` (the residual conditioned as PR 37
    found necessary, the state-space tensors in the published
    initialisation's ranges), jitted once: weights are made on the device in
    the type they are served in."""
    import jax
    from functools import partial

    from ray_tpu.models import nemotron_h

    return jax.jit(partial(nemotron_h.init, cfg))(seeded_key(seed))


# -- the yardstick's shapes functions for this architecture

def blocks_of(m: dict, letter: str) -> int:
    return m["hybrid_override_pattern"].count(letter)


def cache_layers(m: dict) -> int:
    """(K, V) pairs a token caches: the attention blocks alone."""
    return blocks_of(m, "*")


def d_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_channels(m: dict) -> int:
    return d_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_params(m: dict) -> int:
    """A Mamba-2 block: the in-projection to [z | xBC | dt], the out-projection,
    the taps and their bias, `A_log`, `D`, `dt_bias`, the gate norm and the
    block's norm."""
    h, heads = m["hidden_size"], m["mamba_num_heads"]
    return (h * (d_inner(m) + conv_channels(m) + heads) + d_inner(m) * h
            + (m["conv_kernel"] + 1) * conv_channels(m) + 3 * heads + d_inner(m) + h)


def attention_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * d + 2 * h * m["num_key_value_heads"] * d + h)


def expert_params(m: dict) -> int:
    """One routed expert: two matrices, no gate."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def shared_params(m: dict) -> int:
    return 2 * m["hidden_size"] * m["moe_shared_expert_intermediate_size"]


def router_params(m: dict) -> int:
    """The router's matrix, its selection bias and the block's norm."""
    return (m["hidden_size"] + 1) * m["share"]["router_outputs"] + m["hidden_size"]


def params_here(m: dict) -> dict:
    """Weights this chip holds, by part: what the configuration file's memory
    arithmetic is reckoned from. The head is its own matrix (untied)."""
    h, E = m["hidden_size"], blocks_of(m, "E")
    return {"embedding": h * m["vocab_size"], "head": h * m["vocab_size"] + h,
            "mamba_mixers": blocks_of(m, "M") * mamba_params(m),
            "attention": blocks_of(m, "*") * attention_params(m),
            "routers": E * router_params(m), "shared_experts": E * shared_params(m),
            "experts_held": E * m["n_routed_experts"] * expert_params(m)}


def experts_touched(m: dict, batch: float) -> float:
    """Of the experts held here, how many at least one of `batch` tokens
    chooses if the router spreads evenly: each token takes
    `num_experts_per_tok` of `share.router_outputs`."""
    miss = 1.0 - m["num_experts_per_tok"] / m["share"]["router_outputs"]
    return m["n_routed_experts"] * (1.0 - miss ** batch)


def pool_row(m: dict) -> int:
    """Values in a token's K (or V) row of the paged pool: every key-value
    head in whole 128-lane tiles (2 heads of 128: nothing is padded)."""
    return m["num_key_value_heads"] * -(-m["head_dim"] // 128) * 128


def state_page_bytes(m: dict) -> int:
    """One Mamba-2 block's state a SEQUENCE: every head's [P, N] running sum
    in float32 and the convolution's `conv_kernel - 1` rows in the model's
    type: 2,097,152 + 36,864 B at the published sizes."""
    ssm = m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"] * 4
    return ssm + (m["conv_kernel"] - 1) * conv_channels(m) * shapes._itemsize(m)


def state_pool_pages(config: dict) -> int:
    """State pages of the engine's pool that a sequence can be given: one a
    slot (the pool's `num_sequences` less the garbage page)."""
    return config["engine"]["max_batch_size"]


def paged_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Paged decode attention over one decode step, the 6 attention blocks:
    each reads the keys and values of the `context_tokens` tokens the live
    sequences hold, 2 heads of 128 lanes a row, and reads and writes one query
    and output row a slot. HBM bandwidth bounds it."""
    L, hq, d = cache_layers(m), m["num_attention_heads"], m["head_dim"]
    item = shapes._itemsize(m)
    kv = 2 * context_tokens * pool_row(m) * item
    qo = 2 * batch * hq * d * item
    return {"flops": L * 2 * 2 * context_tokens * hq * d, "bytes": L * (kv + qo)}


def ssm_state_step(m: dict, batch: float) -> dict:
    """The Mamba-2 blocks' state over one decode step: every live slot's page
    of every block read once and written once (`state_page_bytes`). FLOPs: a
    decay, an outer product's add and the read-out's multiply-add a state
    value. HBM bandwidth bounds it."""
    L = blocks_of(m, "M")
    values = m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
    return {"flops": L * batch * 6 * values, "bytes": L * batch * 2 * state_page_bytes(m)}


def ssm_kernel_step(m: dict, batch: float) -> dict:
    """The in-place state kernel's calls of one decode step
    (`ray_tpu/ops/ssm_state.py`, one a Mamba-2 block): every live slot's
    float32 running sum read once and written once; the convolution's rows
    are not the kernel's. HBM bandwidth bounds it."""
    L = blocks_of(m, "M")
    values = m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
    return {"flops": L * batch * 6 * values, "bytes": L * batch * 2 * 4 * values}


def decode_stream_step(m: dict, context_tokens: float, batch: int) -> dict:
    """What ONE decode step must stream from HBM: every mixer's, attention
    block's, router's and shared expert's matrices once, the head once (the
    embedding is a lookup of `batch` rows), of the held experts those that
    `batch` rows touch (`experts_touched`: 14.4 of 16 at 48 rows), the live
    context's K and V rows of the 6 attention blocks (`paged_attention_step`)
    and the 23 state-space blocks' state pages of the live slots, read and
    written (`ssm_state_step`). FLOPs: 2 a weight and row in the matrices a
    row goes through (of the experts, its share of its 6), attention's and
    the state's. HBM bandwidth bounds it at decode batch sizes."""
    here = params_here(m)
    E = blocks_of(m, "E")
    fixed = (here["head"] + here["mamba_mixers"] + here["attention"] + here["routers"]
             + here["shared_experts"])
    touched = E * experts_touched(m, batch) * expert_params(m)
    routed = (E * m["num_experts_per_tok"] * expert_params(m)
              * m["n_routed_experts"] / m["share"]["router_outputs"])
    attn, state = paged_attention_step(m, context_tokens, batch), ssm_state_step(m, batch)
    return {"flops": 2 * batch * (fixed + routed) + attn["flops"] + state["flops"],
            "bytes": (fixed + touched) * shapes._itemsize(m) + attn["bytes"] + state["bytes"]}
