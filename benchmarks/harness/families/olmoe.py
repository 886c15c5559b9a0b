"""The OLMoE family (allenai OLMoE-1B-7B: multi-head attention with RMSNorm
over the whole projected query and key, rotary positions, and in every block
a router over 64 SwiGLU experts of which a token uses 8, none dropped):
`ray_tpu/models/moe.py` trained by the SPMD step with the program's OLMoE
model record. It does not serve yet (ROADMAP R1: behind S7, S8 and S5), so it
has no `serve_app`. See the package docstring for what a family module holds.
"""

from __future__ import annotations

from benchmarks.harness.families import seeded_key
# attention is what it is for the Llama family: found here by readers
from benchmarks.harness.shapes import _itemsize, flash_attention_step  # noqa: F401

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings", "hidden_act",
              "torch_dtype", "num_experts", "num_experts_per_tok",
              "norm_topk_prob", "router_aux_loss_coef", "clip_qkv",
              "attention_bias")


def model_config(model: dict, **extra):
    """From the configuration file's model section (HF key names, as
    published) to the program's `MoEConfig`: attention, widths and depth as
    the Llama family maps them (`intermediate_size` is ONE expert's width,
    there and in `MoEConfig.base`), and the QK-norm every OLMoE attention
    block has."""
    from benchmarks.harness.families import llama as llama_family
    from ray_tpu.models import moe

    if model.get("clip_qkv") is not None or model.get("attention_bias"):
        raise SystemExit("benchmark: MoEConfig has no clip_qkv and no attention bias")
    return moe.MoEConfig(
        base=llama_family.model_config(model, **extra),
        num_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        norm_topk_prob=model["norm_topk_prob"], qk_norm=True,
        router_aux_coeff=model["router_aux_loss_coef"])


def train_state_and_step(model: dict, trainer: dict, mesh, key):
    """`spmd.init_state` jitted once with `out_shardings`, and
    `spmd.make_train_step` on that state, both with the program's OLMoE model
    record. A program without one (any before PR 27) ends here, by name."""
    import jax

    from ray_tpu.models import moe
    from ray_tpu.train import spmd

    record = getattr(moe, "MODEL", None)
    if record is None or not hasattr(moe.MoEConfig, "qk_norm"):
        raise SystemExit(
            "benchmark: the family 'olmoe' needs the model record `ray_tpu.models."
            "moe.MODEL` (init, logical_axes, loss) that `train/spmd.py` takes as "
            "`model=`, and `MoEConfig.qk_norm`/`norm_topk_prob`: this program has "
            "neither, so it cannot train OLMoE through JaxTrainer.fit -> "
            "spmd.make_train_step")
    cfg = model_config(model, remat=True, remat_policy=trainer["remat_policy"])
    optimizer = spmd.make_optimizer(warmup=trainer["warmup_steps"])

    def init(k):
        return spmd.init_state(cfg, k, optimizer=optimizer, model=record)

    shardings = spmd.state_shardings(cfg, mesh, jax.eval_shape(init, key), record)
    state = jax.block_until_ready(jax.jit(init, out_shardings=shardings)(key))
    return state, spmd.make_train_step(cfg, mesh, optimizer=optimizer, model=record)(state)


# -- the yardstick's shapes functions that differ for this architecture

def _attention_and_router_params(m: dict) -> int:
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return h * q + 2 * h * kv + q * h + h * m["num_experts"]


def active_params_per_layer(m: dict) -> int:
    """Matrix weights a token meets in a layer: attention, the router, and 3
    matrices of hidden x expert width for each of its 8 of the 64 experts
    (norm weights are not matrix products)."""
    return _attention_and_router_params(m) + (
        m["num_experts_per_tok"] * 3 * m["hidden_size"] * m["intermediate_size"])


def params_per_layer(m: dict) -> int:
    """All weights of a layer: the matrices with all 64 experts, plus the
    four norm weights (two of hidden size, q_norm and k_norm of theirs)."""
    norms = 2 * m["hidden_size"] + (
        m["num_attention_heads"] + m["num_key_value_heads"]) * m["head_dim"]
    return _attention_and_router_params(m) + norms + (
        m["num_experts"] * 3 * m["hidden_size"] * m["intermediate_size"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one token of a causal sequence of `seq_len`
    requires, by the ACTIVE weights: 2 FLOPs per weight it meets (attention,
    router, its 8 experts, the output head; the embedding is a lookup), and
    attention's pairs as `shapes.train_flops_per_token` counts them. Backward
    is twice forward; recomputed forward work is not counted."""
    weights = (m["num_hidden_layers"] * active_params_per_layer(m)
               + m["hidden_size"] * m["vocab_size"])
    attn = (m["num_hidden_layers"] * m["num_attention_heads"] * 2 * 2
            * m["head_dim"] * (seq_len + 1) / 2)
    return 3.0 * (2.0 * weights + attn)


def grouped_matmul_step(m: dict, batch: int, seq_len: int) -> dict:
    """The grouped matrix products of one train step on ONE chip holding
    `batch` sequences, every layer: 9 products of `2 * T * k * hidden *
    expert width` FLOPs (3 forward; backward one d lhs and one d rhs for each;
    a rematted forward is time, never count). Bytes: each pass reads the
    expert weights once (3 matrices forward, 3 for d lhs) and d rhs writes
    their gradient once; every product reads its sorted rows and writes its
    result once (d rhs reads two row operands). Compute bounds it from a few
    hundred rows an expert."""
    L, h, w = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    rows = batch * seq_len * m["num_experts_per_tok"]
    item = _itemsize(m)
    flops = L * 9 * 2 * rows * h * w
    weights = 9 * m["num_experts"] * h * w * item          # 3 fwd, 3 d lhs, 3 d rhs
    # rows moved: gate and up read [rows, h] and write [rows, w], down the
    # other way (3 products forward, the same 3 shapes for d lhs); d rhs reads
    # both operands of each of the 3
    per_product = rows * (h + w) * item
    return {"flops": flops, "bytes": L * (weights + 9 * per_product)}
