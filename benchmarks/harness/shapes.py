"""Operations and bytes that the ALGORITHM needs, from shapes alone.

These are the yardstick's side of every roofline share and of `train_mfu`:
they count what has to be done, not what the program as written does, so
recomputed work (remat, a backward kernel that rebuilds the probabilities
twice) raises the time and never the count. `m` is the configuration file's
model section (HF key names).
"""

from __future__ import annotations


def _itemsize(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["torch_dtype"]]


def matmul_params_per_layer(m: dict) -> int:
    h, mlp = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * mlp


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward + backward FLOPs one token of a causal sequence of `seq_len`
    requires. Matrix multiplications: 2 FLOPs per weight in the layers and the
    output head (the embedding is a lookup). Attention: QK^T and PV are
    2*D FLOPs each per (query, key) pair per head, and a causal query meets
    (seq_len + 1) / 2 keys on average. Backward is twice forward. Recomputed
    forward work is not counted."""
    weights = (m["num_hidden_layers"] * matmul_params_per_layer(m)
               + m["hidden_size"] * m["vocab_size"])
    attn = (m["num_hidden_layers"] * m["num_attention_heads"] * 2 * 2
            * m["head_dim"] * (seq_len + 1) / 2)
    return 3.0 * (2.0 * weights + attn)


def flash_attention_step(m: dict, batch: int, seq_len: int) -> dict:
    """Flash attention over one train step on ONE chip holding `batch`
    sequences: forward (2 matrix products over the causal half of the score
    matrix) and backward (5: the scores again, dP, dV, dQ, dK), every layer.
    Bytes: q, k, v, o read or written once forward; q, k, v, o, dO read and
    dQ, dK, dV written backward. Compute bounds it at these shapes."""
    L, hq, hkv, d = (m["num_hidden_layers"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    pairs = batch * hq * seq_len * (seq_len + 1) / 2
    flops = L * 7 * 2 * d * pairs
    q_bytes = batch * seq_len * hq * d * _itemsize(m)
    kv_bytes = batch * seq_len * hkv * d * _itemsize(m)
    fwd = 2 * q_bytes + 2 * kv_bytes
    bwd = 4 * q_bytes + 4 * kv_bytes
    return {"flops": flops, "bytes": L * (fwd + bwd)}


def paged_attention_step(m: dict, context_tokens: float, batch: int) -> dict:
    """Paged decode attention over one decode step: every layer reads the keys
    and values of the `context_tokens` tokens that the live sequences hold
    (nothing of an empty slot or of a page past a sequence's end), and reads
    and writes one query and output row per slot. HBM bandwidth bounds it:
    2 FLOPs per byte of K and V against a machine balance of 240."""
    L, hq, hkv, d = (m["num_hidden_layers"], m["num_attention_heads"],
                     m["num_key_value_heads"], m["head_dim"])
    kv = 2 * context_tokens * hkv * d * _itemsize(m)
    qo = 2 * batch * hq * d * _itemsize(m)
    return {"flops": L * 2 * 2 * context_tokens * hq * d, "bytes": L * (kv + qo)}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes over
    peak bytes/s, and which of the two it was."""
    t_flops = work["flops"] / peaks["bf16_flops"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
