"""Pallas paged decode-attention kernel tests (interpret mode on CPU).

Reference analog: the vLLM paged_attention kernel the reference delegates
serving to; here native (ops/paged_attention.py), validated against the
dense cached-attention math in models/llama.py.

These run the kernel interpreted. Compiled, it was checked on the chip by
chip_smoke.py's kernels phase (PR 21, 2026-09-26, TPU v5 lite, jax 0.9.0 /
libtpu 0.0.34): a forward_paged decode step at llama_1b widths (32 query / 8
KV heads of 64, block 16, a 128-block table, bf16, 2 layers) with ragged
lengths 1, 2, 16, 17, 38, 1028, 2047 and 2048 gave logits within 5.5e-2 of
the dense arm's (largest logit 5.14, tolerance 8 bf16 eps of it), and the
compiled step held the Mosaic call. tests/test_tpu_aot.py compiles it for a
v5e from this host at D=64 and D=128.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.paged_attention import paged_decode_attention


def _scatter_pages(k_seq, tables, block_size, num_pool_blocks):
    """[B, S, H, D] sequence layout -> head-major paged pool [H, NB, BS, D]."""
    B, S, H, D = k_seq.shape
    pages = np.zeros((H, num_pool_blocks, block_size, D), np.float32)
    for b in range(B):
        for s in range(S):
            blk = tables[b, s // block_size]
            pages[:, blk, s % block_size] = k_seq[b, s]
    return jnp.asarray(pages)


@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_decode_matches_dense(g):
    rng = np.random.default_rng(0)
    B, Hkv, D, BS, max_blocks = 3, 2, 16, 8, 4
    Hq = Hkv * g
    NB = B * max_blocks + 1
    lengths = np.array([5, 17, 32], np.int32)  # ragged, incl. full table
    # non-trivial table: pages deliberately out of order across the pool
    perm = rng.permutation(np.arange(1, NB))
    tables = perm[: B * max_blocks].reshape(B, max_blocks).astype(np.int32)

    S = max_blocks * BS
    k_seq = rng.standard_normal((B, S, Hkv, D), np.float32)
    v_seq = rng.standard_normal((B, S, Hkv, D), np.float32)
    q = jnp.asarray(rng.standard_normal((B, Hq, D), np.float32))

    k_pages = _scatter_pages(k_seq, tables, BS, NB)
    v_pages = _scatter_pages(v_seq, tables, BS, NB)

    out = paged_decode_attention(q, k_pages, v_pages, jnp.asarray(tables),
                                 jnp.asarray(lengths), interpret=True)

    # dense reference: q position = lengths-1, KV valid prefix = lengths
    ref = llama._cached_attention(
        q[:, None], jnp.asarray(k_seq), jnp.asarray(v_seq),
        jnp.asarray(lengths - 1),
        jnp.asarray(lengths - 1)[:, None],
    )[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_paged_kernel_path_matches_gather_path():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    bs = 8
    max_blocks = cfg.max_seq_len // bs
    pool = llama.init_kv_pool(cfg, num_blocks=2 * max_blocks + 1, block_size=bs)
    tables = jnp.asarray(
        np.arange(1, 2 * max_blocks + 1).reshape(2, max_blocks), jnp.int32)

    # prefill (gather path) then one decode step via both paths
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0, cfg.vocab_size)
    _, pool = llama.forward_paged(params, prompt, cfg, pool, tables,
                                  jnp.zeros(2, jnp.int32), bs)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 1), 0, cfg.vocab_size)
    lens = jnp.full((2,), 11, jnp.int32)
    lg_gather, _ = llama.forward_paged(params, tok, cfg, pool, tables, lens, bs,
                                       use_kernel=False)
    lg_kernel, _ = llama.forward_paged(params, tok, cfg, pool, tables, lens, bs,
                                       use_kernel=True)
    np.testing.assert_allclose(np.asarray(lg_kernel), np.asarray(lg_gather),
                               atol=2e-4)
