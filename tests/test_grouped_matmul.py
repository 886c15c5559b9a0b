"""ops/grouped_matmul.py in interpret mode against a loop over experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm

# group sizes over M = 96 rows in tiles of 16: uneven groups, an empty group
# in the middle, at the start and at the end, boundaries inside a tile
# (40 = 2.5 tiles), one group that is everything, and evenly tiled ones
CASES = {
    "uneven": [40, 8, 27, 21],
    "empty_middle": [30, 0, 50, 16],
    "empty_first_and_last": [0, 48, 48, 0],
    "boundary_inside_tile": [9, 23, 33, 31],
    "one_takes_all": [0, 0, 96, 0],
    "even_tiles": [32, 16, 16, 32],
}


def _loop(lhs, rhs, sizes):
    out, start = [], 0
    for e, n in enumerate(sizes):
        out.append(lhs[start:start + n] @ rhs[e])
        start += n
    return jnp.concatenate(out)


def _operands(sizes, K=128, N=256, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    M, E = sum(sizes), len(sizes)
    return (jax.random.normal(k1, (M, K), dtype), jax.random.normal(k2, (E, K, N), dtype),
            jnp.asarray(sizes, jnp.int32), jax.random.normal(k3, (M, N), dtype))


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_and_both_gradients_match_a_loop_over_experts(case):
    sizes = CASES[case]
    lhs, rhs, gs, cot = _operands(sizes)

    def kernel(lhs, rhs):
        out = gm.grouped_matmul(lhs, rhs, gs, tiles=(16, 128, 128), interpret=True)
        return (out * cot).sum(), out

    def loop(lhs, rhs):
        out = _loop(lhs, rhs, sizes)
        return (out * cot).sum(), out

    (_, out), (dl, dr) = jax.value_and_grad(kernel, (0, 1), has_aux=True)(lhs, rhs)
    (_, want), (wl, wr) = jax.value_and_grad(loop, (0, 1), has_aux=True)(lhs, rhs)
    # float32 operands, float32 accumulation: only the order of the sums differs
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(dl, wl, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(dr, wr, rtol=1e-5, atol=1e-4)
    for e, n in enumerate(sizes):
        if n == 0:
            assert not np.asarray(dr[e]).any(), "an empty group's d rhs is zero"


def test_rows_that_are_no_multiple_of_the_tile_and_jit():
    sizes = [13, 0, 41, 23]          # 77 rows, padded to 80 inside
    lhs, rhs, gs, cot = _operands(sizes, seed=1)
    f = jax.jit(lambda l, r, g: gm.grouped_matmul(l, r, g, tiles=(16, 128, 128),
                                                  interpret=True))
    np.testing.assert_allclose(f(lhs, rhs, gs), _loop(lhs, rhs, sizes),
                               rtol=1e-5, atol=1e-4)
    # the same compiled program on other sizes: they are data, not shapes
    sizes = [0, 70, 7, 0]
    np.testing.assert_allclose(f(lhs, rhs, jnp.asarray(sizes, jnp.int32)),
                               _loop(lhs, rhs, sizes), rtol=1e-5, atol=1e-4)


def test_bfloat16_operands_accumulate_in_float32():
    sizes = CASES["uneven"]
    lhs, rhs, gs, _ = _operands(sizes, dtype=jnp.bfloat16, seed=2)
    out = gm.grouped_matmul(lhs, rhs, gs, tiles=(16, 128, 128), interpret=True)
    assert out.dtype == jnp.bfloat16
    want = _loop(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    # one rounding of a float32 sum to bfloat16 (2**-8 relative)
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=2 ** -7, atol=0.05)


def test_off_the_tpu_it_is_ragged_dot_and_tiles_come_from_the_shapes():
    sizes = CASES["boundary_inside_tile"]
    lhs, rhs, gs, _ = _operands(sizes, seed=3)
    dense = gm.grouped_matmul(lhs, rhs, gs)            # CPU arrays: no kernel
    np.testing.assert_allclose(dense, _loop(lhs, rhs, sizes), rtol=1e-5, atol=1e-4)
    chosen = gm.grouped_matmul(lhs, rhs, gs, interpret=True)   # choose_tiles
    np.testing.assert_allclose(chosen, dense, rtol=1e-5, atol=1e-4)
    # at the OLMoE shapes the blocks fit the budget and divide the widths
    for kernel, (k, n) in {"fwd": (2048, 1024), "dlhs": (1024, 2048),
                           "drhs": (2048, 1024)}.items():
        tm, tk, tn = gm.choose_tiles(65536, k, n, 2, kernel)
        assert 65536 % tm == 0 and k % tk == 0 and n % tn == 0
        assert gm.tile_vmem_bytes(kernel, tm, tk, tn, 2) <= gm.VMEM_BUDGET
