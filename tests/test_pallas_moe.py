"""The grouped matrix products (ops/pallas_moe.py ``moe_gmm``, ``moe_tgmm``)
in interpret mode against a loop over the experts and against
``lax.ragged_dot``: forward, dX and dW, with groups of 0 and 1 rows and of
rows that are no multiple of the tile (dX runs the forward's kernel on the
same weights with their last dimension contracted)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from theanompi_tpu.ops import pallas_moe as pm

TM, K, N = 8, 32, 16
SIZES = {
    "mixed_0_1_13": [0, 1, 13, 8, 0, 5],
    "first_and_last_empty": [0, 9, 16, 0],
    "one_group": [11],
    "no_rows_at_all": [0, 0, 0],
    "whole_tiles": [8, 16, 8],
}


def _case(sizes, seed=0):
    sizes = jnp.asarray(sizes, jnp.int32)
    G = sizes.shape[0]
    M = pm.padded_rows(48, G, TM)
    tiles = pm.group_tiles(sizes, TM)
    starts = np.asarray(TM * (jnp.cumsum(tiles) - tiles))
    real = np.zeros(M, bool)
    for g in range(G):
        real[starts[g]:starts[g] + int(sizes[g])] = True
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    # rows of no pair are zero, as the routed layer makes them
    x = jnp.where(real[:, None], jax.random.normal(kx, (M, K)), 0.0)
    w = jax.random.normal(kw, (G, K, N))
    cot = jnp.where(real[:, None], jax.random.normal(kg, (M, N)), 0.0)
    return sizes, tiles, starts, real, x, w, cot


def _loop(x, w, sizes, starts):
    """y of the real rows by a loop over the experts; zero elsewhere."""
    y = jnp.zeros((x.shape[0], w.shape[2]))
    for g in range(w.shape[0]):
        rows = slice(int(starts[g]), int(starts[g]) + int(sizes[g]))
        y = y.at[rows].set(jnp.dot(x[rows], w[g], precision="highest"))
    return y


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", list(SIZES))
def test_gmm_forward_dx_dw_against_a_loop_and_ragged_dot(name, seed):
    sizes, tiles, starts, real, x, w, cot = _case(SIZES[name], seed)
    live = np.arange(x.shape[0]) < TM * int(jnp.sum(tiles))

    def kernel(x, w):
        y = pm.gmm(x, w, sizes, tm=TM)
        return jnp.sum(jnp.where(real[:, None], y, 0.0) * cot), y

    def loop(x, w):
        y = _loop(x, w, sizes, starts)
        return jnp.sum(y * cot), y

    def ragged(x, w):
        y = lax.ragged_dot(x, w, TM * tiles, precision="highest")
        return jnp.sum(y * cot), y

    with jax.default_matmul_precision("highest"):
        (_, y), (dx, dw) = jax.value_and_grad(kernel, (0, 1), has_aux=True)(x, w)
        for oracle in (loop, ragged):
            (_, y0), (dx0, dw0) = jax.value_and_grad(oracle, (0, 1), has_aux=True)(x, w)
            np.testing.assert_allclose(np.where(real[:, None], y, 0), y0, atol=1e-4)
            # rows of dead tiles are not written: compare the live ones
            np.testing.assert_allclose(np.where(live[:, None], dx, 0),
                                       np.where(live[:, None], dx0, 0), atol=1e-4)
            np.testing.assert_allclose(dw, dw0, atol=1e-4)
    for g, n in enumerate(SIZES[name]):
        if n == 0:
            assert not np.any(np.asarray(dw[g]))  # a group of no rows: no gradient, no garbage


def test_tile_map_gives_each_live_tile_its_expert():
    te, nl = pm.tile_map(jnp.asarray([0, 1, 13, 8, 0, 5], jnp.int32), 8, 12)
    assert int(nl[0]) == 5
    assert list(np.asarray(te[:5])) == [1, 2, 2, 3, 5]
    te, nl = pm.tile_map(jnp.zeros((3,), jnp.int32), 8, 4)
    assert int(nl[0]) == 0 and int(te.max()) <= 2


def test_bf16_operands_accumulate_in_fp32():
    sizes, tiles, starts, real, x, w, _ = _case(SIZES["mixed_0_1_13"])
    y = pm.gmm(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), sizes, tm=TM)
    assert y.dtype == jnp.bfloat16
    want = _loop(x.astype(jnp.bfloat16).astype(jnp.float32),
                 w.astype(jnp.bfloat16).astype(jnp.float32), sizes, starts)
    np.testing.assert_allclose(np.where(real[:, None], y.astype(jnp.float32), 0), want,
                               rtol=2e-2, atol=2e-2)


def test_rows_that_are_no_whole_tiles_are_refused():
    with pytest.raises(ValueError, match="whole tiles"):
        pm.gmm(jnp.zeros((20, K)), jnp.zeros((2, K, N)), jnp.asarray([3, 4]), tm=TM)
