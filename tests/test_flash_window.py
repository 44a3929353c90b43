"""``flash_attention(..., window=W)`` and fewer K/V heads than query heads
(ops/pallas_attention.py), forward and gradients against a masked softmax:
W below, at and above a block, T no multiple of the block, through both
forms of the backward (the query side resident, and the 2-D grid)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import pallas_attention as pa

B, T, H, HK, D, BLOCK = 1, 100, 32, 4, 8, 32


def _masked_softmax_attention(q, k, v, window):
    """Query head i reads K/V head i // (H / HK); query t sees keys s with
    t - window < s <= t (every s <= t without a window)."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k, precision="highest") / math.sqrt(q.shape[-1])
    t, pos = jnp.arange(k.shape[1])[None, :], jnp.arange(q.shape[1])[:, None]
    seen = t <= pos
    if window is not None:
        seen &= pos - t < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", p, v, precision="highest")


@pytest.fixture(scope="module")
def qkvg():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(ks[0], (B, T, H, D)), jax.random.normal(ks[1], (B, T, HK, D)),
            jax.random.normal(ks[2], (B, T, HK, D)), jax.random.normal(ks[3], (B, T, H, D)))


@pytest.mark.parametrize("backward", ["1d", "2d"])
@pytest.mark.parametrize("window", [None, 5, BLOCK, 50, 3 * T],
                         ids=["full", "below_a_block", "a_block", "above_a_block", "above_T"])
def test_windowed_flash_matches_a_masked_softmax(qkvg, window, backward, monkeypatch):
    if backward == "2d":
        monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 16)
    q, k, v, g = qkvg

    def flash(q, k, v):
        o = pa.flash_attention(q, k, v, causal=True, window=window, precision="highest",
                               block_q=BLOCK, block_k=BLOCK)
        return jnp.sum(o * g), o

    def plain(q, k, v):
        o = _masked_softmax_attention(q, k, v, window)
        return jnp.sum(o * g), o

    (_, o), grads = jax.value_and_grad(flash, (0, 1, 2), has_aux=True)(q, k, v)
    (_, o0), grads0 = jax.value_and_grad(plain, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(o, o0, atol=2e-5)
    for a, b in zip(grads, grads0):
        assert a.shape == b.shape  # dk, dv summed over each group of query heads
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("blocks", [(32, 16), (16, 32)], ids=["bq_gt_bk", "bq_lt_bk"])
def test_unequal_blocks_under_a_window_through_the_2d_backward(qkvg, blocks, monkeypatch):
    monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 16)
    q, k, v, g = qkvg

    def flash(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, causal=True, window=40, precision="highest",
                                          block_q=blocks[0], block_k=blocks[1]) * g)

    grads = jax.grad(flash, (0, 1, 2))(q, k, v)
    grads0 = jax.grad(lambda q, k, v: jnp.sum(_masked_softmax_attention(q, k, v, 40) * g),
                      (0, 1, 2))(q, k, v)
    for a, b in zip(grads, grads0):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_windowed_2d_grid_spans_the_window_not_the_sequence():
    cfg = pa._Cfg(True, 1.0, 8192, 8192, 512, 512, True, 2048)
    assert pa._win_q_steps(cfg, 16, 16) == 5  # of 16 query blocks


def test_a_window_without_causal_is_refused(qkvg):
    q, k, v, _ = qkvg
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, causal=False, window=8)


def test_query_heads_that_no_kv_head_count_divides_are_refused(qkvg):
    q, k, v, _ = qkvg
    with pytest.raises(ValueError, match="K/V heads"):
        pa.flash_attention(q, k[:, :, :3], v[:, :, :3], causal=True)


def test_tmpi_pallas_0_takes_the_same_window(qkvg, monkeypatch):
    q, k, v, _ = qkvg
    monkeypatch.setenv("TMPI_PALLAS", "0")
    o = pa.flash_attention(q, k, v, causal=True, window=5, precision="highest")
    np.testing.assert_allclose(o, _masked_softmax_attention(q, k, v, 5), atol=2e-5)
