"""The ONE backward kernel of ``flash_attention`` (ops/pallas_attention.py
``flash_bwd``, and ``flash_bwd_2d`` at or above ``_BWD_2D_MIN_T``): every
product on operands of the input dtype with fp32 accumulation, ``dq`` summed
over several key blocks of another width than the query blocks, and the ring
hop's call with global offsets — in both regimes, through the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import pallas_attention as pa
from theanompi_tpu.ops.ring_attention import full_attention_reference

REGIMES = pytest.mark.parametrize("regime", ["resident", "2d"])


def _regime(monkeypatch, regime):
    if regime == "2d":
        monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 1)


def _eqns(jaxpr, primitive):
    """Every equation of ``primitive`` in ``jaxpr`` and the jaxprs below it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, primitive)


@REGIMES
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
def test_every_backward_product_runs_in_the_input_dtype(regime, dtype, window, monkeypatch):
    """The module's contract: operands in the INPUT dtype, fp32 accumulation
    — five products a tile, none lifted to fp32 for bf16 inputs."""
    _regime(monkeypatch, regime)
    q = jnp.ones((1, 64, 2, 16), dtype)

    def loss(q, k, v):
        o = pa.flash_attention(q, k, v, causal=True, window=window, block_q=16, block_k=32)
        return jnp.sum(o.astype(jnp.float32))

    grad = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr
    backward = [e for e in _eqns(grad, "pallas_call") if e.params["name"] != pa.FWD_NAME]
    assert [e.params["name"] for e in backward] == [
        pa.BWD_NAME if regime == "resident" else pa.BWD_2D_NAME]
    dots = list(_eqns(backward[0].params["jaxpr"], "dot_general"))
    assert len(dots) == 5
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


CASES = [
    # Tq, Tk, block_q, block_k, causal, window
    (96, 96, 16, 32, True, None),    # three key blocks, six query blocks
    (96, 96, 32, 16, True, None),    # six key blocks
    (96, 96, 16, 32, False, None),
    (96, 96, 32, 16, True, 40),      # a window over several blocks
    (96, 96, 16, 32, True, 7),       # a window inside one block
    (72, 120, 16, 32, True, None),   # Tq < Tk: the last key blocks see no query
    (120, 72, 32, 16, False, None),  # Tq > Tk, both ragged against their blocks
    (120, 72, 16, 32, True, 50),
]


@REGIMES
@pytest.mark.parametrize("Tq,Tk,bq,bk,causal,window", CASES)
def test_dq_summed_over_several_key_blocks(Tq, Tk, bq, bk, causal, window, regime, monkeypatch):
    """``dq`` is one resident output that every key block's grid step adds
    to: against AD of the unfused oracle, with ``block_q != block_k``."""
    _regime(monkeypatch, regime)
    r = np.random.RandomState(Tq + bq)
    q = jnp.asarray(r.randn(2, Tq, 2, 16), jnp.float32)
    k = jnp.asarray(r.randn(2, Tk, 2, 16), jnp.float32)
    v = jnp.asarray(r.randn(2, Tk, 2, 16), jnp.float32)
    g = jnp.asarray(r.randn(2, Tq, 2, 16), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(g * pa.flash_attention(q, k, v, causal=causal, window=window,
                                              block_q=bq, block_k=bk))

    def plain(q, k, v):
        return jnp.sum(g * full_attention_reference(q, k, v, causal=causal, window=window))

    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4,
                                   err_msg=f"d{name}")


@REGIMES
def test_the_ring_hops_call_with_global_offsets(regime, monkeypatch):
    """``_bwd_dispatch`` as ``_ring_flash_vjp_bwd`` calls it: two shards of a
    causal sequence, every (query shard, key shard) pair with its offsets and
    the GLOBAL lse and dsum; dq summed over the key shards and dk, dv over the
    query shards are the whole sequence's gradients, and the hop whose keys
    all lie in the future adds exactly nothing."""
    _regime(monkeypatch, regime)
    T, D, blk = 64, 16, 16  # a shard: four blocks
    r = np.random.RandomState(5)
    q, k, v, g = (jnp.asarray(r.randn(1, 2 * T, 2, D), jnp.float32) for _ in range(4))
    want = jax.grad(lambda q, k, v: jnp.sum(g * full_attention_reference(q, k, v, causal=True)),
                    argnums=(0, 1, 2))(q, k, v)

    whole = pa._Cfg(True, D ** -0.5, 2 * T, 2 * T, blk, blk, True)
    shard = whole._replace(Tq=T, Tk=T)
    q3, k3, v3, g3 = (pa._to_heads_major(x, 1, 2 * T, 2, D) for x in (q, k, v, g))
    o, lse = pa._fwd(whole, q3, k3, v3, *pa._zero_offs())
    dsum = pa._dsum_of(g3, o)
    part = lambda x, rank: x[:, rank * T:(rank + 1) * T]

    dq = [0.0, 0.0]
    dk = [0.0, 0.0]
    dv = [0.0, 0.0]
    for qr in range(2):
        for kr in range(2):
            dq_j, dk_j, dv_j = pa._bwd_dispatch(
                shard, part(q3, qr), part(k3, kr), part(v3, kr), part(g3, qr),
                part(lse, qr), part(dsum, qr), pa._as_off(qr * T), pa._as_off(kr * T))
            if kr > qr:
                assert not np.any(np.asarray(dq_j)) and not np.any(np.asarray(dk_j))
            dq[qr], dk[kr], dv[kr] = dq[qr] + dq_j, dk[kr] + dk_j, dv[kr] + dv_j
    for parts, b, name in zip((dq, dk, dv), want, "qkv"):
        a = jnp.concatenate(parts, axis=1).reshape(1, 2, 2 * T, D).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4,
                                   err_msg=f"d{name}")
