"""Pallas fused flash attention (ops/pallas_attention.py) vs the unfused
single-device oracle — forward, backward (custom VJP), padding/masking
edges, the transformer wiring, and the ulysses+flash composition. On CPU
the kernels run through the Pallas interpreter — same numerics as the
native TPU lowering. (BEYOND-PARITY: the 2016 reference has no attention
op; SURVEY.md §5.7.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.pallas_attention import flash_attention
from theanompi_tpu.ops.ring_attention import (
    full_attention_reference,
    ulysses_attention,
)


def qkv(shape, seed, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    return [jnp.asarray(r.randn(*shape), dtype) for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "T,D,bq,bk",
    [
        (64, 32, 32, 32),   # exact multiples, several blocks
        (80, 24, 32, 16),   # ragged T (query+key padding), ragged D
        (16, 8, 128, 128),  # T smaller than one block
    ],
)
def test_forward_matches_reference(causal, T, D, bq, bk):
    q, k, v = qkv((2, T, 3, D), seed=T + D, )
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = full_attention_reference(q, k, v, causal=causal)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_cross_attention_unequal_lengths(causal, monkeypatch):
    """Tq != Tk cross attention, both ragged vs blocks, causal included
    (position-aligned-at-start convention) — and the TMPI_PALLAS=0
    fallback must accept the same shapes (it used to build a [Tq, Tq]
    tril mask and crash on causal Tq != Tk)."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 40, 2, 16), jnp.float32)
    k = jnp.asarray(r.randn(2, 72, 2, 16), jnp.float32)
    v = jnp.asarray(r.randn(2, 72, 2, 16), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-6, rtol=1e-5)
    monkeypatch.setenv("TMPI_PALLAS", "0")
    fb = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(fb), np.asarray(want),
                               atol=3e-6, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    """Custom-VJP backward (dq/dk/dv kernels) vs jax AD of the oracle;
    ragged sizes so the padded tail's zero-gradient path is exercised."""
    q, k, v = qkv((2, 48, 2, 24), seed=7)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            jnp.sin(f(q, k, v)) * (1.0 + jnp.arange(24))
        )

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16)),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: full_attention_reference(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


def test_bf16_inputs():
    """bf16 in/out with fp32 softmax statistics: matches the fp32 oracle
    within bf16 matmul tolerance, and preserves the input dtype."""
    q, k, v = qkv((2, 64, 2, 32), seed=3, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = full_attention_reference(
        *(t.astype(jnp.float32) for t in (q, k, v)), causal=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=4e-2, rtol=4e-2
    )


def test_fallback_env_matches(monkeypatch):
    """TMPI_PALLAS=0 routes to the unfused reference (same signature)."""
    q, k, v = qkv((1, 32, 2, 16), seed=5)
    with_pallas = flash_attention(q, k, v, causal=True)
    monkeypatch.setenv("TMPI_PALLAS", "0")
    without = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(with_pallas), np.asarray(without), atol=3e-6, rtol=1e-5
    )


@pytest.mark.slow
def test_transformer_flash_matches_dense():
    """TransformerLM(attn='flash') loss AND grads == the default local
    full-attention path on identical params (no SP axis)."""
    from theanompi_tpu.models.transformer import TransformerLM

    r = np.random.RandomState(11)
    toks = jnp.asarray(r.randint(0, 64, (2, 40)), jnp.int32)
    lm_ref = TransformerLM(vocab=64, d_model=32, n_heads=2, n_layers=2,
                           d_ff=64, max_len=40)
    lm_flash = lm_ref._replace(attn="flash")
    params = lm_ref.init(jax.random.PRNGKey(0))

    lr, gr = jax.value_and_grad(
        lambda p: lm_ref.loss(p, toks, axis_name=None)
    )(params)
    lf, gf = jax.value_and_grad(
        lambda p: lm_flash.loss(p, toks, axis_name=None)
    )(params)
    np.testing.assert_allclose(float(lf), float(lr), atol=1e-5, rtol=1e-5)
    flat_r = jax.tree_util.tree_leaves(gr)
    flat_f = jax.tree_util.tree_leaves(gf)
    for a, b in zip(flat_f, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3)


def test_precision_highest_bf16_matches_fp32_oracle():
    """precision=HIGHEST upcasts the tiles: bf16 inputs then match the
    fp32 oracle to fp32 tolerance (not bf16's ~5e-3) — the same knob the
    unfused reference exposes, so ulysses local_fn forwarding is sound."""
    q, k, v = qkv((2, 64, 2, 32), seed=17, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True,
                          precision=jax.lax.Precision.HIGHEST,
                          block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16  # output dtype preserved
    want = full_attention_reference(
        *(t.astype(jnp.float32) for t in (q, k, v)), causal=True
    )
    # bf16 OUTPUT rounding is the only remaining error source
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=1e-2, rtol=1e-2
    )
    # vs the non-upcast path the error should be strictly smaller
    loose = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    err_hi = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    err_lo = float(jnp.max(jnp.abs(loose.astype(jnp.float32) - want)))
    assert err_hi <= err_lo + 1e-6


def test_transformer_ulysses_flash_without_sp_uses_flash():
    """attn='ulysses_flash' with no SP axis degenerates to the fused
    local kernel (NOT the unfused O(T^2) reference) and matches the
    dense path numerically."""
    from theanompi_tpu.models.transformer import TransformerLM

    r = np.random.RandomState(19)
    toks = jnp.asarray(r.randint(0, 64, (2, 32)), jnp.int32)
    lm_uf = TransformerLM(vocab=64, d_model=32, n_heads=2, n_layers=1,
                          d_ff=64, max_len=32, attn="ulysses_flash")
    params = lm_uf.init(jax.random.PRNGKey(0))
    l_uf = float(lm_uf.loss(params, toks, axis_name=None))
    l_ref = float(lm_uf._replace(attn="ring").loss(params, toks, axis_name=None))
    np.testing.assert_allclose(l_uf, l_ref, atol=1e-5, rtol=1e-5)


def test_transformer_flash_under_sp_rejected():
    """attn='flash' is a local kernel: combining it with a seq axis must
    fail loudly at trace time, pointing at ring/ulysses."""
    from theanompi_tpu.models.transformer import SEQ_AXIS, TransformerLM, \
        make_sp_train_step
    from theanompi_tpu.parallel import make_mesh

    lm = TransformerLM(vocab=64, d_model=32, n_heads=2, n_layers=1,
                       d_ff=64, max_len=64, attn="flash")
    mesh = make_mesh(8, axis_names=(SEQ_AXIS,))
    step = make_sp_train_step(lm, mesh)
    toks = jnp.zeros((2, 64), jnp.int32)
    with pytest.raises(ValueError, match="ring"):
        step(lm.init(jax.random.PRNGKey(0)), toks)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense_oracle(causal, mesh8):
    """ring_flash_attention on the 8-way mesh == the dense single-device
    oracle: per-hop flash folds + logsumexp merge reproduce the exact
    global softmax, with GLOBAL-position causal masking."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.ops.pallas_attention import ring_flash_attention

    B, T, H, D = 2, 64, 2, 16
    qg, kg, vg = qkv((B, T, H, D), seed=23)

    f = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, "data", causal=causal, block_q=8, block_k=8
            ),
            mesh=mesh8,
            in_specs=(P(None, "data"),) * 3, out_specs=P(None, "data"),
            check_vma=False,
        )
    )
    shard = NamedSharding(mesh8, P(None, "data"))
    got = f(*(jax.device_put(t, shard) for t in (qg, kg, vg)))
    want = full_attention_reference(qg, kg, vg, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("backward", ["resident", "2d"])
def test_ring_flash_grads_match_dense_oracle(mesh8, backward, monkeypatch):
    """Whole-ring custom VJP (dq local-accumulated, dk/dv traveling with
    their shard) == jax AD of the dense oracle; each hop's backward is
    the one kernel, in the form its local length selects."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import theanompi_tpu.ops.pallas_attention as pa
    from theanompi_tpu.ops.pallas_attention import ring_flash_attention

    if backward == "2d":
        monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 1)

    B, T, H, D = 1, 32, 2, 8
    qg, kg, vg = qkv((B, T, H, D), seed=29)
    weight = jnp.asarray(np.random.RandomState(31).randn(D), jnp.float32)

    def ring_loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: ring_flash_attention(
                q, k, v, "data", causal=True, block_q=8, block_k=8
            ),
            mesh=mesh8,
            in_specs=(P(None, "data"),) * 3, out_specs=P(None, "data"),
            check_vma=False,
        )(q, k, v)
        return jnp.sum(jnp.sin(out) * weight)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.sin(full_attention_reference(q, k, v, causal=True)) * weight)

    shard = NamedSharding(mesh8, P(None, "data"))
    qs, ks, vs = (jax.device_put(t, shard) for t in (qg, kg, vg))
    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(qs, ks, vs)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(qg, kg, vg)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-3,
            err_msg=f"ring_flash d{name} mismatch",
        )


@pytest.mark.slow
def test_transformer_ring_flash_matches_ring(mesh8):
    """TransformerLM(attn='ring_flash') == attn='ring' (unfused) on the
    same params over the 8-way seq mesh — loss and one SGD step."""
    from theanompi_tpu.models.transformer import (
        SEQ_AXIS,
        TransformerLM,
        make_sp_train_step,
    )
    from theanompi_tpu.parallel import make_mesh

    mesh = make_mesh(8, axis_names=(SEQ_AXIS,))
    r = np.random.RandomState(37)
    toks = jnp.asarray(r.randint(0, 64, (2, 64)), jnp.int32)
    losses = {}
    for attn in ("ring", "ring_flash"):
        lm = TransformerLM(vocab=64, d_model=32, n_heads=2, n_layers=1,
                           d_ff=64, max_len=64, attn=attn)
        step = make_sp_train_step(lm, mesh, lr=0.1)
        params = lm.init(jax.random.PRNGKey(0))
        params, loss = step(params, toks)
        losses[attn] = (float(loss), params)
    np.testing.assert_allclose(losses["ring_flash"][0], losses["ring"][0],
                               atol=1e-5, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(losses["ring_flash"][1]),
                    jax.tree_util.tree_leaves(losses["ring"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


def test_ulysses_flash_composition(mesh8):
    """ulysses_attention(local_fn=flash_attention) on the 8-way mesh ==
    the dense oracle: the fused kernel runs inside shard_map, after the
    head<->sequence all-to-all."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    r = np.random.RandomState(13)
    B, T, H, D = 2, 64, 8, 16
    qg, kg, vg = qkv((B, T, H, D), seed=13)

    def sp(q, k, v):
        return ulysses_attention(
            q, k, v, "data", causal=True, local_fn=flash_attention
        )

    f = jax.jit(
        jax.shard_map(
            sp, mesh=mesh8,
            in_specs=(P(None, "data"),) * 3, out_specs=P(None, "data"),
            check_vma=False,
        )
    )
    shard = NamedSharding(mesh8, P(None, "data"))
    got = f(*(jax.device_put(t, shard) for t in (qg, kg, vg)))
    want = full_attention_reference(qg, kg, vg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_2d_grid_backward_matches_reference(causal, monkeypatch):
    """The long-context 2-D-grid backward kernels (both sides streamed
    in blocks, outputs accumulated across grid revisits — the path that
    removes the full-sequence VMEM residency at T >= _BWD_2D_MIN_T)
    must produce the SAME gradients as AD of the dense oracle. Forced
    on at small T by lowering the threshold; ragged sizes exercise the
    padded-tail and causal-skip masking."""
    import theanompi_tpu.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "_BWD_2D_MIN_T", 1)
    q, k, v = qkv((2, 48, 2, 24), seed=11)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            jnp.sin(f(q, k, v)) * (1.0 + jnp.arange(24))
        )

    gf = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=16)),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: full_attention_reference(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4,
            err_msg=f"2d d{name} mismatch (causal={causal})",
        )
