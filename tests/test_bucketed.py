"""Bucketed overlap-with-backward allreduce (ROADMAP 2b,
parallel/strategies.py::BucketedOverlapSync): bucket geometry, exact
parity with the single psum on a real multi-device mesh, codec
composition (value-space and :ef), 2-device convergence, and the
traffic-model cross-check that keeps SPMD101 honest."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.bsp import make_bsp_train_step
from theanompi_tpu.parallel.mesh import put_global_batch
from theanompi_tpu.parallel.strategies import (
    BucketedOverlapSync,
    assign_buckets,
    bucket_overlap_frac,
    bucketed,
)
from theanompi_tpu.train import init_train_state
from tests.tinymodel import TinyCNN

BUCKET_MB = 0.001  # tiny-model scale: splits TinyCNN into >= 2 buckets


def _setup(batch=16, n_dev=4):
    model = TinyCNN(TinyCNN.default_recipe().replace(batch_size=batch))
    mesh = make_mesh(n_dev)
    state = init_train_state(model, jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = put_global_batch(
        mesh, jnp.asarray(r.randn(batch, *model.recipe.input_shape),
                          jnp.float32))
    y = put_global_batch(
        mesh, jnp.asarray(r.randint(0, model.recipe.num_classes, batch),
                          jnp.int32))
    return model, mesh, state, x, y


def _params_equal(a, b):
    return all(
        bool(jnp.all(x == y))
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params))
    )


# --------------------------------------------------------------------------
# geometry
# --------------------------------------------------------------------------


def test_assign_buckets_reverse_order_and_budget():
    leaves = [np.zeros(s, np.float32) for s in ((100,), (10,), (200,), (5,))]
    # budget 600 B: reverse walk [5(20B), 200(800B), 10, 100] — the 200
    # leaf overflows the first bucket and takes its own
    buckets = assign_buckets(leaves, 600)
    assert buckets == [[3], [2], [1, 0]]
    # every index exactly once
    assert sorted(i for b in buckets for i in b) == [0, 1, 2, 3]
    # one huge budget -> one bucket
    assert assign_buckets(leaves, 10 ** 9) == [[3, 2, 1, 0]]


def test_overlap_frac_schedule():
    assert bucket_overlap_frac(1) == 0.0
    assert bucket_overlap_frac(0) == 0.0
    assert bucket_overlap_frac(4) == pytest.approx(0.75)


def test_bucketed_validation():
    with pytest.raises(ValueError, match="psum"):
        bucketed("ring", "data", 4, 8.0)
    with pytest.raises(ValueError, match="positive"):
        BucketedOverlapSync("data", bucket_mb=0.0)
    # stateless codec rides the backward; :ef must not
    assert BucketedOverlapSync("data", 8.0, codec="bf16").in_backward
    ef = BucketedOverlapSync("data", 8.0, codec="int8:ef")
    assert ef.stateful and not ef.in_backward


def test_accum_steps_refused_with_buckets():
    model, mesh, *_ = _setup()
    with pytest.raises(ValueError, match="accum"):
        make_bsp_train_step(model, mesh, allreduce_buckets=BUCKET_MB,
                            accum_steps=2)
    # ...but the :ef variant syncs POST-backward (stateful) and
    # composes with accumulation — one bucketed exchange per
    # accumulated step, no refusal (README "MFU push")
    make_bsp_train_step(model, mesh, allreduce_buckets=BUCKET_MB,
                        accum_steps=2, wire_codec="int8:ef")


# --------------------------------------------------------------------------
# parity with the single psum (the collective is leafwise either way,
# so bucketing must be BIT-identical)
# --------------------------------------------------------------------------


def test_bucketed_step_bitidentical_to_psum():
    model, mesh, state, x, y = _setup()
    rng = jax.random.PRNGKey(1)
    ref = make_bsp_train_step(model, mesh, donate=False)
    bkt = make_bsp_train_step(model, mesh, donate=False,
                              allreduce_buckets=BUCKET_MB)
    s1, m1 = ref(state, x, y, rng)
    s2, m2 = bkt(state, x, y, rng)
    assert float(m1["loss"]) == float(m2["loss"])
    assert _params_equal(s1, s2)
    # a second step from the bucketed state stays on the trajectory
    s1b, _ = ref(s1, x, y, jax.random.PRNGKey(2))
    s2b, _ = bkt(s2, x, y, jax.random.PRNGKey(2))
    assert _params_equal(s1b, s2b)


def test_bucketed_fused_update_bitidentical():
    """Both tentpole knobs together == the plain psum step (fp32, same
    in-graph expression chain per leaf)."""
    model, mesh, state, x, y = _setup()
    rng = jax.random.PRNGKey(1)
    ref = make_bsp_train_step(model, mesh, donate=False)
    both = make_bsp_train_step(model, mesh, donate=False,
                               allreduce_buckets=BUCKET_MB,
                               fused_update=True)
    s1, _ = ref(state, x, y, rng)
    s2, _ = both(state, x, y, rng)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_bucketed_numerics_sentinels_match_psum():
    """nm_* gauges see the post-sync grads identically under bucketing
    (grads ARE synced by the in-backward tags)."""
    model, mesh, state, x, y = _setup()
    rng = jax.random.PRNGKey(1)
    ref = make_bsp_train_step(model, mesh, donate=False, numerics=True)
    bkt = make_bsp_train_step(model, mesh, donate=False, numerics=True,
                              allreduce_buckets=BUCKET_MB)
    _, m1 = ref(state, x, y, rng)
    _, m2 = bkt(state, x, y, rng)
    for k in ("nm_grad_norm", "nm_update_norm", "nm_param_norm",
              "nm_nonfinite"):
        np.testing.assert_allclose(float(m1[k]), float(m2[k]), rtol=1e-6)


# --------------------------------------------------------------------------
# codec composition
# --------------------------------------------------------------------------


def test_bucketed_bf16_codec_matches_codec_psum():
    """Stateless codec in the backward tags == codec_psum_mean's
    value-space compression (leafwise either way)."""
    model, mesh, state, x, y = _setup()
    rng = jax.random.PRNGKey(1)
    ref = make_bsp_train_step(model, mesh, donate=False, wire_codec="bf16")
    bkt = make_bsp_train_step(model, mesh, donate=False, wire_codec="bf16",
                              allreduce_buckets=BUCKET_MB)
    s1, m1 = ref(state, x, y, rng)
    s2, m2 = bkt(state, x, y, rng)
    assert float(m1["loss"]) == float(m2["loss"])
    assert _params_equal(s1, s2)


def test_bucketed_int8_ef_matches_codec_psum():
    """:ef buckets sync post-backward with per-bucket residuals — the
    SAME leafwise algebra as the unbucketed stateful strategy, so
    params AND residuals stay bit-identical."""
    from theanompi_tpu.parallel.bsp import BSPEngine

    model, mesh, _, x, y = _setup()
    rng = jax.random.PRNGKey(1)
    ref_eng = BSPEngine(model, mesh, wire_codec="int8:ef")
    bkt_eng = BSPEngine(model, mesh, wire_codec="int8:ef",
                        allreduce_buckets=BUCKET_MB)
    s_ref = ref_eng.init_state(jax.random.PRNGKey(0))
    s_bkt = bkt_eng.init_state(jax.random.PRNGKey(0))
    for i in range(3):
        k = jax.random.PRNGKey(10 + i)
        s_ref, _ = ref_eng.train_step(s_ref, x, y, k)
        s_bkt, _ = bkt_eng.train_step(s_bkt, x, y, k)
    assert _params_equal(s_ref, s_bkt)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref.ef),
                    jax.tree_util.tree_leaves(s_bkt.ef)):
        assert bool(jnp.all(a == b))


# --------------------------------------------------------------------------
# 2-device convergence (the acceptance criterion's CPU-runnable proof)
# --------------------------------------------------------------------------


def test_two_device_bucketed_convergence():
    model = TinyCNN(TinyCNN.default_recipe().replace(batch_size=8))
    mesh = make_mesh(2)
    step = make_bsp_train_step(model, mesh, allreduce_buckets=BUCKET_MB,
                               fused_update=True)
    state = init_train_state(model, jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = put_global_batch(
        mesh, jnp.asarray(r.randn(8, *model.recipe.input_shape),
                          jnp.float32))
    y = put_global_batch(mesh, jnp.asarray(
        r.randint(0, model.recipe.num_classes, 8), jnp.int32))
    losses = []
    for i in range(12):
        state, m = step(state, x, y, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert int(state.step.addressable_shards[0].data.reshape(-1)[0]) == 12
    # fixed batch: the bucketed+fused trajectory must actually descend
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) * 0.8


# --------------------------------------------------------------------------
# traffic model stays truthful (the live SPMD101 contract)
# --------------------------------------------------------------------------


def test_traffic_model_reports_bucket_geometry():
    from theanompi_tpu.parallel.bsp import BSPEngine

    model, mesh, _, _, _ = _setup()
    plain = BSPEngine(model, mesh)
    bkt = BSPEngine(model, mesh, allreduce_buckets=BUCKET_MB)
    state = bkt.init_state(jax.random.PRNGKey(0))
    t_plain = plain.traffic_model(state)
    t_bkt = bkt.traffic_model(state)
    # same bytes on the wire — bucketing chunks, it does not compress
    assert t_bkt.bytes_per_step == t_plain.bytes_per_step
    assert t_bkt.raw_bytes_per_step == t_plain.raw_bytes_per_step
    nb = t_bkt.detail["n_buckets"]
    assert nb >= 2
    assert t_bkt.detail["overlap_frac"] == pytest.approx(
        bucket_overlap_frac(nb))
    assert "n_buckets" not in t_plain.detail



def test_traced_wire_bytes_match_declared_under_buckets():
    """The live SPMD101 cross-check (obs/attribution.traced_wire_bytes)
    on the bucketed step: B per-bucket psums must sum to the declared
    allreduce volume."""
    from theanompi_tpu.obs.attribution import (
        crosscheck_traffic,
        traced_wire_bytes,
    )
    from theanompi_tpu.parallel.bsp import BSPEngine

    model, mesh, _, x, y = _setup()
    eng = BSPEngine(model, mesh, allreduce_buckets=BUCKET_MB)
    state = jax.eval_shape(eng.init_state, jax.random.PRNGKey(0))
    traced = traced_wire_bytes(
        [(eng._steps[False], (state, x, y, jax.random.PRNGKey(0)), 1.0)]
    )
    declared = float(eng.traffic_model(state).raw_bytes_per_step_amortized)
    assert crosscheck_traffic(traced, declared)["ok"]
