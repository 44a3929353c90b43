"""Numerics flight recorder (ISSUE 3), sentinel half: in-graph helpers,
engine ``numerics_model()`` declarations, EWMA/NaN anomaly detection,
and the driver-level invariants — recorder JSONL rows for healthy steps
stay bit-identical to a numerics-off run (the sentinels are EXTRA
outputs of the same program, split out at drain time), and the
heartbeat carries the dispatch-pipeline liveness fields."""

import json
import math
import os

import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.obs.numerics import (
    AnomalyDetector,
    global_norm,
    nonfinite_count,
    split_numerics,
)
from theanompi_tpu.tools.check_obs_schema import check_file

_TINY = dict(
    recipe_overrides={
        "batch_size": 32,
        "input_shape": (16, 16, 3),
        "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]},
    },
    dataset="synthetic",
    dataset_kwargs={"n_train": 64, "n_val": 32, "image_shape": (16, 16, 3)},
    print_freq=0,
)


# -- in-graph helpers -------------------------------------------------------

def test_global_norm_and_nonfinite_count():
    import jax.numpy as jnp

    tree = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.zeros((2, 2))}
    assert float(global_norm(tree)) == pytest.approx(5.0)
    assert float(nonfinite_count(tree)) == 0.0
    bad = {"a": jnp.asarray([jnp.nan, 1.0, jnp.inf]), "b": jnp.ones(3)}
    assert float(nonfinite_count(bad)) == 2.0
    assert float(global_norm({})) == 0.0


def test_split_numerics_strips_prefix_only():
    m = {"loss": 1.0, "lr": 0.1, "nm_grad_norm": 2.0, "nm_nonfinite": 0.0}
    plain, nm = split_numerics(m)
    assert plain == {"loss": 1.0, "lr": 0.1}
    assert nm == {"nm_grad_norm": 2.0, "nm_nonfinite": 0.0}
    clean = {"loss": 1.0}
    plain2, nm2 = split_numerics(clean)
    assert plain2 is clean and nm2 == {}  # zero-copy on the hot path


# -- host-side detection ----------------------------------------------------

def test_detector_warmup_swallows_early_swings():
    # the first observations legitimately swing orders of magnitude
    # (fresh init, LR warmup): no spike may fire inside the warmup
    d = AnomalyDetector(spike_factor=10.0, warmup=4)
    assert d.observe(0, {}, {"nm_grad_norm": 100.0}) == []
    assert d.observe(1, {}, {"nm_grad_norm": 1.0}) == []


def test_detector_spike_after_warmup():
    d = AnomalyDetector(spike_factor=10.0, warmup=4)
    for s in range(8):
        assert d.observe(s, {}, {"nm_grad_norm": 1.0}) == []
    fired = d.observe(8, {}, {"nm_grad_norm": 50.0})
    assert len(fired) == 1
    a = fired[0]
    assert a["metric"] == "nm_grad_norm" and a["reason"] == "spike"
    assert a["step"] == 8 and a["value"] == 50.0


def test_detector_nonfinite_triggers():
    d = AnomalyDetector()
    fired = d.observe(3, {"loss": float("nan")}, {"nm_nonfinite": 7.0})
    reasons = {a["reason"] for a in fired}
    assert reasons == {"nonfinite", "nonfinite_grads"}
    # non-finite values never carry a numeric `value` (JSON-safe)
    nonf = [a for a in fired if a["reason"] == "nonfinite"][0]
    assert "value" not in nonf and nonf["value_repr"] == "nan"


def test_detector_rebaselines_after_spike():
    d = AnomalyDetector(spike_factor=10.0, warmup=2, ewma_alpha=1.0)
    for s in range(4):
        d.observe(s, {}, {"nm_grad_norm": 1.0})
    assert d.observe(4, {}, {"nm_grad_norm": 20.0})  # fires
    # alpha=1.0: EWMA jumped to 20 — the new regime is the baseline
    assert d.observe(5, {}, {"nm_grad_norm": 20.0}) == []


def test_sharded_global_norm_spec_aware(mesh8):
    """The ND-engine helper: sharded leaves psum over their sharded
    axes only; replicated leaves must NOT be multiplied by the mesh
    size. Checked against the dense norm of the same global tree."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.obs.numerics import (
        sharded_global_norm,
        sharded_nonfinite_count,
    )

    tree = {"sharded": jnp.arange(16.0), "repl": jnp.asarray([3.0, 4.0])}
    specs = {"sharded": P("data"), "repl": P()}

    def f(t):
        return (sharded_global_norm(t, specs),
                sharded_nonfinite_count(t, specs))

    norm, nonf = jax.jit(jax.shard_map(
        f, mesh=mesh8, in_specs=(specs,), out_specs=(P(), P()),
        check_vma=False,
    ))(tree)
    dense = float(jnp.sqrt(jnp.sum(jnp.arange(16.0) ** 2) + 25.0))
    assert float(norm) == pytest.approx(dense, rel=1e-6)
    assert float(nonf) == 0.0


# -- engine declarations + in-graph sentinels -------------------------------

def test_every_engine_declares_numerics_model():
    from theanompi_tpu.parallel.bsp import BSPEngine
    from theanompi_tpu.parallel.easgd import EASGDEngine
    from theanompi_tpu.parallel.gosgd import GOSGDEngine
    from theanompi_tpu.parallel.nd import NDEngine
    from theanompi_tpu.parallel.zero import ZeroEngine

    for eng in (BSPEngine, EASGDEngine, GOSGDEngine, NDEngine, ZeroEngine):
        assert callable(getattr(eng, "numerics_model", None)), eng


def _tiny_model(batch=32):
    return TinyCNN(
        TinyCNN.default_recipe().replace(
            batch_size=batch, input_shape=(16, 16, 3),
        )
    )


def test_bsp_in_graph_sentinels(mesh8):
    import jax

    from theanompi_tpu.parallel.bsp import BSPEngine
    from theanompi_tpu.parallel.mesh import put_global_batch

    eng = BSPEngine(_tiny_model(), mesh8)
    state = eng.init_state(jax.random.PRNGKey(0))
    # host COPY before the step (np.array, not np.asarray: on the CPU
    # backend asarray can alias the device buffer, which the donated
    # step then overwrites in place)
    p0 = jax.tree_util.tree_map(lambda l: np.array(l), state.params)
    r = np.random.RandomState(0)
    x = put_global_batch(mesh8, r.randn(32, 16, 16, 3).astype(np.float32))
    y = put_global_batch(mesh8, r.randint(0, 10, 32).astype(np.int32))
    new_state, m = eng.train_step(state, x, y, jax.random.PRNGKey(1),
                                  numerics=True)
    for k in ("nm_grad_norm", "nm_update_norm", "nm_param_norm",
              "nm_nonfinite"):
        assert k in m, k
        assert math.isfinite(float(m[k]))
    assert float(m["nm_nonfinite"]) == 0.0
    assert float(m["nm_grad_norm"]) > 0.0
    # update_norm is the norm of the applied param delta (SGD: checkable
    # from the states themselves)
    delta_sq = sum(
        float(np.sum((np.asarray(a, np.float32) - b.astype(np.float32)) ** 2))
        for a, b in zip(jax.tree_util.tree_leaves(new_state.params),
                        jax.tree_util.tree_leaves(p0))
    )
    assert float(m["nm_update_norm"]) == pytest.approx(
        math.sqrt(delta_sq), rel=1e-4
    )
    nm = eng.numerics_model(state)
    assert nm.rule == "bsp" and nm.divergence is None


def test_easgd_divergence_gauge(mesh8):
    import jax

    from theanompi_tpu.parallel.easgd import EASGDEngine
    from theanompi_tpu.parallel.mesh import put_global_batch

    eng = EASGDEngine(_tiny_model(batch=8), mesh8, avg_freq=2)
    state = eng.init_state(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    # per-worker batches: global = 8 workers x 8
    x = put_global_batch(mesh8, r.randn(64, 16, 16, 3).astype(np.float32))
    y = put_global_batch(mesh8, r.randint(0, 10, 64).astype(np.int32))
    _, m = eng.train_step(state, x, y, jax.random.PRNGKey(1), numerics=True)
    # after one LOCAL step (no exchange yet) workers have left the
    # center: the gauge must read a positive finite distance
    assert "nm_divergence" in m
    div = float(m["nm_divergence"])
    assert math.isfinite(div) and div > 0.0
    nm = eng.numerics_model(state)
    assert nm.divergence == "center_worker_l2"


def test_easgd_one_worker_nan_counts_whole(mesh8):
    """Per-worker sentinel aggregation: ONE worker's NaN grads must
    drain as a psummed count (>= 1), never as the fractional 1/n a
    blanket pmean would report."""
    import jax

    from theanompi_tpu.parallel.easgd import EASGDEngine
    from theanompi_tpu.parallel.mesh import put_global_batch

    eng = EASGDEngine(_tiny_model(batch=8), mesh8, avg_freq=2)
    state = eng.init_state(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = r.randn(64, 16, 16, 3).astype(np.float32)
    x[:8] = np.nan  # worker 0's shard only
    xg = put_global_batch(mesh8, x)
    yg = put_global_batch(mesh8, r.randint(0, 10, 64).astype(np.int32))
    _, m = eng.train_step(state, xg, yg, jax.random.PRNGKey(1),
                          numerics=True)
    count = float(m["nm_nonfinite"])
    assert count >= 1.0
    assert count == pytest.approx(round(count))  # a COUNT, not a mean


def test_gosgd_divergence_gauge(mesh8):
    import jax

    from theanompi_tpu.parallel.gosgd import GOSGDEngine
    from theanompi_tpu.parallel.mesh import put_global_batch

    eng = GOSGDEngine(_tiny_model(batch=8), mesh8, p_push=1.0)
    state = eng.init_state(jax.random.PRNGKey(0))
    r = np.random.RandomState(0)
    x = put_global_batch(mesh8, r.randn(64, 16, 16, 3).astype(np.float32))
    y = put_global_batch(mesh8, r.randint(0, 10, 64).astype(np.int32))
    _, m = eng.train_step(state, x, y, jax.random.PRNGKey(1), numerics=True)
    # replicas see different shards, so post-step disagreement > 0
    assert "nm_divergence" in m
    div = float(m["nm_divergence"])
    assert math.isfinite(div) and div > 0.0
    nm = eng.numerics_model(state)
    assert nm.divergence == "replica_disagreement"
    assert nm.detail["extra_bytes_per_numerics_step"] > 0


# -- driver-level invariants ------------------------------------------------

def _rows(save_dir, name="run"):
    rows = []
    with open(os.path.join(save_dir, f"{name}.jsonl")) as f:
        for line in f:
            r = json.loads(line)
            r.pop("images_per_sec", None)
            if r.get("kind") == "epoch":
                r.pop("seconds", None)
            rows.append(r)
    assert rows
    return rows


def test_healthy_rows_bit_identical_numerics_on_off(tmp_path):
    """The acceptance invariant: sentinels are extra outputs split out
    at drain time — the recorder stream must not change by a bit, at
    freq 1 (every step numerics) and freq 2 (alternating programs)."""
    def run(tag, nfreq):
        d = str(tmp_path / tag)
        run_training(rule="bsp", model_cls=TinyCNN, devices=8, n_epochs=2,
                     save_dir=d, run_name="run", dispatch_depth=4,
                     numerics_freq=nfreq, **_TINY)
        return _rows(d)

    base = run("off", 0)
    assert run("nf1", 1) == base
    assert run("nf2", 2) == base
    assert all(not any(k.startswith("nm_") for k in r) for r in base)


def test_numerics_telemetry_outputs(tmp_path):
    obs = tmp_path / "obs"
    summary = run_training(
        rule="bsp", model_cls=TinyCNN, devices=8, n_epochs=2,
        save_dir=str(tmp_path), obs_dir=str(obs), numerics_freq=1,
        metrics_snapshot_freq=1, **_TINY,
    )
    assert summary["steps"] == 4
    assert summary["anomalies"] == 0
    # numerics JSONL: one sentinel row per step, schema-valid
    nm_path = obs / "numerics_rank0.jsonl"
    rows = [json.loads(l) for l in nm_path.read_text().splitlines()]
    assert [r["step"] for r in rows if r["kind"] == "numerics"] == [1, 2, 3, 4]
    assert all("nm_grad_norm" in r["metrics"] for r in rows)
    assert check_file(str(nm_path)) == []
    # sentinel gauges + declaration gauges in the metrics snapshots
    snaps = [json.loads(l)
             for l in (obs / "metrics.jsonl").read_text().splitlines()]
    m = snaps[-1]["metrics"]
    assert "tmpi_nm_grad_norm" in m and "tmpi_nm_param_norm" in m
    assert m["tmpi_numerics_freq"] == 1
    assert m["tmpi_numerics_has_divergence"] == 0.0  # bsp
    # heartbeat gained the dispatch liveness split
    hb = json.loads((obs / "heartbeat_rank0.json").read_text())
    assert hb["dispatch_in_flight"] == 0  # drained at close
    assert hb["last_drained_step"] == 4
    assert check_file(str(obs / "heartbeat_rank0.json")) == []
    # healthy run: no anomaly dump
    assert not (obs / "anomaly_rank0").exists()


def test_numerics_freq_gates_cadence(tmp_path):
    obs = tmp_path / "obs"
    run_training(
        rule="bsp", model_cls=TinyCNN, devices=8, n_epochs=2,
        obs_dir=str(obs), numerics_freq=2, **_TINY,
    )
    rows = [json.loads(l)
            for l in (obs / "numerics_rank0.jsonl").read_text().splitlines()]
    # 4 steps, freq 2: sentinel rows on steps 2 and 4 only
    assert [r["step"] for r in rows if r["kind"] == "numerics"] == [2, 4]


def test_zero_numerics_sentinels(tmp_path):
    obs = tmp_path / "obs"
    summary = run_training(
        rule="bsp", model_cls=TinyCNN, devices=8, zero=1, n_epochs=1,
        obs_dir=str(obs), numerics_freq=1, **_TINY,
    )
    assert summary["steps"] == 2 and summary["anomalies"] == 0
    rows = [json.loads(l)
            for l in (obs / "numerics_rank0.jsonl").read_text().splitlines()]
    nm = [r for r in rows if r["kind"] == "numerics"]
    assert len(nm) == 2
    for r in nm:
        assert r["metrics"]["nm_nonfinite"] == 0.0
        assert r["metrics"]["nm_grad_norm"] > 0.0
    assert check_file(str(obs / "numerics_rank0.jsonl")) == []
