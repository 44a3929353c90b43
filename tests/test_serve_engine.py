"""Serving engine (serve/engine.py): bucketed micro-batching compiles
once per bucket, padding never changes logits, deadlines/overload are
rejected not served, drain finishes the backlog, and the HTTP front
speaks the engine's admission semantics."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax

from tinymodel import TinyCNN

from theanompi_tpu.models.zoo import infer_fn
from theanompi_tpu.serve.engine import (
    DeadlineExceeded,
    EngineDraining,
    EngineOverloaded,
    ServeEngine,
)
from theanompi_tpu.train import init_train_state


def tiny_model():
    return TinyCNN(
        TinyCNN.default_recipe().replace(
            input_shape=(8, 8, 3), batch_size=8
        )
    )


@pytest.fixture
def served_engine():
    """Started engine over a TinyCNN with buckets (1, 4, 8)."""
    model = tiny_model()
    engine = ServeEngine(model, buckets=(1, 4, 8), max_queue=64)
    state = init_train_state(model, jax.random.PRNGKey(0))
    engine.set_params(state.params, state.model_state, 1)
    engine.warmup()
    yield engine
    engine.drain(timeout=10.0)


def test_warmup_compiles_one_program_per_bucket(served_engine):
    assert served_engine.compile_count == 3
    # re-warm is free: every bucket shape is already compiled
    assert served_engine.warmup() == 3


def test_mixed_stream_compiles_at_most_len_buckets(served_engine):
    """The ISSUE acceptance: a mixed-size request stream — bursts that
    land on every bucket — never compiles a program beyond the warmed
    set (the compile-counter fixture is the engine's own trace count,
    incremented exactly once per compiled input signature)."""
    engine = served_engine
    engine.start()
    r = np.random.RandomState(0)
    futs = []
    for burst in (1, 3, 5, 13, 2, 8, 1):
        futs += [engine.submit(r.randn(8, 8, 3)) for _ in range(burst)]
        time.sleep(0.01)  # vary arrival so batch sizes vary
    results = [f.result(20.0) for f in futs]
    assert len(results) == 33
    assert all(res.step == 1 for res in results)
    assert engine.compile_count <= len(engine.buckets)
    # coalescing actually happened: fewer batches than requests
    assert engine._batches < len(results)


def test_padding_rows_cannot_perturb_real_rows(served_engine):
    """Eval-mode forwards are row-independent, so what shares a padded
    micro-batch with a request cannot change its logits: the served row
    is BIT-identical to the same bucket-8 program run with every other
    row replaced by noise. Against an unbatched (bucket-1) forward — a
    different compiled program, whose matmuls may accumulate in another
    order — it agrees to float32 rounding (7e-7 observed on XLA:CPU),
    not to the bit."""
    engine = served_engine
    model = engine.model
    state = init_train_state(tiny_model(), jax.random.PRNGKey(0))
    r = np.random.RandomState(1)
    xs = [r.randn(8, 8, 3).astype(np.float32) for _ in range(5)]
    # submit BEFORE start: the batcher coalesces all 5 into one
    # micro-batch, padded 5 -> bucket 8
    futs = [engine.submit(x) for x in xs]
    engine.start()
    got = [f.result(20.0).logits for f in futs]
    assert engine._batches == 1
    ref_fwd = jax.jit(infer_fn(model))
    for i, (x, out) in enumerate(zip(xs, got)):
        crowd = r.randn(8, 8, 8, 3).astype(np.float32)
        crowd[i] = x
        same_program = np.asarray(
            ref_fwd(state.params, state.model_state, crowd))[i]
        np.testing.assert_array_equal(out, same_program)
        unbatched = np.asarray(
            ref_fwd(state.params, state.model_state, x[None]))[0]
        np.testing.assert_allclose(out, unbatched, rtol=1e-5, atol=1e-5)


def test_expired_deadline_rejected_not_served():
    model = tiny_model()
    engine = ServeEngine(model, buckets=(1, 4), max_queue=16)
    state = init_train_state(model, jax.random.PRNGKey(0))
    engine.set_params(state.params, state.model_state, 1)
    engine.warmup()
    r = np.random.RandomState(0)
    # queued before the batcher exists; its 1 ms deadline is long gone
    # by the time a batch slot opens
    doomed = engine.submit(r.randn(8, 8, 3), deadline_ms=1.0)
    time.sleep(0.05)
    fine = engine.submit(r.randn(8, 8, 3))  # no deadline
    engine.start()
    with pytest.raises(DeadlineExceeded):
        doomed.result(10.0)
    assert fine.result(10.0).step == 1
    stats = engine.stats()
    assert stats["tmpi_serve_expired_total"] == 1.0
    assert stats["tmpi_serve_served_total"] == 1.0
    engine.drain(timeout=10.0)


def test_overload_rejects_with_retry_after():
    model = tiny_model()
    engine = ServeEngine(model, buckets=(1,), max_queue=2)
    r = np.random.RandomState(0)
    engine.submit(r.randn(8, 8, 3))
    engine.submit(r.randn(8, 8, 3))
    with pytest.raises(EngineOverloaded) as ei:
        engine.submit(r.randn(8, 8, 3))
    assert ei.value.retry_after_ms > 0
    assert engine.stats()["tmpi_serve_rejected_total"] == 1.0


def test_drain_serves_backlog_then_rejects_new(served_engine):
    engine = served_engine
    r = np.random.RandomState(0)
    futs = [engine.submit(r.randn(8, 8, 3)) for _ in range(11)]
    engine.start()
    assert engine.drain(timeout=20.0)
    # every queued request was served, none dropped
    assert all(f.result(0.1).step == 1 for f in futs)
    with pytest.raises(EngineDraining):
        engine.submit(r.randn(8, 8, 3))


def test_submit_validates_shape(served_engine):
    with pytest.raises(ValueError, match="request shape"):
        served_engine.submit(np.zeros((4, 4, 3)))


def test_warmup_without_params_raises():
    engine = ServeEngine(tiny_model(), buckets=(1,))
    with pytest.raises(RuntimeError, match="load_initial"):
        engine.warmup()


def test_serve_records_schema_valid(tmp_path):
    """The serve JSONL stream validates against the documented schema
    (kind=serve; tmpi_serve_-prefixed numeric map)."""
    from theanompi_tpu.tools.check_obs_schema import check_file

    model = tiny_model()
    engine = ServeEngine(
        model, buckets=(1, 4), max_queue=16,
        obs_dir=str(tmp_path), record_every=2,
    )
    state = init_train_state(model, jax.random.PRNGKey(0))
    engine.set_params(state.params, state.model_state, 1)
    engine.warmup()
    engine.start()
    r = np.random.RandomState(0)
    for _ in range(6):
        engine.infer(r.randn(8, 8, 3), timeout=20.0)
    engine.drain(timeout=10.0)
    path = tmp_path / "serve.jsonl"
    assert path.exists()
    assert check_file(str(path)) == []
    kinds = [json.loads(l)["kind"] for l in path.read_text().splitlines()]
    assert "serve" in kinds


def test_http_frontend_infer_healthz_metrics(served_engine):
    """The stdlib HTTP front: /infer round-trips logits + served step,
    /healthz reports the engine, /metrics exposes tmpi_serve_*."""
    from theanompi_tpu.serve.frontend import serve_http

    engine = served_engine
    engine.start()
    httpd = serve_http(engine, host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=20)
        x = np.random.RandomState(0).randn(8, 8, 3).tolist()
        conn.request("POST", "/infer", body=json.dumps({"input": x}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["step"] == 1
        assert len(body["logits"]) == 10  # num_classes
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        assert resp.status == 200 and health["params_step"] == 1
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert b"tmpi_serve_requests_total" in resp.read()
        # bad shape -> 400, not a hung socket
        conn.request("POST", "/infer",
                     body=json.dumps({"input": [[1.0]]}))
        assert conn.getresponse().status == 400
    finally:
        httpd.shutdown()
        httpd.server_close()
