"""DecodeEngine integration: lifecycle, bounded compile count under a
mixed-length request stream, hot-reload mid-generation with zero drops,
typed deadline eviction, overload admission control, KV conservation
through abort, and the Router fronting N decode replicas UNCHANGED."""

import threading
import time

import jax
import numpy as np
import pytest

from theanompi_tpu.models.lm import LMRecipe, MoELMModel, TransformerLMModel
from theanompi_tpu.serve.decode import DecodeEngine, DecodeResult
from theanompi_tpu.serve.engine import (
    DeadlineExceeded,
    EngineDead,
    EngineDraining,
    EngineOverloaded,
)
from theanompi_tpu.serve.router import Router


def tiny_model():
    return TransformerLMModel(LMRecipe(
        input_shape=(64,), num_classes=32,
        d_model=32, n_heads=2, n_layers=2, d_ff=64, attn="ring",
        dataset="lm_synthetic",
    ))


def make_engine(model=None, **kw):
    cfg = dict(prefill_buckets=(4, 8), page_size=4, kv_pages=32,
               max_seqs=4, max_new_tokens=4, record_every=5)
    cfg.update(kw)
    return DecodeEngine(model or tiny_model(), **cfg)


def set_tiny_params(engine, step=1, scale=0.0):
    params, state = engine.model.init(jax.random.PRNGKey(0))
    if scale:
        params = jax.tree_util.tree_map(lambda a: a + scale, params)
    assert engine.set_params(params, state, step)
    return params, state


def prompt(*toks):
    return np.asarray(toks, np.int32)


def test_requires_decode_surface():
    from theanompi_tpu.models.zoo import zoo_entry

    cnn_cls, _batch = zoo_entry("mlp")
    with pytest.raises(ValueError, match="does not support"):
        DecodeEngine(cnn_cls())


def test_submit_drain_lifecycle():
    eng = make_engine()
    set_tiny_params(eng)
    assert eng.warmup() == len(eng.buckets) + 1
    eng.start()
    try:
        futs = [eng.submit(prompt(1, 2, 3)),
                eng.submit(prompt(7)),
                eng.submit(prompt(4, 5, 6, 8, 9), max_new_tokens=2)]
        res = [f.result(30) for f in futs]
    finally:
        assert eng.drain(timeout=60)
    assert all(isinstance(r, DecodeResult) for r in res)
    assert [len(r.tokens) for r in res] == [4, 4, 2]
    assert all(r.step == 1 for r in res)
    assert all(0 <= t < 32 for r in res for t in r.tokens)
    st = eng.stats()
    assert st["tmpi_decode_served_total"] == 3.0
    assert st["tmpi_decode_failed_total"] == 0.0
    # the free-list must balance after a full drain
    assert eng._cache.free_list.conserved()
    assert eng._cache.pages_used == 0
    # drained: new submissions are refused
    with pytest.raises(EngineDraining):
        eng.submit(prompt(1))


def tiny_moe_model():
    """The MoE LM serves through the dense LM's paged functions and cache
    spec. A capacity no token exceeds: the full forward drops nothing."""
    return MoELMModel(LMRecipe(
        input_shape=(64,), num_classes=32, d_model=32, n_heads=2,
        n_layers=2, d_ff=64, attn="ring", dataset="lm_synthetic",
        n_experts=4, capacity_factor=4.0,
    ))


@pytest.mark.parametrize("prompt_len", [
    1,  # nothing to prefill: the first step's softmax sees its own row only
    5,  # 4 cached rows: the first decoded row lands on a page's FIRST offset
    8,  # 7 cached rows: ... on a page's LAST offset
])
@pytest.mark.parametrize("make_model", [tiny_model, tiny_moe_model],
                         ids=["dense", "moe"])
def test_served_greedy_tokens_are_the_full_forwards(make_model, prompt_len):
    """Prefill then decode through the engine's lane-dense pools
    (``[L, kv_pages + 1, page_size, H * hd]``, not donated) against the
    full-context forward, token by token, beside a second running slot."""
    from test_decode_correctness import oracle_next

    model = make_model()
    eng = make_engine(model, max_new_tokens=6)
    params, _ = set_tiny_params(eng)
    spec = model.cache_spec(eng.page_size)
    assert spec["kind"] == "kv" and spec["donate"] is False
    assert spec["k_page"] == spec["v_page"] == (4, 32)
    assert eng._cache.k_pool.shape == eng._cache.v_pool.shape == (2, 33, 4, 32)
    assert eng.warmup() == len(eng.buckets) + 1
    rng = np.random.RandomState(prompt_len)
    toks = rng.randint(0, 32, size=prompt_len).astype(np.int32)
    eng.start()
    try:
        other = eng.submit(prompt(9, 3, 6), max_new_tokens=6)
        got = eng.submit(toks, max_new_tokens=6).result(60).tokens
        other.result(60)
    finally:
        assert eng.drain(timeout=60)
    assert eng.compile_count == len(eng.buckets) + 1
    assert eng._cache.free_list.conserved()
    ctx = [int(t) for t in toks]
    for i, tok in enumerate(got):
        want = oracle_next(model.arch, params, ctx)
        assert tok == want, f"token {i}: served {tok}, full forward {want}"
        ctx.append(tok)


def test_compile_count_bounded_under_mixed_stream():
    """The acceptance bound: <= len(prefill_buckets) + 1 compiled
    programs no matter how prompt lengths / output budgets mix."""
    eng = make_engine()
    set_tiny_params(eng)
    eng.warmup()
    eng.start()
    try:
        rng = np.random.RandomState(0)
        futs = []
        for i in range(12):
            plen = int(rng.randint(1, 9))  # spans both buckets + skip
            toks = rng.randint(0, 32, size=plen).astype(np.int32)
            futs.append(eng.submit(
                toks, max_new_tokens=int(rng.randint(1, 5)),
                temperature=float(rng.choice([0.0, 0.7])),
            ))
        for f in futs:
            f.result(30)
    finally:
        eng.drain(timeout=60)
    assert eng.compile_count == len(eng.buckets) + 1


def test_hot_reload_mid_generation_zero_drops():
    """A set_params swap while generations are in flight: nothing
    drops, every future resolves, and the served step at completion is
    monotone (old or new, never backward)."""
    eng = make_engine(max_new_tokens=24, kv_pages=64)
    set_tiny_params(eng, step=1)
    eng.warmup()
    eng.start()
    try:
        futs = [eng.submit(prompt(1, 2, 3)) for _ in range(6)]
        time.sleep(0.05)  # let some tokens land under step 1
        set_tiny_params(eng, step=2, scale=0.01)
        res = [f.result(60) for f in futs]
    finally:
        eng.drain(timeout=120)
    assert eng.params_step == 2
    assert [len(r.tokens) for r in res] == [24] * 6
    assert all(r.step in (1, 2) for r in res)
    st = eng.stats()
    assert st["tmpi_decode_served_total"] == 6.0
    assert st["tmpi_decode_failed_total"] == 0.0
    assert st["tmpi_decode_rejected_total"] == 0.0
    assert eng._cache.free_list.conserved()


def test_reload_backward_step_refused():
    eng = make_engine()
    params, state = eng.model.init(jax.random.PRNGKey(0))
    assert eng.set_params(params, state, 5)
    assert not eng.set_params(params, state, 5)
    assert not eng.set_params(params, state, 3)
    assert eng.params_step == 5


def test_deadline_eviction_is_typed():
    """A deadline that passes mid-generation (or in the queue) must
    surface as DeadlineExceeded and be COUNTED as expired/evicted —
    never a silent drop — and its pages must come back."""
    eng = make_engine(max_new_tokens=40, kv_pages=64)
    set_tiny_params(eng)
    eng.warmup()
    eng.start()
    try:
        fut = eng.submit(prompt(1, 2, 3), deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            fut.result(30)
    finally:
        eng.drain(timeout=60)
    st = eng.stats()
    assert st["tmpi_decode_expired_total"] + st["tmpi_decode_evicted_total"] >= 1.0
    assert st["tmpi_decode_failed_total"] == 0.0
    assert eng._cache.free_list.conserved()
    assert eng._cache.pages_used == 0


def test_overload_rejection():
    eng = make_engine(max_queue=2)
    set_tiny_params(eng)
    # engine not started: the queue only fills
    eng.submit(prompt(1))
    eng.submit(prompt(2))
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(prompt(3))
    assert ei.value.retry_after_ms > 0
    # start and drain: the queued generations must still complete
    eng.warmup()
    eng.start()
    assert eng.drain(timeout=60)
    assert eng.stats()["tmpi_decode_served_total"] == 2.0


def test_submit_validation():
    eng = make_engine()
    set_tiny_params(eng)
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng.submit(np.zeros((2, 3), np.int32))
    with pytest.raises(ValueError, match="exceeds the largest"):
        eng.submit(np.zeros((10,), np.int32))  # max_prompt_len = 8+1
    with pytest.raises(ValueError, match="max_new_tokens"):
        make_engine(max_new_tokens=0)
    with pytest.raises(ValueError, match="cannot hold"):
        make_engine(kv_pages=1)


def test_abort_rejects_and_conserves_pages():
    eng = make_engine(max_new_tokens=48, kv_pages=64)
    set_tiny_params(eng)
    eng.warmup()
    eng.start()
    futs = [eng.submit(prompt(1, 2, 3)) for _ in range(4)]
    time.sleep(0.02)
    eng.abort()
    errors = []
    for f in futs:
        try:
            f.result(30)
        except BaseException as e:  # noqa: BLE001 — collecting outcomes
            errors.append(e)
    # abort mid-flight: everything not already finished rejects typed
    assert all(isinstance(e, EngineDead) for e in errors)
    assert eng.drain(timeout=60)
    assert not eng.alive
    assert eng._cache.free_list.conserved()
    assert eng._cache.pages_used == 0


def test_static_mode_runs_batches_to_completion():
    """mode='static' admits only into an empty batch. It must still
    serve everything correctly."""
    eng = make_engine(mode="static", max_seqs=2)
    set_tiny_params(eng)
    eng.warmup()
    eng.start()
    try:
        futs = [eng.submit(prompt(i + 1)) for i in range(5)]
        res = [f.result(60) for f in futs]
    finally:
        eng.drain(timeout=60)
    assert all(len(r.tokens) == 4 for r in res)
    assert eng.stats()["tmpi_decode_served_total"] == 5.0


def test_continuous_needs_fewer_iterations_than_static():
    """The structural claim of continuous batching, as a count: the same
    mixed-length requests, all queued before the engine starts (so the
    schedule is the scheduler's alone, no clock in it), take strictly
    fewer decode iterations when a freed slot is refilled at once than
    when a batch runs to its longest member. ``mode="static"`` is this
    count's reference: it fails if static is made to admit mid-batch."""
    budgets = [12, 1, 12, 1, 1, 1, 1, 1]
    iterations = {}
    for mode in ("continuous", "static"):
        eng = make_engine(mode=mode, max_seqs=2, max_new_tokens=12)
        set_tiny_params(eng)
        eng.warmup()
        futs = [eng.submit(prompt(i + 1, i + 2), max_new_tokens=n)
                for i, n in enumerate(budgets)]
        eng.start()
        try:
            res = [f.result(60) for f in futs]
        finally:
            eng.drain(timeout=60)
        assert [len(r.tokens) for r in res] == budgets
        iterations[mode] = eng.stats()["tmpi_decode_iterations_total"]
    assert 0 < iterations["continuous"] < iterations["static"], iterations


def test_router_fronts_decode_replicas_unchanged(tmp_path):
    """The tentpole composition claim: serve/router.py fronts N
    DecodeEngines with NO router changes — same factory contract, same
    submit/result surface, step floor monotone, zero drops."""
    model = tiny_model()
    params, state = model.init(jax.random.PRNGKey(0))

    def factory(rid):
        eng = make_engine(
            model, replica_id=rid, obs_dir=str(tmp_path),
            sink_name=f"decode_r{rid}.jsonl",
        )
        eng.set_params(params, state, 1)
        eng.warmup()
        eng.start()
        return eng

    router = Router(factory, 2, obs_dir=str(tmp_path), seed=0,
                    health_interval=0.05)
    router.start()
    try:
        futs = [router.submit(prompt(1, 2, int(i % 5) + 3))
                for i in range(8)]
        res = [f.result(60) for f in futs]
    finally:
        assert router.drain(timeout=120)
    assert all(isinstance(r, DecodeResult) for r in res)
    assert all(len(r.tokens) == 4 and r.step == 1 for r in res)
    st = router.stats()
    assert st["tmpi_router_served_total"] == 8.0
    assert st["tmpi_router_dropped_total"] == 0.0
    # both members' KV accounting balances after the fleet drain
    for rep in router.replicas:
        assert rep.engine._cache.free_list.conserved()
        assert rep.engine._cache.pages_used == 0


def test_concurrent_submitters():
    """Many client threads against one engine: every generation lands,
    tokens counters reconcile with per-request budgets."""
    eng = make_engine(max_queue=64)
    set_tiny_params(eng)
    eng.warmup()
    eng.start()
    results, errs = [], []
    lock = threading.Lock()

    def client(seed):
        rng = np.random.RandomState(seed)
        for _ in range(3):
            toks = rng.randint(0, 32, size=int(rng.randint(1, 6)))
            try:
                r = eng.generate(toks.astype(np.int32), timeout=60)
                with lock:
                    results.append(r)
            except BaseException as e:  # noqa: BLE001
                with lock:
                    errs.append(e)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.drain(timeout=60)
    assert not errs
    assert len(results) == 12
    assert eng.stats()["tmpi_decode_tokens_total"] == sum(
        len(r.tokens) for r in results
    )


def test_http_frontend_single_decode_engine():
    """The stdlib HTTP front over ONE DecodeEngine (no router): /infer
    round-trips tokens + served step, /healthz answers 200 via the
    shared ``queue_depth`` property (the regression: the handler used
    to read the ServeEngine-only ``tmpi_serve_queue_depth`` stats key
    and crashed the connection), /metrics exposes tmpi_decode_*."""
    import http.client
    import json

    from theanompi_tpu.serve.frontend import serve_http

    eng = make_engine()
    set_tiny_params(eng, step=3)
    eng.warmup()
    eng.start()
    httpd = serve_http(eng, host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        assert resp.status == 200
        assert health == {"params_step": 3, "queue_depth": 0,
                          "draining": False}
        conn.request("POST", "/infer",
                     body=json.dumps({"input": [3, 7, 1, 4, 9]}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["step"] == 3
        assert len(body["tokens"]) == 4  # max_new_tokens
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert b"tmpi_decode_tokens_total" in resp.read()
    finally:
        httpd.shutdown()
        httpd.server_close()
        eng.drain(timeout=30)
