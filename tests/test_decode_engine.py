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


# -- ISSUE 38: one packed operand for the decode step ----------------------
def _mistral4_model():
    from theanompi_tpu.models.mistral4 import Mistral4LM

    return Mistral4LM()


def _minicpm_sala_model():
    from theanompi_tpu.models.minicpm_sala import MiniCPMSALA

    return MiniCPMSALA()


# the cache kinds the suite's tiny engines cover: per-head K and V (not donated), the latent kind and pages
# beside state held a slot (both donated)
KINDS = {"kv": tiny_model, "latent": _mistral4_model, "pages_and_state": _minicpm_sala_model}


def _kind_engine(kind, **kw):
    eng = DecodeEngine(KINDS[kind](), prefill_buckets=(8, 16), kv_pages=48, page_size=8, max_seqs=4,
                       max_new_tokens=8, seed=11, **kw)
    params, state = eng.model.init(jax.random.PRNGKey(3))
    assert eng.set_params(params, state, 1)
    return eng


def _the_five_arrays(sched):
    """``step_arrays`` as it stood before ISSUE 38: five host arrays."""
    S = sched.cache.max_seqs
    seq_lens, last = np.zeros((S,), np.int32), np.zeros((S,), np.int32)
    active, temp = np.zeros((S,), bool), np.zeros((S,), np.float32)
    for slot, seq in sched.running.items():
        seq_lens[slot], last[slot], active[slot], temp[slot] = seq.pos, seq.last_token, True, seq.temperature
    return sched.cache.page_tables.copy(), seq_lens, last, active, temp


def test_the_decode_program_hands_decode_step_what_step_arrays_packed():
    """The packed buffer cut apart INSIDE the program: ``decode_step`` gets, bit
    for bit and dtype for dtype, the five arrays the scheduler made before and
    the key folded from the counter: a batch with an inactive slot and a freed
    one, temperatures that are not 0, ``it`` > 0."""
    from theanompi_tpu.serve.decode.scheduler import DecodeSequence

    eng = _kind_engine("kv")
    seen = lambda params, k_pool, v_pool, *given, page_size: (given[:5], given[5], k_pool, v_pool)  # noqa: E731
    eng.model.decode_step = seen  # before the one trace: the program hands out what the model was given
    seqs = [DecodeSequence(prompt(*range(1, n + 1)), max_new_tokens=5, temperature=t)
            for n, t in ((3, 0.0), (9, 0.7), (1, 1.3))]
    for seq in seqs:
        eng._sched.add(seq)
    assert len(eng._sched.admit(0.0)[0]) == 3
    seqs[1].generated += [4, 17]
    eng._sched.remove(seqs[0].slot, "finished")  # a freed slot's row is zeros again
    want = _the_five_arrays(eng._sched)
    assert want[3].tolist().count(True) == 2 and set(want[4].tolist()) == {0.0, np.float32(0.7), np.float32(1.3)}
    c = eng._cache
    for it in (7, 2 ** 31 - 1):
        packed = eng._sched.step_arrays(it)
        assert packed.dtype == np.int32 and packed.shape == (4 * (c.max_pages_per_seq + 4) + 1,)
        assert packed is eng._sched.step_arrays(it)  # kept and written in place
        got, key, _, _ = eng._decode(eng._served.params, c.k_pool, c.v_pool, packed)
        for g, w in zip(got, want):
            g = np.asarray(g)
            assert g.dtype == w.dtype and g.shape == w.shape and g.tobytes() == w.tobytes()
        assert np.array_equal(np.asarray(key), np.asarray(jax.random.fold_in(jax.random.PRNGKey(11), np.int32(it))))
    assert eng.compile_count == 1


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_short_generation_is_token_for_token_the_five_operand_programs(kind):
    """The whole loop over the packed operand against the same loop over the
    program as it stood (five operands and the counter, made on the host from
    the same buffer): the same tokens, sampled ones among them, in every cache
    kind. Every request is queued before the start, so both loops admit all
    of them in iteration 0 and the counter (the sampling key) runs alike."""
    import jax.numpy as jnp

    tokens = {}
    for form in ("packed", "five"):
        eng = _kind_engine(kind)
        if form == "five":
            model = eng.model

            def five(params, k_pool, v_pool, tables, seq_lens, last, active, temp, it):
                key = jax.random.fold_in(jax.random.PRNGKey(11), it)
                return model.decode_step(params, k_pool, v_pool, tables, seq_lens, last, active, temp, key,
                                         page_size=8)

            program = jax.jit(five, donate_argnums=(1, 2) if eng._donate else ())

            def decode(params, k_pool, v_pool, packed):
                tables, seq_lens, last, active, temp, it = eng._sched.split_step(packed)
                return program(params, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(seq_lens),
                               jnp.asarray(last), jnp.asarray(active != 0), jnp.asarray(temp.view(np.float32)),
                               np.int32(it))

            eng._decode = decode
        assert eng.warmup() == len(eng.buckets) + (form == "packed")
        futs = [eng.submit(prompt(*range(2, 2 + n)), max_new_tokens=m, temperature=t)
                for n, m, t in ((5, 8, 0.0), (1, 3, 0.9), (12, 6, 1.4))]  # three of four slots: one inactive
        eng.start()
        try:
            tokens[form] = [f.result(120).tokens.tolist() for f in futs]
        finally:
            assert eng.drain(timeout=60)
        assert eng._iterations == 8 and eng._cache.free_list.conserved()
    assert [len(t) for t in tokens["packed"]] == [8, 3, 6]
    assert tokens["packed"] == tokens["five"]


def test_the_program_count_holds_through_warm_up_and_twenty_mixed_iterations():
    eng = make_engine(max_new_tokens=6)
    set_tiny_params(eng)
    assert eng.warmup() == len(eng.buckets) + 1
    rng = np.random.RandomState(1)
    eng.start()
    try:
        while eng._iterations < 20:  # prompts over both buckets and of one token, greedy and sampled, in waves
            futs = [eng.submit(rng.randint(0, 32, size=int(rng.randint(1, 10))).astype(np.int32),
                               max_new_tokens=int(rng.randint(1, 7)), temperature=float(rng.choice([0.0, 0.8])))
                    for _ in range(6)]
            for f in futs:
                f.result(60)
    finally:
        assert eng.drain(timeout=60)
    assert eng._iterations >= 20 and eng.compile_count == len(eng.buckets) + 1


@pytest.mark.parametrize("case,want", [("empty", True), ("someone_waits", False), ("no_slot", False),
                                       ("no_page", False), ("static_batch_runs", False),
                                       ("static_batch_emptied", True)])
def test_the_scheduler_says_whether_a_submission_made_now_would_be_admitted(case, want):
    from theanompi_tpu.serve.decode.kvcache import PagedKVCache
    from theanompi_tpu.serve.decode.scheduler import DecodeScheduler, DecodeSequence

    cache = PagedKVCache(n_layers=1, page_size=4, n_pages=4, max_seqs=2, max_pages_per_seq=2,
                         k_page=(4, 8), v_page=(4, 8), dtype=np.float32)
    sched = DecodeScheduler(cache, prefill_buckets=(4,), mode="static" if case.startswith("static") else "continuous")
    for _ in range({"no_slot": 2, "no_page": 2, "static_batch_runs": 1, "static_batch_emptied": 1}.get(case, 0)):
        sched.add(DecodeSequence(prompt(1, 2, 3), max_new_tokens=5 if case == "no_page" else 1))
    sched.admit(0.0)
    if case == "no_page":  # both slots' worst case took the four pages; one slot is free again
        assert cache.pages_free == 0
        sched._free_slots.append(sched.running.popitem()[0])
    if case == "static_batch_emptied":
        sched.remove(next(iter(sched.running)), "finished")
    if case == "someone_waits":
        sched.add(DecodeSequence(prompt(1), max_new_tokens=1))
    assert sched.can_admit() is want
