"""Replica-group serving router (serve/router.py, ISSUE 19 tentpole):
health-checked least-loaded routing with bounded failover, the
supervisor restarting crashed replicas under decorrelated-jitter
backoff, served-step monotonicity across central hot-reload, the
fleet-level overload/healthz semantics the HTTP front exposes, and the
satellite-2 requirement: the router's health-transition path run under
a seeded ``StressHarness`` scenario."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

import jax

from tinymodel import TinyCNN

from theanompi_tpu.serve.engine import EngineDraining, ServeEngine
from theanompi_tpu.serve.router import (
    Router,
    RouterOverloaded,
    RouterUnavailable,
)
from theanompi_tpu.tools.analyze.stress import (
    Scenario,
    StressHarness,
    inject_delay,
)
from theanompi_tpu.tools.check_obs_schema import check_file
from theanompi_tpu.train import init_train_state

WALL_BUDGET_S = 45.0


def tiny_model():
    return TinyCNN(
        TinyCNN.default_recipe().replace(
            input_shape=(8, 8, 3), batch_size=8
        )
    )


_MODEL = tiny_model()
_STATE = init_train_state(_MODEL, jax.random.PRNGKey(0))


def member_factory(obs_dir=None, buckets=(1, 4), max_queue=64, step=1,
                   stall_s=None):
    """A Router factory over the shared TinyCNN state. ``stall_s``
    slows every micro-batch (overload tests fill bounded queues
    deterministically)."""
    def factory(replica_id):
        eng = ServeEngine(
            _MODEL, buckets=buckets, max_queue=max_queue,
            obs_dir=obs_dir, replica_id=replica_id,
            sink_name=f"serve_r{replica_id}.jsonl",
        )
        eng.set_params(_STATE.params, _STATE.model_state, step)
        eng.warmup()
        eng.start()
        if stall_s is not None:
            orig = eng._serve_batch

            def slow(*a, **k):
                time.sleep(stall_s)
                return orig(*a, **k)

            eng._serve_batch = slow
        return eng
    return factory


def test_failover_on_kill_loses_no_request(tmp_path):
    """The tentpole contract: requests in flight on a killed replica
    are RE-ADMITTED to the survivor — every submitted request is
    served, the drop counter stays zero, and the failover is recorded
    with its destination replica."""
    router = Router(
        member_factory(obs_dir=str(tmp_path), stall_s=0.05),
        2, obs_dir=str(tmp_path), seed=0,
    )
    router.start(supervise=False)
    r = np.random.RandomState(0)
    futs = [router.submit(r.randn(8, 8, 3)) for _ in range(12)]
    # the stalled batchers guarantee a backlog on replica 0 at kill time
    router.kill_replica(0)
    results = [f.result(30.0) for f in futs]
    assert len(results) == 12 and all(res.step == 1 for res in results)
    stats = router.stats()
    assert stats["tmpi_router_served_total"] == 12.0
    assert stats["tmpi_router_dropped_total"] == 0.0
    assert stats["tmpi_router_failovers_total"] >= 1.0
    assert router.drain(timeout=20.0)
    lines = [json.loads(l) for l in
             (tmp_path / "router.jsonl").read_text().splitlines()]
    fos = [l for l in lines if l.get("event") == "failover"]
    assert fos and all(l["to_replica"] == 1 for l in fos)
    downs = [l for l in lines if l.get("event") == "health"
             and l.get("to_state") == "down"]
    assert downs and downs[0]["replica_id"] == 0
    assert check_file(str(tmp_path / "router.jsonl")) == []


def test_supervisor_restarts_crashed_replica(tmp_path):
    """The supervisor demotes a killed member and restarts it through
    the factory under decorrelated-jitter backoff; the fleet returns
    to full strength without any caller intervention."""
    router = Router(
        member_factory(obs_dir=str(tmp_path), buckets=(1,)),
        2, obs_dir=str(tmp_path),
        health_interval=0.02, restart_base_s=0.02, restart_cap_s=0.2,
        seed=3,
    )
    router.start()
    try:
        assert router.healthy_count == 2
        router.kill_replica(0)
        assert router.healthy_count == 1
        deadline = time.monotonic() + 20.0
        while router.healthy_count < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.healthy_count == 2, "supervisor never restarted 0"
        assert router.replicas[0].restarts == 1
        # serving works on the restarted member too
        res = router.infer(np.random.RandomState(1).randn(8, 8, 3))
        assert res.step == 1
        stats = router.stats()
        assert stats["tmpi_router_restarts_total"] == 1.0
        assert stats["tmpi_router_restart_failures_total"] == 0.0
    finally:
        assert router.drain(timeout=20.0)
    lines = [json.loads(l) for l in
             (tmp_path / "router.jsonl").read_text().splitlines()]
    restarts = [l for l in lines if l.get("event") == "restart"]
    assert len(restarts) == 1 and restarts[0]["replica_id"] == 0
    assert restarts[0]["backoff_s"] >= 0.02
    # full state machine on the record stream: down -> restarting ->
    # healthy, in order
    states = [(l.get("from_state"), l.get("to_state")) for l in lines
              if l.get("replica_id") == 0 and "to_state" in l]
    assert states.index(("healthy", "down")) \
        < states.index(("down", "restarting")) \
        < states.index(("restarting", "healthy"))
    assert check_file(str(tmp_path / "router.jsonl")) == []


def test_step_floor_monotone_across_central_reload():
    """Central hot-reload fan-out: one set_params swaps every member,
    the fleet floor ratchets, and ``params_step`` (min over healthy)
    reflects the slowest member — served steps can never regress."""
    router = Router(member_factory(buckets=(1,)), 2, seed=0)
    router.start(supervise=False)
    try:
        r = np.random.RandomState(0)
        first = router.infer(r.randn(8, 8, 3))
        assert first.step == 1 and router.params_step == 1
        assert router.set_params(_STATE.params, _STATE.model_state, 5)
        assert router.params_step == 5  # every member swapped
        later = [router.infer(r.randn(8, 8, 3)) for _ in range(4)]
        assert all(res.step == 5 for res in later)
        assert router.stats()["tmpi_router_step_floor"] == 5.0
        # a stale swap is refused fleet-wide
        assert not router.set_params(_STATE.params, _STATE.model_state, 2)
        assert router.params_step == 5
    finally:
        assert router.drain(timeout=20.0)


def test_healthz_fleet_semantics():
    """The LB probe stays green while ANY member is healthy (a
    degraded-but-serving fleet keeps taking traffic) and goes 503 only
    at zero healthy replicas or on drain."""
    router = Router(member_factory(buckets=(1,)), 2, seed=0)
    router.start(supervise=False)
    ok, body = router.healthz()
    assert ok and body["replicas"] == 2 and body["healthy"] == 2
    assert body["states"] == {"0": "healthy", "1": "healthy"}
    router.kill_replica(0)
    ok, body = router.healthz()
    assert ok and body["healthy"] == 1  # degraded, still routable
    assert body["states"]["0"] == "down"
    router.kill_replica(1)
    ok, body = router.healthz()
    assert not ok and body["healthy"] == 0
    router.drain(timeout=20.0)
    ok, body = router.healthz()
    assert not ok and body["draining"]


def test_fleet_overload_and_unavailable_semantics():
    """RouterOverloaded fires only when EVERY healthy replica's own
    admission control rejects, and its retry-after comes from the
    FLEET's backlog/capacity estimate; zero healthy replicas is
    RouterUnavailable; draining is the engine-compatible reject."""
    router = Router(
        member_factory(buckets=(1,), max_queue=1, stall_s=0.4),
        2, seed=0,
    )
    router.start(supervise=False)
    r = np.random.RandomState(0)
    futs = []
    with pytest.raises(RouterOverloaded) as ei:
        for _ in range(20):
            futs.append(router.submit(r.randn(8, 8, 3)))
    # both replicas admitted work before the fleet-level reject
    assert len(futs) >= 2
    assert ei.value.retry_after_ms > 0
    assert router.retry_after_ms() > 0
    assert router.stats()["tmpi_router_rejected_total"] == 1.0
    for f in futs:
        f.result(30.0)
    router.kill_replica(0)
    router.kill_replica(1)
    with pytest.raises(RouterUnavailable) as ei:
        router.submit(r.randn(8, 8, 3))
    assert ei.value.retry_after_ms > 0
    router.drain(timeout=20.0)
    with pytest.raises(EngineDraining):
        router.submit(r.randn(8, 8, 3))


def test_http_frontend_fronts_router(tmp_path):
    """The unchanged frontend over a Router: /infer serves through the
    fleet, /healthz carries the fleet body and stays 200 with one dead
    member, /metrics exposes tmpi_router_*, and a fleet-level 503
    carries Retry-After from the router's surviving-capacity estimate
    (the satellite-5 bugfix path)."""
    from theanompi_tpu.serve.frontend import serve_http

    router = Router(
        member_factory(buckets=(1,), max_queue=1, stall_s=0.4),
        2, obs_dir=str(tmp_path), seed=0,
    )
    router.start(supervise=False)
    httpd = serve_http(router, host="127.0.0.1", port=0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        x = np.random.RandomState(0).randn(8, 8, 3).tolist()
        conn.request("POST", "/infer", body=json.dumps({"input": x}))
        resp = conn.getresponse()
        assert resp.status == 200
        assert json.loads(resp.read())["step"] == 1
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["replicas"] == 2 and body["healthy"] == 2
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert b"tmpi_router_requests_total" in resp.read()
        # fill both bounded queues so the FLEET rejects the next POST.
        # A stalled batch can complete in the gap between the fill loop
        # and the HTTP round trip (freeing a max_queue=1 slot), so top
        # up and retry until the 503 lands — bounded by a wall deadline
        r = np.random.RandomState(1)
        futs = []
        status, headers, err = None, None, None
        wall = time.time() + 30.0
        while time.time() < wall:
            for _ in range(20):
                try:
                    futs.append(router.submit(r.randn(8, 8, 3)))
                except RouterOverloaded:
                    break
            conn.request("POST", "/infer", body=json.dumps({"input": x}))
            resp = conn.getresponse()
            status, headers = resp.status, resp.headers
            err = json.loads(resp.read())
            if status == 503:
                break
        assert status == 503
        assert int(headers["Retry-After"]) >= 1
        # the reject is the ROUTER's (aggregate view), not one engine's
        assert "healthy replicas overloaded" in err["error"]
        for f in futs:
            f.result(30.0)
        # one dead member: the probe stays green (degraded, routable)
        router.kill_replica(0)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200 and body["healthy"] == 1
    finally:
        httpd.shutdown()
        httpd.server_close()
        router.drain(timeout=20.0)


def test_central_reload_via_checkpoint_reloader(tmp_path):
    """serve/reload.py over a Router: ONE keep-chain poll + load fans
    out to every member, the kind=reload record lands in router.jsonl,
    and tmpi_router_reloads_total counts it."""
    from theanompi_tpu.serve.reload import CheckpointReloader
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = tiny_model()
    state = init_train_state(model, jax.random.PRNGKey(0))
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), state, 7, rng=jax.random.PRNGKey(1))

    def factory(replica_id):
        eng = ServeEngine(model, buckets=(1,), replica_id=replica_id)
        eng.set_params(state.params, state.model_state, 1)
        eng.warmup()
        eng.start()
        return eng

    obs = tmp_path / "obs"
    router = Router(factory, 2, obs_dir=str(obs), seed=0)
    router.start(supervise=False)
    try:
        reloader = CheckpointReloader(router, str(ckpt), interval=60.0)
        assert reloader.poll_once() == 7
        assert router.params_step == 7  # both members swapped
        assert router.infer(np.zeros((8, 8, 3))).step == 7
        assert router.stats()["tmpi_router_reloads_total"] == 1.0
    finally:
        assert router.drain(timeout=20.0)
    lines = [json.loads(l) for l in
             (obs / "router.jsonl").read_text().splitlines()]
    reloads = [l for l in lines if l["kind"] == "reload"]
    assert reloads and reloads[0]["from_step"] == 1 \
        and reloads[0]["to_step"] == 7
    assert check_file(str(obs / "router.jsonl")) == []


def test_router_snapshot_record_schema_valid():
    """The kind=router snapshot validates and every stats key carries
    the documented tmpi_router_ prefix."""
    from theanompi_tpu.tools.check_obs_schema import validate_record

    router = Router(member_factory(buckets=(1,)), 1, seed=0)
    router.start(supervise=False)
    try:
        router.infer(np.zeros((8, 8, 3)))
        rec = router.router_record()
        assert rec["kind"] == "router" and rec["event"] == "snapshot"
        assert validate_record(rec) == []
        assert all(k.startswith("tmpi_router_") for k in rec["metrics"])
    finally:
        router.drain(timeout=20.0)


# --------------------------------------------------------------------------
# satellite 2: the health-transition path under a seeded StressHarness
# scenario — kills land mid-traffic with the demote window widened, and
# the no-drop / step-floor invariants must hold in every interleaving
# --------------------------------------------------------------------------


def test_router_health_transitions_under_stress(tmp_path):
    """Seeded stress over healthy -> down -> restarting -> healthy
    while submitters hammer the fleet: a kill landing in ANY
    interleaving (mark_down widened by inject_delay) never drops a
    request, never regresses the served step, and the survivor keeps
    the probe green."""

    def make(rng):
        router = Router(
            member_factory(buckets=(1,)), 2,
            health_interval=0.01, restart_base_s=0.01,
            restart_cap_s=0.05, seed=rng.randrange(1 << 16),
        )
        router.start()
        # widen the demote window: the health transition races the
        # request path exactly where the analyzer sees the contention
        undo = inject_delay(router.replicas[0], "mark_down", rng,
                            before_s=2e-3)
        failures = []
        steps = []

        def submitter():
            r = np.random.RandomState(rng.randrange(1 << 16))
            for _ in range(8):
                try:
                    steps.append(router.infer(r.randn(8, 8, 3),
                                              timeout=30.0).step)
                except Exception as e:  # noqa: BLE001 — any reject or
                    # drop under a single-replica kill is a violation
                    failures.append(repr(e))

        def killer():
            time.sleep(rng.random() * 0.05)
            router.kill_replica(0)

        def check():
            out = []
            if failures:
                out.append(f"{len(failures)} failed requests: "
                           f"{failures[:2]}")
            if len(steps) + len(failures) != 16:
                out.append(f"lost results: {len(steps)}")
            if any(s != 1 for s in steps):
                out.append(f"served step moved: {sorted(set(steps))}")
            stats = router.stats()
            if stats["tmpi_router_dropped_total"] != 0.0:
                out.append("requests dropped under kill")
            ok, _ = router.healthz()
            if not ok:
                out.append("fleet probe went red with a survivor up")
            return out

        def cleanup():
            undo()
            router.drain(timeout=20.0)

        return Scenario(threads=[submitter, submitter, killer],
                        check=check, cleanup=cleanup)

    h = StressHarness(seed=19, obs_dir=str(tmp_path))
    res = h.run("router-health-transitions", make, rounds=3,
                wall_budget_s=WALL_BUDGET_S)
    assert res.ok, res.violations
    # the stress evidence rides the telemetry stream
    assert check_file(str(tmp_path / "stress.jsonl")) == []


@pytest.mark.parametrize("kind", ["eval", "decode"])
def test_fleet_members_take_distinct_devices(kind):
    """`tmpi serve --replicas N` built every member identically, so a
    fleet on a multi-chip host sat on chip 0. Member ``rid`` now takes
    local device ``rid % n_local``: params (and the decode member's KV
    pool) live there, and the member answers from there."""
    from theanompi_tpu.serve.cli import replica_sharding

    n_local = len(jax.local_devices())
    assert n_local >= 4

    def member(rid):
        if kind == "eval":
            eng = ServeEngine(_MODEL, buckets=(1,),
                              sharding=replica_sharding(rid))
            eng.set_params(_STATE.params, _STATE.model_state, 1)
            x = np.zeros((8, 8, 3), np.float32)
        else:
            from test_decode_engine import make_engine, set_tiny_params

            eng = make_engine(sharding=replica_sharding(rid))
            set_tiny_params(eng)
            x = np.asarray([1, 2, 3], np.int32)
        eng.warmup()
        eng.start()
        try:
            eng.infer(x, timeout=60)
        finally:
            eng.drain(timeout=60)
        return eng

    members = [member(rid) for rid in range(4)]
    ids = [m.params_device()["device_ids"] for m in members]
    assert ids == [[0], [1], [2], [3]]
    if kind == "decode":
        for rid, m in enumerate(members):
            assert {d.id for d in m._cache.k_pool.devices()} == {rid}
            # and no request moved the pool (nothing retraced)
            assert m.compile_count == len(m.buckets) + 1
    # more members than devices wrap around; a single engine and a
    # tensor-sharded recipe are left alone
    wrapped = replica_sharding(n_local + 1)
    assert [d.id for d in wrapped.mesh.devices.flat] == [1]
    assert replica_sharding(None) is None
    assert replica_sharding(2, base=wrapped) is wrapped
