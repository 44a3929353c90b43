"""In-process TPU inference engine: request queue + dynamic micro-batcher.

The serving analogue of the training side's dispatch pipeline
(utils/dispatch.py): keep Python, compilation, and host syncs OFF the
hot path. Three rules shape the implementation:

1. **Bucketed shapes, compiled once.** XLA compiles one program per
   input shape; letting request coalescing produce arbitrary batch
   sizes would compile an unbounded program set and pay seconds of
   latency on the first request of every new size. The engine instead
   pads every micro-batch UP to a small ascending set of batch buckets
   (default 1/8/32/128), so the jitted eval-mode apply
   (``models/zoo.infer_fn`` — train=False, no rng, fixed BN stats, and
   donation-FREE: the served params must survive the call) compiles at
   most ``len(buckets)`` programs, all AOT-warmed in :meth:`warmup`
   before the first request arrives. Padding is sound because
   eval-mode forwards are row-independent (no cross-batch reduction:
   BN uses running stats, dropout is off), so the padded rows cannot
   perturb the real ones — proven bit-identical in
   tests/test_serve_engine.py.

2. **Coalesce what is waiting, never wait to coalesce.** The batcher
   takes every queued request up to the largest bucket and serves them
   as one forward. Under load, batches fill toward the big buckets
   (throughput); when idle, a lone request rides the size-1 bucket
   immediately (latency). No artificial batching window.

3. **Swap params between batches.** Hot reload (serve/reload.py)
   publishes a new :class:`ServedParams` by atomic reference swap; the
   batcher reads the reference once per micro-batch, so every request
   is served by exactly one coherent (params, model_state, step)
   triple, the served step only moves forward, and zero requests fail
   or drop during a swap (tests/test_serve_reload.py hammers this).

Admission control: the queue is bounded (``max_queue``) — a full queue
rejects with :class:`EngineOverloaded` carrying a ``retry_after_ms``
estimate from the EWMA batch time, per-request deadlines expire queued
requests with :class:`DeadlineExceeded` (rejected, never served), and
:meth:`drain` (wired to SIGTERM by the CLI, reusing the training
driver's grace discipline) stops admission, finishes the backlog, and
only then stops the batcher.

Telemetry rides the existing obs subsystem: ``tmpi_serve_*`` counters/
gauges/histograms in a :class:`~theanompi_tpu.obs.metrics.
MetricsRegistry` (p50/p99 via ``Histogram.quantile``), and periodic
``serve`` JSONL records (plus the reloader's ``reload`` records) in
``<obs_dir>/serve.jsonl`` — schemas in tools/check_obs_schema.py.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

DEFAULT_BUCKETS = (1, 8, 32, 128)

# latency histogram bounds: request latencies live in the 1ms..seconds
# band (the obs DEFAULT_BUCKETS top out at 60s — step/checkpoint scale)
LATENCY_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0,
)


class Rejected(RuntimeError):
    """Base: the engine refused to take (or serve) a request."""

    retry_after_ms: Optional[float] = None


class EngineOverloaded(Rejected):
    """Admission control: the bounded queue is full. ``retry_after_ms``
    estimates when capacity frees up (queue depth x EWMA batch time)."""

    def __init__(self, depth: int, retry_after_ms: float):
        self.retry_after_ms = float(retry_after_ms)
        super().__init__(
            f"serve queue full ({depth} waiting); retry in "
            f"~{retry_after_ms:.0f} ms"
        )


class EngineDraining(Rejected):
    """The engine is draining (SIGTERM / shutdown): backlog is being
    served, new requests are not admitted."""

    def __init__(self):
        super().__init__("serve engine is draining; not admitting requests")


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed while it waited — rejected, not
    served (serving a result the client stopped waiting for wastes a
    batch slot someone else's deadline needed)."""


class EngineDead(RuntimeError):
    """The engine hard-died (:meth:`ServeEngine.abort` — a crashed
    replica, or chaos's ``replica_crash``): queued AND in-flight
    requests are rejected with this error, which a fronting router
    (serve/router.py) treats as "re-admit on a healthy replica", never
    as a client-visible failure."""


class ServedParams(NamedTuple):
    """One coherent serving triple, swapped by atomic reference."""

    params: object
    model_state: object
    step: int

    def device(self) -> dict:
        """Where the params live, read off the placed arrays (not off
        what placement was asked for)."""
        import jax

        devs = sorted(
            {d for leaf in jax.tree_util.tree_leaves(self.params)
             for d in leaf.devices()},
            key=lambda d: d.id,
        )
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "device_ids": [d.id for d in devs]}


class ServeResult(NamedTuple):
    """Per-request result: the logits row and the checkpoint step of
    the params that produced it (reload tests assert monotonicity)."""

    logits: np.ndarray
    step: int


class ServeFuture:
    """Minimal completion handle (threading.Event + slots — no
    concurrent.futures machinery on the hot path)."""

    __slots__ = ("_event", "_lock", "_value", "_error", "t_submit")

    def __init__(self):
        self._event = threading.Event()
        # settlement can come from the batcher thread OR a router
        # failover/abort path on another thread; first writer wins
        self._lock = threading.Lock()
        self._value: Optional[ServeResult] = None
        self._error: Optional[BaseException] = None
        self.t_submit = time.monotonic()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if not self._event.wait(timeout):
            raise TimeoutError("serve request still in flight")
        if self._error is not None:
            raise self._error
        return self._value

    # -- engine side --------------------------------------------------------
    def _resolve(self, value: ServeResult) -> None:
        with self._lock:
            if not self._event.is_set():
                self._value = value
                self._event.set()

    def _reject(self, error: BaseException) -> None:
        with self._lock:
            if not self._event.is_set():
                self._error = error
                self._event.set()


class _Request:
    __slots__ = ("x", "deadline", "future")

    def __init__(self, x, deadline: Optional[float], future: ServeFuture):
        self.x = x
        self.deadline = deadline  # absolute time.monotonic(), or None
        self.future = future


class ServeEngine:
    """Dynamic micro-batching inference engine over one model.

    ``model`` is a constructed :class:`~theanompi_tpu.models.contract.
    Model`; requests are single examples shaped ``recipe.input_shape``
    (float images, or int token rows for LM models). Params come from
    :meth:`load_initial` / :meth:`set_params` (serve/reload.py swaps
    them live). Lifecycle: construct → ``load_initial`` → ``warmup`` →
    ``start`` → ``submit``/``infer`` ... → ``drain``.

    ``default_deadline_ms``: applied to requests that don't carry their
    own; None = requests wait indefinitely.
    ``record_every``: write a ``serve`` JSONL record every N
    micro-batches (obs_dir only); one final record lands at drain.
    ``replica_id``: set by the router (serve/router.py) when this
    engine is one member of a replica group — rides every ``serve``
    record so a fleet's obs streams attribute to the member.
    ``sink_name``: the JSONL file under ``obs_dir`` (replica members
    write ``serve_r<id>.jsonl`` so N members never interleave one
    file). ``sharding``: the serving :class:`~theanompi_tpu.parallel.
    recipe.ShardingRecipe` (default: replicated on the first device; a
    fleet member passes the recipe of its own chip).
    """

    def __init__(
        self,
        model,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        max_queue: int = 256,
        default_deadline_ms: Optional[float] = None,
        obs_dir: Optional[str] = None,
        registry=None,
        record_every: int = 50,
        replica_id: Optional[int] = None,
        sink_name: str = "serve.jsonl",
        sharding=None,
    ):
        from theanompi_tpu.models.zoo import infer_fn
        from theanompi_tpu.obs.metrics import MetricsRegistry

        self.model = model
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        if len(set(self.buckets)) != len(self.buckets):
            raise ValueError(f"duplicate buckets in {buckets!r}")
        self.max_queue = int(max_queue)
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.default_deadline_ms = default_deadline_ms
        self.obs_dir = obs_dir
        self.record_every = max(1, int(record_every))
        self.replica_id = None if replica_id is None else int(replica_id)
        self.sink_name = str(sink_name)

        ishape = tuple(model.recipe.input_shape)
        self._ishape = ishape
        self._in_dtype = (
            np.int32 if getattr(model, "is_lm", False) else np.float32
        )

        # the ONE inference definition (models/zoo.infer_fn), jitted
        # donation-free; the host-side trace counter increments once per
        # compiled program (jit retraces exactly when a new input
        # signature arrives), so ``compile_count`` is the proof handle
        # for "≤ len(buckets) programs" (tests/test_serve_engine.py)
        import jax

        self._trace_count = 0
        fwd = infer_fn(model)

        def _counted(params, model_state, x):
            self._trace_count += 1  # trace-time only, never per call
            return fwd(params, model_state, x)

        self._fwd = jax.jit(_counted)
        # the serving ShardingRecipe (parallel/recipe.py): params/BN
        # replicated on the serving mesh — the DECLARED placement the
        # train->serve handoff check (tools/analyze/sharding.py,
        # SHARD004) compares against the training engine's stamped
        # ``__topology__`` specs, and the placement set_params uses
        from theanompi_tpu.parallel.recipe import ShardingRecipe

        self.sharding = sharding if sharding is not None else ShardingRecipe.serve()

        self._served: Optional[ServedParams] = None
        self._swap_lock = threading.Lock()
        self._q: collections.deque[_Request] = collections.deque()
        self._cond = threading.Condition()
        self._draining = False
        self._abort_error: Optional[BaseException] = None
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._batch_s_ewma: Optional[float] = None
        self._batches = 0
        self._fill_sum = 0.0
        self._serve_f = None
        self._sink_lock = threading.Lock()
        self._sink_retired = False

        self.registry = registry or MetricsRegistry()
        self._h_latency = self.registry.histogram(
            "tmpi_serve_latency_seconds",
            help="request latency, submit -> result (serve/engine.py)",
            buckets=LATENCY_BUCKETS,
        )
        self._g_queue = self.registry.gauge(
            "tmpi_serve_queue_depth", help="requests waiting for a batch slot"
        )
        self._g_fill = self.registry.gauge(
            "tmpi_serve_batch_fill",
            help="real rows / bucket rows of the last micro-batch",
        )
        self._g_step = self.registry.gauge(
            "tmpi_serve_params_step", help="checkpoint step currently served"
        )
        self._c_requests = self.registry.counter(
            "tmpi_serve_requests_total",
            help="requests by outcome (status=served|expired|rejected)",
        )
        self._c_batches = self.registry.counter(
            "tmpi_serve_batches_total",
            help="micro-batches by bucket size (bucket=N)",
        )
        self._c_reloads = self.registry.counter(
            "tmpi_serve_reloads_total",
            help="checkpoint hot-reloads applied (serve/reload.py)",
        )

    # -- params -------------------------------------------------------------
    @property
    def params_step(self) -> int:
        """Checkpoint step currently served (-1 before load_initial)."""
        served = self._served
        return served.step if served is not None else -1

    def params_device(self) -> dict:
        """Platform, kind and device ids holding the served params."""
        return self._served.device()

    def load_initial(self, ckpt_dir: str) -> int:
        """Load the newest VERIFIED checkpoint from a training run's
        keep-chain (the same discovery resume uses) and serve it."""
        from theanompi_tpu.serve.reload import load_for_serving
        from theanompi_tpu.utils.checkpoint import latest_checkpoint

        path = latest_checkpoint(ckpt_dir, verify=True)
        if path is None:
            raise FileNotFoundError(
                f"no verified checkpoint under {ckpt_dir!r} to serve"
            )
        params, model_state, step = load_for_serving(
            path, self.model, target_mesh=self.sharding.mesh
        )
        self.set_params(params, model_state, step)
        return step

    def set_params(self, params, model_state, step: int) -> bool:
        """Atomically publish a serving triple. Refuses to move the
        served step BACKWARD (a slow reload racing a fresh one must not
        regress what is served); returns whether the swap happened.
        In-flight micro-batches finish on the triple they read — the
        swap is a reference assignment, nothing is mutated. The
        device_put runs OUTSIDE the swap lock (it is the slow part),
        and the step check re-runs under it, so two racing publishers
        cannot interleave check and assignment."""
        step = int(step)
        current = self._served
        if current is not None and step <= current.step:
            return False
        # placement per the serving recipe (replicated on ITS mesh)
        params = self.sharding.place_replicated(params)
        model_state = self.sharding.place_replicated(model_state)
        with self._swap_lock:
            current = self._served
            if current is not None and step <= current.step:
                return False
            self._served = ServedParams(params, model_state, step)
            # gauge inside the lock: a racing older publisher must not
            # leave the exported step regressed vs what is served
            self._g_step.set(step)
        return True

    def note_reload(self, from_step: int, to_step: int, ms: float) -> None:
        """Reloader hook: count the swap + write a ``reload`` record."""
        self._c_reloads.inc()
        self._write_record({
            "kind": "reload", "t": time.time(),
            "from_step": int(from_step), "to_step": int(to_step),
            "ms": round(float(ms), 3),
        })

    def note_reload_failed(self, from_step: int, error: str) -> None:
        """Reloader hook for a reload that verified but failed to LOAD
        (the keep-chain pruned the file between discovery and open —
        the TOCTOU race — or a structure mismatch): count it and write
        a failed ``reload`` record (``ok: false``, ``to_step: -1``) so
        the telemetry shows the race happened even though serving never
        blinked and the next poll simply retries."""
        self._c_reloads.inc(status="failed")
        self._write_record({
            "kind": "reload", "t": time.time(),
            "from_step": int(from_step), "to_step": -1,
            "ok": False, "error": str(error)[:500],
        })

    # -- lifecycle ----------------------------------------------------------
    def warmup(self) -> int:
        """AOT-warm every bucket shape through the jitted apply, so no
        request ever pays a compile. Returns the compile count (==
        len(buckets) on a fresh engine; re-warms are free)."""
        import jax.numpy as jnp

        if self._served is None:
            raise RuntimeError("warmup needs params (load_initial first)")
        served = self._served
        for b in self.buckets:
            x = jnp.zeros((b, *self._ishape), self._in_dtype)
            np.asarray(self._fwd(served.params, served.model_state, x))
        return self.compile_count

    @property
    def compile_count(self) -> int:
        """Programs compiled so far (trace-count of the jitted apply)."""
        return self._trace_count

    def start(self) -> None:
        # under the engine lock: the router's supervisor starts
        # restarted members from its own thread
        with self._cond:
            if self._thread is not None:
                raise RuntimeError("engine already started")
            self._thread = threading.Thread(
                target=self._loop, name="tmpi-serve-batcher", daemon=True
            )
        self._thread.start()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: reject new admissions, serve everything
        already queued, stop the batcher, flush the final ``serve``
        record. Idempotent. Returns True when the backlog fully
        drained inside ``timeout``."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = None if timeout is None else time.monotonic() + timeout
        drained = True
        if self._thread is not None:
            self._thread.join(
                None if deadline is None else max(0.0, deadline - time.monotonic())
            )
            drained = not self._thread.is_alive()
        # claim the final record exactly once, under the sink lock:
        # drain is reachable from the SIGTERM drain thread AND the
        # CLI's finally concurrently, and a bare check-then-act here
        # wrote the final record twice
        with self._sink_lock:
            first = not self._stopped.is_set()
            self._stopped.set()
        if first and self.obs_dir is not None:
            # compute the record outside the lock (it reads the
            # internally-locked counters), then write-and-retire in
            # ONE hold — a straggling reloader write can land before
            # the final record, never after it
            rec = self.serve_record()
            with self._sink_lock:
                if not self._sink_retired:
                    if self._serve_f is None:
                        os.makedirs(self.obs_dir, exist_ok=True)
                        self._serve_f = open(
                            os.path.join(self.obs_dir, self.sink_name), "a"
                        )
                    self._serve_f.write(json.dumps(rec) + "\n")
                    self._sink_retired = True
                    self._serve_f.close()
                    self._serve_f = None
        return drained

    close = drain

    def abort(self, error: Optional[BaseException] = None) -> None:
        """Hard death (the crash analogue of :meth:`drain`): stop
        admitting, reject every QUEUED request with ``error``
        (default :class:`EngineDead`), and poison the in-flight batch
        so its futures reject too — nothing resolves after an abort.
        A fronting router re-admits the rejected requests on healthy
        replicas; a bare engine surfaces them as failures. Idempotent.
        """
        err = error if error is not None else EngineDead("engine aborted")
        with self._cond:
            if self._abort_error is None:
                self._abort_error = err
            self._draining = True
            doomed = list(self._q)
            self._q.clear()
            self._g_queue.set(0.0)
            self._cond.notify_all()
        for r in doomed:
            r.future._reject(err)
        if doomed:
            self._c_requests.inc(len(doomed), status="failed")

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def alive(self) -> bool:
        """Health the router polls: a started, un-aborted, un-draining
        engine whose batcher thread is running."""
        t = self._thread
        return (t is not None and t.is_alive()
                and self._abort_error is None and not self._draining)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a batch slot (the router's load signal)."""
        return len(self._q)

    @property
    def batch_s_ewma(self) -> Optional[float]:
        """EWMA seconds per micro-batch (None before the first batch) —
        the other half of the router's least-loaded score."""
        return self._batch_s_ewma

    # -- request path -------------------------------------------------------
    def submit(self, x, deadline_ms: Optional[float] = None) -> ServeFuture:
        """Enqueue one example; returns a :class:`ServeFuture`.
        Raises :class:`EngineOverloaded` / :class:`EngineDraining`
        synchronously (admission control); deadline expiry surfaces
        from ``future.result()`` as :class:`DeadlineExceeded`."""
        x = np.asarray(x, self._in_dtype)
        if x.shape != self._ishape:
            raise ValueError(
                f"request shape {x.shape} != model input {self._ishape}"
            )
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        deadline = (
            time.monotonic() + float(deadline_ms) / 1000.0
            if deadline_ms else None
        )
        fut = ServeFuture()
        with self._cond:
            if self._draining:
                self._c_requests.inc(status="rejected")
                raise EngineDraining()
            if len(self._q) >= self.max_queue:
                self._c_requests.inc(status="rejected")
                batch_s = self._batch_s_ewma or 0.05
                n_batches = -(-len(self._q) // self.buckets[-1])
                raise EngineOverloaded(
                    len(self._q), retry_after_ms=1000.0 * batch_s * n_batches
                )
            self._q.append(_Request(x, deadline, fut))
            self._g_queue.set(len(self._q))
            self._cond.notify()
        return fut

    def infer(self, x, deadline_ms: Optional[float] = None,
              timeout: Optional[float] = 30.0) -> ServeResult:
        """Blocking convenience: submit + wait."""
        return self.submit(x, deadline_ms=deadline_ms).result(timeout)

    # -- batcher ------------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def _loop(self) -> None:
        max_take = self.buckets[-1]
        while True:
            with self._cond:
                while not self._q and not self._draining:
                    self._cond.wait(0.05)
                if not self._q and self._draining:
                    return
                reqs = [
                    self._q.popleft()
                    for _ in range(min(len(self._q), max_take))
                ]
                self._g_queue.set(len(self._q))
            try:
                self._serve_batch(reqs)
            except BaseException as e:  # noqa: BLE001 — requests must
                # never hang on an engine bug: fail THIS batch's futures
                # and keep serving (a poisoned input must not take the
                # engine down with it). An abort poisons the batch on
                # purpose — those count as failed, not rejected
                failed = 0
                for r in reqs:
                    if not r.future.done():
                        r.future._reject(e)
                        failed += 1
                if failed:
                    status = ("failed" if e is self._abort_error
                              else "rejected")
                    self._c_requests.inc(failed, status=status)

    def _serve_batch(self, reqs: list) -> None:
        import jax.numpy as jnp

        err = self._abort_error
        if err is not None:  # the replica died under this batch
            raise err
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                r.future._reject(DeadlineExceeded(
                    f"deadline passed {1000 * (now - r.deadline):.1f} ms "
                    "before a batch slot opened"
                ))
                self._c_requests.inc(status="expired")
            else:
                live.append(r)
        if not live:
            return
        t0 = time.monotonic()
        bucket = self._bucket_for(len(live))
        batch = np.zeros((bucket, *self._ishape), self._in_dtype)
        for i, r in enumerate(live):
            batch[i] = r.x
        served = self._served  # ONE read: the swap point for hot reload
        logits = np.asarray(
            self._fwd(served.params, served.model_state, jnp.asarray(batch))
        )
        t_done = time.monotonic()
        err = self._abort_error
        if err is not None:  # abort landed mid-forward: nothing
            raise err        # resolves after a death
        for i, r in enumerate(live):
            r.future._resolve(ServeResult(logits[i], served.step))
            self._h_latency.observe(t_done - r.future.t_submit)
        self._c_requests.inc(len(live), status="served")
        self._c_batches.inc(bucket=bucket)
        fill = len(live) / bucket
        self._g_fill.set(fill)
        self._fill_sum += fill
        self._batches += 1
        batch_s = t_done - t0
        self._batch_s_ewma = (
            batch_s if self._batch_s_ewma is None
            else 0.8 * self._batch_s_ewma + 0.2 * batch_s
        )
        if self._batches % self.record_every == 0:
            self._write_serve_record()

    # -- stats / telemetry --------------------------------------------------
    @property
    def mean_batch_fill(self) -> Optional[float]:
        return self._fill_sum / self._batches if self._batches else None

    def latency_ms(self, q: float) -> Optional[float]:
        s = self._h_latency.quantile(q)
        return None if s is None else 1000.0 * s

    def stats(self) -> dict:
        """Flat numeric snapshot (the ``serve`` record's metrics map;
        every key is ``tmpi_serve_``-prefixed — enforced by the schema
        checker so serve records stay greppable by one prefix)."""
        out = {
            "tmpi_serve_queue_depth": float(len(self._q)),
            "tmpi_serve_served_total": self._c_requests.value(status="served"),
            "tmpi_serve_expired_total": self._c_requests.value(status="expired"),
            "tmpi_serve_rejected_total": self._c_requests.value(status="rejected"),
            "tmpi_serve_failed_total": self._c_requests.value(status="failed"),
            "tmpi_serve_reloads_total": self._c_reloads.value(),
            "tmpi_serve_reload_failures_total":
                self._c_reloads.value(status="failed"),
            "tmpi_serve_batches_total": float(self._batches),
        }
        if self._batches:
            out["tmpi_serve_batch_fill_mean"] = self.mean_batch_fill
        for name, q in (("p50", 0.5), ("p99", 0.99)):
            ms = self.latency_ms(q)
            if ms is not None:
                out[f"tmpi_serve_{name}_ms"] = ms
        return out

    def serve_record(self) -> dict:
        """The one constructor of a ``kind=serve`` record (schema:
        tools/check_obs_schema.py) — used for the periodic/drain-time
        obs lines AND the CLI's final stdout line, so the two can never
        drift apart on shape. Replica members stamp ``replica_id``."""
        rec = {"kind": "serve", "t": time.time(),
               "params_step": self.params_step, "metrics": self.stats()}
        if self.replica_id is not None:
            rec["replica_id"] = self.replica_id
        return rec

    def _write_serve_record(self) -> None:
        self._write_record(self.serve_record())

    def _write_record(self, rec: dict) -> None:
        if self.obs_dir is None:
            return
        with self._sink_lock:
            if self._sink_retired:
                return
            if self._serve_f is None:
                os.makedirs(self.obs_dir, exist_ok=True)
                self._serve_f = open(
                    os.path.join(self.obs_dir, self.sink_name), "a"
                )
            self._serve_f.write(json.dumps(rec) + "\n")
            self._serve_f.flush()
