"""Continuous-batching LM decode engine over a paged KV-cache.

The decode sibling of :class:`theanompi_tpu.serve.engine.ServeEngine`
(one-shot eval forwards): same queue/admission/drain/hot-reload
lifecycle and the same ``submit``/``drain``/``set_params``/
``params_step`` surface — so :class:`theanompi_tpu.serve.router.Router`
fronts N decode replicas UNCHANGED — but each request is a *generation*
(a prompt plus up to ``max_new_tokens`` sampled continuations), not a
single forward. Three rules carry over from the eval engine, reshaped
for autoregression:

1. **Fixed shapes, bounded programs.** The KV pool is ONE preallocated
   device array per layer (``serve/decode/kvcache.py``); page tables
   and per-slot operand vectors have fixed ``[max_seqs]`` shapes and
   ride in ONE int32 host buffer (``scheduler.py step_arrays``, cut
   apart inside the program), so the single-token decode step compiles
   exactly ONCE no matter how sequences come and go, and takes one
   transfer an iteration. Prompt prefill pads into a small set of
   length buckets (page-size multiples), one compiled program each,
   AOT-warmed in :meth:`warmup`. Total programs:
   ``len(prefill_buckets) + 1`` — proven by the trace counter
   (``compile_count``), same idiom as the eval engine.

2. **Iteration-level scheduling.** Between decode steps the scheduler
   (``serve/decode/scheduler.py``) admits waiting prompts into free
   batch slots (reserving worst-case pages so a running sequence can
   never die of page exhaustion) and evicts finished/deadline-passed
   ones — sequences join and leave a RUNNING batch, no static-batch
   barrier. The prompt's first ``L-1`` tokens prefill the cache; its
   LAST token rides the decode step, so every emitted token exits
   through the one decode program and each iteration has exactly ONE
   host drain point (the ``np.asarray`` on the next-token vector —
   ``tools/check_hot_loop.py`` HOT004 guards this; a
   ``copy_to_host_async()`` at dispatch was measured and left out: the
   1.3-1.7 ms from the program's end to the loop's wake are the
   runtime's, PERF.md section 6, PR 38). After an iteration
   that resolved a request the loop waits a bounded moment for the
   resolved clients' next submissions BEFORE it reads its queue
   (``_hand_over``; ``tmpi_decode_handover_total`` counts how the
   waits ended): a client that resubmits on resolution gets the slot
   its predecessor freed in the very next iteration.

3. **Swap params between iterations.** Hot reload publishes a new
   :class:`~theanompi_tpu.serve.engine.ServedParams` by atomic
   reference swap; the decode loop reads the reference ONCE per
   iteration, so a mid-generation reload changes the params a sequence
   decodes with between tokens but never mid-step, the served step
   only moves forward, and zero in-flight generations drop
   (tests/test_decode_engine.py hammers this, chaos's decode
   schedules hammer it harder).

Telemetry is ``tmpi_decode_*``-prefixed (schema: ``kind=decode`` in
tools/check_obs_schema.py): TTFT/TPOT/queue-wait histograms, tokens/sec,
kv page occupancy, batch occupancy, eviction/expiry counters, plus
periodic ``decode`` JSONL records in ``<obs_dir>/decode.jsonl``.

**The loop measured from inside** (ISSUE 37). The engine owns one
:class:`~theanompi_tpu.utils.recorder.SpanStore` (the Recorder's own
span class), found after the engine has gone by
``span_store("decode")`` (a replica's: ``"decode/<replica_id>"``). The
loop thread brackets seven spans an iteration, in loop order ``queue``
(``_loop``'s body between two ``_iteration`` calls: the lock, the
hand-over's wait after a resolution, an idle engine's wait, the queue
read into the scheduler), ``admit``, ``prefill`` (every call of the
iteration; their count is kept beside it as ``prefill_calls``),
``upload`` (the decode step's ONE host buffer filled), ``dispatch``
(the decode call, which carries the buffer to the device), ``drain``
(the ONE blocking D2H) and ``harvest``,
each under the iteration's number (``tmpi_decode_iterations_total`` as
the iteration begins), on ``time.time_ns()``, the clock of a profiler
trace; each is a ``TraceAnnotation``. A request gets two spans by its
``seq_id``: ``queue_wait`` (submit to the admission that gave it a
slot) and ``first_token`` (that admission to its first token's
``t_done``), each with the ``cause`` iteration. In memory, always on,
no lock; ``drain()`` writes what the store still holds to
``<obs_dir>/spans_rank<r>.jsonl`` (tools/spans_to_trace.py).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

from theanompi_tpu.serve.decode.kvcache import PagedKVCache, pages_needed
from theanompi_tpu.serve.decode.scheduler import DecodeScheduler, DecodeSequence
from theanompi_tpu.utils.recorder import SpanStore
from theanompi_tpu.serve.engine import (
    LATENCY_BUCKETS,
    DeadlineExceeded,
    EngineDead,
    EngineDraining,
    EngineOverloaded,
    Rejected,
    ServedParams,
    ServeFuture,
)

__all__ = [
    "DecodeEngine",
    "DecodeResult",
    "DEFAULT_PREFILL_BUCKETS",
    "LOOP_SPANS",
    "DeadlineExceeded",
    "EngineDead",
    "EngineDraining",
    "EngineOverloaded",
    "Rejected",
]

DEFAULT_PREFILL_BUCKETS = (16, 64)

# the loop thread's spans of one iteration, in loop order (see the
# module's docstring), and a request's two
LOOP_SPANS = ("queue", "admit", "prefill", "upload", "dispatch", "drain",
              "harvest")
REQUEST_SPANS = ("queue_wait", "first_token")

# the hand-over's bound: how long the loop thread, after an iteration that
# resolved a request, waits for the resolved clients' next submissions
# before it reads its queue. A client needs 0.15-0.4 ms of interpreter
# from the resolution to its ``submit`` (PERF.md section 6, PR 37: a
# request that got in ahead of the read waited 0.163-0.171 ms, one that
# did not a whole iteration, 10-14 ms); a deployment whose clients answer
# over a network pays this on the iterations that resolve something
HANDOVER_WAIT_S = 0.0005

# TPOT (time-per-output-token) lives well below request latency — extend
# the serve band downward into the sub-millisecond range
TPOT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0,
)


class DecodeResult(NamedTuple):
    """Per-request result: the generated token ids and the checkpoint
    step of the params that produced the LAST token (a mid-generation
    hot reload legitimately splits a sequence across steps; the final
    step is what monotonicity tests assert on)."""

    tokens: np.ndarray
    step: int


class DecodeEngine:
    """Continuous-batching generation engine over one LM.

    ``model`` is a constructed zoo model with ``supports_decode`` (the
    incremental ``decode_prefill``/``decode_step`` surface —
    models/lm.py). Requests are 1-D int32 token prompts of any length
    up to ``max(prefill_buckets) + 1``; results are
    :class:`DecodeResult`. Lifecycle mirrors the eval engine:
    construct → ``load_initial`` → ``warmup`` → ``start`` →
    ``submit``/``generate`` ... → ``drain``.

    ``kv_pages`` fixed device pages of ``page_size`` positions bound
    total cache capacity; ``max_seqs`` bounds the decode batch width.
    ``mode="static"`` disables iteration-level admission (a batch runs
    to completion before the next forms) — kept only as a test's
    reference for the iteration count (tests/test_decode_engine.py).
    ``temperature`` is the default sampling temperature (0 = greedy);
    sampling draws from a PRNG stream keyed by ``seed`` and the
    iteration counter INSIDE the jitted step, so replays are
    deterministic and the key never retraces the program.
    """

    def __init__(
        self,
        model,
        *,
        prefill_buckets: Sequence[int] = DEFAULT_PREFILL_BUCKETS,
        kv_pages: int = 64,
        page_size: int = 16,
        max_seqs: int = 8,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        mode: str = "continuous",
        max_queue: int = 256,
        default_deadline_ms: Optional[float] = None,
        obs_dir: Optional[str] = None,
        registry=None,
        record_every: int = 50,
        replica_id: Optional[int] = None,
        sink_name: str = "decode.jsonl",
        seed: int = 0,
        sharding=None,
    ):
        from theanompi_tpu.obs.metrics import MetricsRegistry

        if not getattr(model, "supports_decode", False):
            raise ValueError(
                f"{type(model).__name__} does not support incremental "
                "decode (no decode_prefill/decode_step surface — see "
                "models/lm.py); serve it with the eval-forward "
                "ServeEngine instead"
            )
        self.model = model
        arch = model.arch
        self.page_size = int(page_size)
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.default_temperature = float(temperature)
        self.max_queue = int(max_queue)
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.default_deadline_ms = default_deadline_ms
        self.obs_dir = obs_dir
        self.record_every = max(1, int(record_every))
        self.replica_id = None if replica_id is None else int(replica_id)
        self.sink_name = str(sink_name)
        self._seed = int(seed)

        buckets = tuple(sorted(int(b) for b in prefill_buckets))
        # longest generation the pool must hold: the largest admissible
        # prompt plus the full output budget, capped by the model's
        # position table
        self.max_context = min(
            int(arch.max_len), buckets[-1] + 1 + self.max_new_tokens
        )
        max_pages_per_seq = pages_needed(self.max_context, self.page_size)
        if kv_pages < max_pages_per_seq:
            raise ValueError(
                f"kv_pages={kv_pages} cannot hold even one worst-case "
                f"sequence ({max_pages_per_seq} pages for "
                f"{self.max_context} positions at page_size "
                f"{self.page_size})"
            )
        # the cache by kind: the MODEL says what a page of each pool holds,
        # in which dtype, and whether its programs take the pools donated
        # (they then write each pool once, after every read: no copy)
        spec = model.cache_spec(self.page_size)
        self.cache_kind = str(spec["kind"])
        self._donate = bool(spec["donate"])
        # a model that holds state a SLOT (beside or instead of pages in some
        # layers) also tells its prefill which slot it fills and how many of
        # the bucket's positions are real
        self._slot_state = bool(spec.get("slots"))
        self._cache = PagedKVCache(
            n_layers=spec.get("paged_layers", arch.n_layers),
            page_size=self.page_size,
            n_pages=int(kv_pages),
            max_seqs=int(max_seqs),
            max_pages_per_seq=max_pages_per_seq,
            k_page=spec["k_page"],
            v_page=spec["v_page"],
            dtype=spec["dtype"],
            kind=self.cache_kind,
            slots=spec.get("slots"),
        )
        self._sched = DecodeScheduler(
            self._cache, prefill_buckets=buckets, mode=mode
        )
        # the router reads eng.buckets[-1] for its backlog math; for a
        # decode member that's the prefill bucket set
        self.buckets = self._sched.buckets

        # two jitted programs (+1 shape per prefill bucket), both routed
        # through the host-side trace counter — ``compile_count`` proves
        # the "len(prefill_buckets) + 1 programs" bound under any
        # request mix (tests/test_decode_engine.py)
        import jax
        import jax.numpy as jnp

        self._trace_count = 0
        seed_const = self._seed

        def _counted_prefill(params, tokens, pages, k_pool, v_pool, *where):
            self._trace_count += 1  # trace-time only, never per call
            # ``where``: (slot, real length) as traced scalars for a model
            # with state a slot, else nothing: one program a bucket
            return model.decode_prefill(
                params, tokens, pages, k_pool, v_pool, *where,
                page_size=self.page_size,
            )

        def _counted_decode(params, k_pool, v_pool, packed):
            self._trace_count += 1  # trace-time only, never per call
            # ONE operand for the step's host arrays (the scheduler's
            # ``step_arrays``), cut apart here: a few static slices
            tables, seq_lens, last, active, temp, it = \
                self._sched.split_step(packed)
            # the sampling key is derived INSIDE the program from the
            # traced iteration counter — deterministic replay, no
            # per-iteration retrace, no host-side key threading
            key = jax.random.fold_in(jax.random.PRNGKey(seed_const), it)
            return model.decode_step(
                params, k_pool, v_pool, tables, seq_lens, last, active != 0,
                jax.lax.bitcast_convert_type(temp, jnp.float32), key,
                page_size=self.page_size,
            )

        self._prefill = jax.jit(
            _counted_prefill, donate_argnums=(3, 4) if self._donate else ())
        self._decode = jax.jit(
            _counted_decode, donate_argnums=(1, 2) if self._donate else ())

        from theanompi_tpu.parallel.recipe import ShardingRecipe

        # declared serving placement (SHARD004's comparison target);
        # ``tmpi serve --decode --shard tensor`` passes the tensor-serve
        # recipe here instead of the replicated default
        self.sharding = sharding if sharding is not None else ShardingRecipe.serve()
        # the KV pool lives where the params live, committed like them:
        # a pool left on the process-default device would change
        # placement (and retrace every program) the first time a step
        # returns it from the params' device
        self._place_pools()

        self._served: Optional[ServedParams] = None
        self._swap_lock = threading.Lock()
        self._q: collections.deque[DecodeSequence] = collections.deque()
        self._cond = threading.Condition()
        self._draining = False
        self._abort_error: Optional[BaseException] = None
        self._stopped = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._batch_s_ewma: Optional[float] = None
        self._iterations = 0
        self._tokens_total = 0
        self._t_started: Optional[float] = None
        self._sink_f = None
        self._sink_lock = threading.Lock()
        self._sink_retired = False
        self._spans_written = False
        self._spans = SpanStore(
            "decode" if self.replica_id is None
            else f"decode/{self.replica_id}")

        self.registry = registry or MetricsRegistry()
        self._h_ttft = self.registry.histogram(
            "tmpi_decode_ttft_seconds",
            help="time to first generated token, submit -> token",
            buckets=LATENCY_BUCKETS,
        )
        self._h_tpot = self.registry.histogram(
            "tmpi_decode_tpot_seconds",
            help="per-output-token latency after the first token",
            buckets=TPOT_BUCKETS,
        )
        self._h_queue_wait = self.registry.histogram(
            "tmpi_decode_queue_wait_seconds",
            help="submit -> the admission that gave the request a slot",
            buckets=LATENCY_BUCKETS,
        )
        self._g_queue = self.registry.gauge(
            "tmpi_decode_queue_depth",
            help="generations waiting for a batch slot",
        )
        self._g_occupancy = self.registry.gauge(
            "tmpi_decode_batch_occupancy",
            help="running sequences / max_seqs of the last iteration",
        )
        self._g_pages_used = self.registry.gauge(
            "tmpi_decode_kv_pages_used", help="KV pool pages reserved"
        )
        self._g_pages_free = self.registry.gauge(
            "tmpi_decode_kv_pages_free", help="KV pool pages on the free-list"
        )
        self._g_step = self.registry.gauge(
            "tmpi_decode_params_step", help="checkpoint step currently served"
        )
        g_pool = self.registry.gauge(
            "tmpi_decode_kv_pool_bytes",
            help="bytes the cache holds, by kind: the two paged pools "
                 "(kind=kv|latent), arrays held a slot (kind=compressed|state)",
        )
        for kind, n in self._cache.pool_bytes_by_kind.items():
            g_pool.set(float(n), kind=kind)
        g_position = self.registry.gauge(
            "tmpi_decode_kv_bytes_per_position",
            help="cache bytes one more position takes over all layers, by kind",
        )
        for kind, n in self._cache.bytes_per_position_by_kind.items():
            g_position.set(float(n), kind=kind)
        # a model whose attention sees a chosen part of the context says
        # which share (host side, from the running lengths)
        self._visible_share = getattr(model, "visible_share", None)
        if self._visible_share is not None:
            self._g_visible = self.registry.gauge(
                "tmpi_decode_visible_context_share",
                help="share of their context the sparse layers' queries of "
                     "the last iteration saw",
            )
        self._c_requests = self.registry.counter(
            "tmpi_decode_requests_total",
            help="generations by outcome "
                 "(status=served|expired|evicted|rejected|failed)",
        )
        self._c_tokens = self.registry.counter(
            "tmpi_decode_tokens_total", help="tokens generated"
        )
        self._c_prefills = self.registry.counter(
            "tmpi_decode_prefills_total",
            help="prompt prefills by length bucket (bucket=N)",
        )
        self._c_evicted = self.registry.counter(
            "tmpi_decode_evicted_total",
            help="running sequences evicted (deadline) — typed, not a drop",
        )
        self._c_reloads = self.registry.counter(
            "tmpi_decode_reloads_total",
            help="checkpoint hot-reloads applied (serve/reload.py)",
        )
        self._c_handover = self.registry.counter(
            "tmpi_decode_handover_total",
            help="waits for a resolved client's next submission before the "
                 "queue was read, by how they ended (outcome=submitted|timed_out)",
        )

    # -- params (surface shared with ServeEngine; router/reloader use it) ---
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
        keep-chain and serve it (same discovery/reshard path as the
        eval engine: serve/reload.py::load_for_serving)."""
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
        """Atomically publish a serving triple; refuses to move the
        served step backward. Same discipline as the eval engine: the
        device placement runs OUTSIDE the swap lock, the step check
        re-runs under it. A generation in flight simply decodes its
        next token with the new params — the KV cache entries written
        under the old params remain valid context (same architecture,
        different weights: exactly the semantics of serving the newer
        checkpoint)."""
        step = int(step)
        current = self._served
        if current is not None and step <= current.step:
            return False
        place = getattr(self.sharding, "place_params", None)
        params = place(params) if place else self.sharding.place_replicated(params)
        model_state = self.sharding.place_replicated(model_state)
        with self._swap_lock:
            current = self._served
            if current is not None and step <= current.step:
                return False
            self._served = ServedParams(params, model_state, step)
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
        """Reloader hook for a verified-then-unloadable checkpoint (the
        TOCTOU race) — counted and recorded, serving never blinks."""
        self._c_reloads.inc(status="failed")
        self._write_record({
            "kind": "reload", "t": time.time(),
            "from_step": int(from_step), "to_step": -1,
            "ok": False, "error": str(error)[:500],
        })

    # -- lifecycle ----------------------------------------------------------
    def _place_pools(self) -> None:
        c = self._cache
        c.k_pool, c.v_pool = self.sharding.place_replicated(
            (c.k_pool, c.v_pool))

    def warmup(self) -> int:
        """AOT-compile every program before the first request: one
        prefill per bucket (pages all-scratch — the warmup K/V land on
        the write-discard page) and the single decode step (all slots
        inactive). Returns the compile count, ==
        ``len(prefill_buckets) + 1`` on a fresh engine."""
        import jax
        import jax.numpy as jnp

        if self._served is None:
            raise RuntimeError("warmup needs params (load_initial first)")
        served = self._served
        c = self._cache
        for b in self.buckets:
            toks = jnp.zeros((b,), jnp.int32)
            pages = jnp.full((b // self.page_size,), c.scratch, jnp.int32)
            where = (np.int32(0), np.int32(0)) if self._slot_state else ()
            out = self._prefill(served.params, toks, pages, c.k_pool, c.v_pool, *where)
            jax.block_until_ready(out)  # compile now, discard scratch writes
            if self._donate:  # the pools went into the call: these are they
                c.k_pool, c.v_pool = out
        nxt, _lg, _k, _v = self._decode(
            served.params, c.k_pool, c.v_pool, self._sched.step_arrays(0))
        np.asarray(nxt)
        if self._donate:
            c.k_pool, c.v_pool = _k, _v
        return self.compile_count

    @property
    def compile_count(self) -> int:
        """Programs compiled so far (trace count across both jits)."""
        return self._trace_count

    def start(self) -> None:
        with self._cond:
            if self._thread is not None:
                raise RuntimeError("engine already started")
            self._t_started = time.monotonic()
            self._thread = threading.Thread(
                target=self._loop, name="tmpi-decode-batcher", daemon=True
            )
        self._thread.start()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: reject new admissions, run every queued
        AND running generation to completion (zero drops — the fleet
        invariant), stop the loop, flush the final ``decode`` record.
        Idempotent."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = None if timeout is None else time.monotonic() + timeout
        drained = True
        if self._thread is not None:
            self._thread.join(
                None if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            drained = not self._thread.is_alive()
        with self._sink_lock:
            first = not self._stopped.is_set()
            self._stopped.set()
        if first and self.obs_dir is not None:
            rec = self.decode_record()
            with self._sink_lock:
                if not self._sink_retired:
                    if self._sink_f is None:
                        os.makedirs(self.obs_dir, exist_ok=True)
                        self._sink_f = open(
                            os.path.join(self.obs_dir, self.sink_name), "a"
                        )
                    self._sink_f.write(json.dumps(rec) + "\n")
                    self._sink_retired = True
                    self._sink_f.close()
                    self._sink_f = None
        # the first drain that saw the loop thread end (one that timed out
        # before it leaves this to the next): the rings stand still
        if drained and self.obs_dir is not None and not self._spans_written:
            self._spans_written = True
            self._write_spans()
        return drained

    def _write_spans(self) -> None:
        """The spans the store still holds, as ``span`` lines of the
        training side's schema (tools/check_obs_schema.py) in
        ``<obs_dir>/spans_rank<r>.jsonl`` (``r``: the replica, else 0),
        where tools/spans_to_trace.py looks: a loop span carries its
        ``iteration`` (``prefill`` also its ``calls``), a request's span
        its ``request`` and the ``cause`` iteration."""
        rank = self.replica_id or 0
        lines = []
        calls = self._spans.counts.get("prefill_calls")
        for name in LOOP_SPANS + REQUEST_SPANS:
            ring = self._spans.span_rings.get(name)
            if ring is None:
                continue
            key = "iteration" if name in LOOP_SPANS else "request"
            numbers, t0s, durs = ring.held()
            causes = ring.cause[numbers % ring.capacity]
            for n, t0, dur, cause in zip(*(a.tolist() for a in (numbers, t0s, durs, causes))):
                rec = {"kind": "span", "name": name, "rank": rank,
                       "t0": t0 * 1e-9, "dur": dur * 1e-9, "depth": 0, key: n}
                if cause >= 0:
                    rec["cause"] = cause
                if name == "prefill" and calls is not None:
                    rec["calls"] = calls.get(n) or 0
                lines.append(json.dumps(rec))
        if lines:
            os.makedirs(self.obs_dir, exist_ok=True)
            path = os.path.join(self.obs_dir, f"spans_rank{rank}.jsonl")
            with open(path, "a") as f:
                f.write("\n".join(lines) + "\n")

    close = drain

    def abort(self, error: Optional[BaseException] = None) -> None:
        """Hard death: stop admitting, reject every queued generation,
        poison the in-flight iteration so running generations reject
        too (the loop's failure path releases their KV pages — the
        free-list stays conserved even through a crash). A fronting
        router re-admits the rejected prompts on healthy replicas."""
        err = error if error is not None else EngineDead("engine aborted")
        with self._cond:
            if self._abort_error is None:
                self._abort_error = err
            self._draining = True
            doomed = list(self._q)
            self._q.clear()
            self._g_queue.set(0.0)
            self._cond.notify_all()
        for seq in doomed:
            seq.future._reject(err)
        if doomed:
            self._c_requests.inc(len(doomed), status="failed")

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def alive(self) -> bool:
        t = self._thread
        return (t is not None and t.is_alive()
                and self._abort_error is None and not self._draining)

    @property
    def queue_depth(self) -> int:
        """Generations waiting for a batch slot (the router's load
        signal): the submit queue plus the scheduler's waiting line."""
        return len(self._q) + self._sched.n_waiting

    @property
    def batch_s_ewma(self) -> Optional[float]:
        """EWMA seconds per decode iteration (prefills included)."""
        return self._batch_s_ewma

    # -- request path -------------------------------------------------------
    def submit(self, x, deadline_ms: Optional[float] = None,
               max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None) -> ServeFuture:
        """Enqueue one prompt (1-D int token ids); returns a future
        resolving to :class:`DecodeResult`. Admission control mirrors
        the eval engine: :class:`EngineOverloaded` /
        :class:`EngineDraining` raise synchronously, deadline expiry
        and eviction surface from ``future.result()`` as
        :class:`DeadlineExceeded`."""
        prompt = np.asarray(x, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D token row, got shape "
                f"{prompt.shape}"
            )
        if prompt.size > self._sched.max_prompt_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens exceeds the largest "
                f"prefill bucket + 1 ({self._sched.max_prompt_len})"
            )
        n_new = self.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        n_new = min(n_new, self.max_context - int(prompt.size))
        if n_new < 1:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to "
                f"generate within max_context {self.max_context}"
            )
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        deadline = (
            time.monotonic() + float(deadline_ms) / 1000.0
            if deadline_ms else None
        )
        fut = ServeFuture()
        # the span clock read beside the future's own monotonic stamp: the
        # queue_wait span's t0 (its duration comes from the stamps)
        t_submit_ns = self._spans.clock_ns()
        seq = DecodeSequence(
            prompt,
            max_new_tokens=n_new,
            temperature=(self.default_temperature if temperature is None
                         else float(temperature)),
            deadline=deadline,
            future=fut,
            t_submit=fut.t_submit,
        )
        seq.t_submit_ns = t_submit_ns
        with self._cond:
            if self._draining:
                self._c_requests.inc(status="rejected")
                raise EngineDraining()
            depth = len(self._q) + self._sched.n_waiting
            if depth >= self.max_queue:
                self._c_requests.inc(status="rejected")
                batch_s = self._batch_s_ewma or 0.05
                # a waiting generation needs ~max_new_tokens iterations
                # once admitted; estimate the backlog in batch rounds
                rounds = -(-depth // self._cache.max_seqs)
                raise EngineOverloaded(
                    depth,
                    retry_after_ms=1000.0 * batch_s
                    * self.max_new_tokens * rounds,
                )
            self._q.append(seq)
            self._g_queue.set(len(self._q) + self._sched.n_waiting)
            self._cond.notify()
        return fut

    def generate(self, x, deadline_ms: Optional[float] = None,
                 max_new_tokens: Optional[int] = None,
                 temperature: Optional[float] = None,
                 timeout: Optional[float] = 60.0) -> DecodeResult:
        """Blocking convenience: submit + wait."""
        return self.submit(
            x, deadline_ms=deadline_ms, max_new_tokens=max_new_tokens,
            temperature=temperature,
        ).result(timeout)

    def infer(self, x, deadline_ms: Optional[float] = None,
              timeout: Optional[float] = 60.0) -> DecodeResult:
        """ServeEngine-signature blocking call (the CLI selftest and
        frontend duck-type this surface)."""
        return self.generate(x, deadline_ms=deadline_ms, timeout=timeout)

    # -- decode loop --------------------------------------------------------
    def _loop(self) -> None:
        spans, sched = self._spans, self._sched
        resolved = 0  # sequences the iteration that just ended took out
        while True:
            # an idle engine's waits land in this span and nowhere else,
            # and the hand-over's
            spans.enter("queue")
            with self._cond:
                if resolved:
                    self._hand_over(resolved)
                while (not self._q and not sched.has_work()
                       and not self._draining):
                    self._cond.wait(0.05)
                if (self._draining and not self._q
                        and not sched.has_work()):
                    spans.leave("queue")
                    return
                while self._q:
                    sched.add(self._q.popleft())
                self._g_queue.set(sched.n_waiting)
            spans.leave("queue", self._iterations)
            before = sched.removed_total
            try:
                self._iteration()
                resolved = sched.removed_total - before
            except BaseException as e:  # noqa: BLE001 — generations must
                # never hang on an engine bug: fail everything this loop
                # owns (releasing its KV pages) and keep the thread
                # alive. An abort poisons the iteration on purpose —
                # those count as failed, not rejected
                spans.abandon()
                self._fail_all(e)
                resolved = 0

    def _hand_over(self, resolved: int) -> None:
        """The hand-over before the queue is read (``self._cond`` held):
        the iteration that just returned resolved ``resolved`` requests,
        and their clients hear of it only now (a future's waiter, or
        whoever wraps the harvest, runs after ``future._resolve``). A
        client that submits again on a resolution needs a few tenths of
        a millisecond of interpreter, which this thread does not let go
        of on its own before it has emptied its queue: the resubmission
        then waits a whole iteration for the slot its predecessor freed.
        So where a submission made now would be admitted at once, wait
        for as many as were resolved, ``HANDOVER_WAIT_S`` at most;
        ``submit()`` notifies. Nothing resolved, a queue that already
        holds a request, a draining engine or no room: no wait."""
        if self._q or self._draining or not self._sched.can_admit():
            return
        deadline = time.monotonic() + HANDOVER_WAIT_S
        while len(self._q) < resolved and not self._draining:
            left = deadline - time.monotonic()
            if left <= 0:
                break
            self._cond.wait(left)
        self._c_handover.inc(
            outcome="submitted" if self._q else "timed_out")

    def _iteration(self) -> None:
        """One continuous-batching iteration, in this order: admit,
        prefill the admitted prompts, fill the step's ONE host buffer
        (``upload``), run the single decode step on it (``dispatch``:
        the call takes the host array as it is, one transfer), block on
        the next-token vector (``drain``), harvest tokens. Exactly ONE host drain point (the
        np.asarray on the next-token vector) — tools/check_hot_loop.py
        HOT004 walks this function. The hand-over to clients that
        resubmit on a resolution comes after this has returned, in
        ``_loop``."""
        import jax.numpy as jnp

        err = self._abort_error
        if err is not None:  # the replica died under this iteration
            raise err
        spans, it = self._spans, self._iterations
        now = time.monotonic()
        served = self._served  # ONE read: the swap point for hot reload
        spans.enter("admit")
        admitted, expired = self._sched.admit(now)
        for seq in expired:
            seq.future._reject(DeadlineExceeded(
                "deadline passed before a decode slot opened"
            ))
            self._c_requests.inc(status="expired")
        for seq in admitted:
            self._note_admitted(seq, it)
        spans.leave("admit", it)
        t0 = time.monotonic()
        c = self._cache
        calls = 0
        spans.enter("prefill")
        for seq in admitted:
            pf = self._sched.prefill_args(seq)
            if pf is None:
                continue  # 1-token prompt: the decode step handles it
            bucket, toks, pages = pf
            where = ((np.int32(seq.slot), np.int32(seq.n_cache))
                     if self._slot_state else ())
            c.k_pool, c.v_pool = self._prefill(
                served.params, jnp.asarray(toks), jnp.asarray(pages),
                c.k_pool, c.v_pool, *where,
            )
            self._c_prefills.inc(bucket=bucket)
            calls += 1
        spans.leave("prefill", it)
        spans.count("prefill_calls", it, calls)
        if not self._sched.running:
            return
        spans.enter("upload")
        # ONE host buffer for the step's operands, handed to the call as it
        # is: the jitted call's own argument path makes the one transfer
        packed = self._sched.step_arrays(it)
        if self._visible_share is not None:
            _, seq_lens, _, active, _, _ = self._sched.split_step(packed)
            self._g_visible.set(self._visible_share(seq_lens[active != 0]))
        spans.leave("upload", it)
        spans.enter("dispatch")
        nxt, _logits, c.k_pool, c.v_pool = self._decode(
            served.params, c.k_pool, c.v_pool, packed)
        spans.leave("dispatch", it)
        spans.enter("drain")
        next_np = np.asarray(nxt)  # the ONE host drain per iteration
        spans.leave("drain", it)
        t_done = time.monotonic()
        err = self._abort_error
        if err is not None:  # abort landed mid-step: nothing resolves
            raise err        # after a death
        self._harvest(next_np, served.step, t_done, t0)

    def _note_admitted(self, seq: DecodeSequence, it: int) -> None:
        """A request got its slot in iteration ``it``: its ``queue_wait``
        span (the scheduler's ``t_admit`` less the future's ``t_submit``,
        both monotonic; ``t0`` from the span clock read at submission)
        and the histogram, once a request."""
        if seq.t_submit is None or seq.t_submit_ns is None:
            return
        wait = max(0.0, seq.t_admit - seq.t_submit)
        self._spans.put("queue_wait", seq.seq_id, seq.t_submit_ns,
                        int(wait * 1e9), cause=it)
        self._h_queue_wait.observe(wait)

    def _harvest(self, next_np: np.ndarray, step: int, t_done: float,
                 t0: float) -> None:
        """Post-step bookkeeping: append tokens, resolve finished
        generations, evict deadline-passed ones (typed — never a
        silent drop), update telemetry."""
        spans, it = self._spans, self._iterations
        spans.enter("harvest")
        n_live = 0
        for slot, seq in list(self._sched.running.items()):
            tok = int(next_np[slot])
            seq.generated.append(tok)
            n_live += 1
            if seq.t_first_token is None:
                seq.t_first_token = t_done
                if seq.t_submit is not None:
                    self._h_ttft.observe(t_done - seq.t_submit)
                    if seq.t_submit_ns is not None:
                        wait = int(max(0.0, seq.t_admit - seq.t_submit) * 1e9)
                        spans.put("first_token", seq.seq_id,
                                  seq.t_submit_ns + wait,
                                  int((t_done - seq.t_admit) * 1e9), cause=it)
            if seq.done:
                self._sched.remove(slot, "finished")
                n = len(seq.generated)
                if n > 1 and seq.t_first_token is not None:
                    self._h_tpot.observe(
                        (t_done - seq.t_first_token) / (n - 1)
                    )
                seq.future._resolve(DecodeResult(
                    np.asarray(seq.generated, np.int32), step
                ))
                self._c_requests.inc(status="served")
        self._tokens_total += n_live
        self._c_tokens.inc(n_live)
        for slot in self._sched.running_deadline_victims(t_done):
            seq = self._sched.remove(slot, "evicted")
            seq.future._reject(DeadlineExceeded(
                f"deadline passed after {len(seq.generated)} of "
                f"{seq.max_new_tokens} tokens — evicted"
            ))
            self._c_evicted.inc()
            self._c_requests.inc(status="evicted")
        self._g_occupancy.set(self._sched.occupancy)
        self._g_pages_used.set(self._cache.pages_used)
        self._g_pages_free.set(self._cache.pages_free)
        batch_s = t_done - t0
        self._batch_s_ewma = (
            batch_s if self._batch_s_ewma is None
            else 0.8 * self._batch_s_ewma + 0.2 * batch_s
        )
        self._iterations += 1
        if self._iterations % self.record_every == 0:
            self._write_record(self.decode_record())
        spans.leave("harvest", it)

    def _fail_all(self, e: BaseException) -> None:
        """Failure path for a poisoned iteration: reject every
        generation the loop owns, RELEASING their KV pages so the
        free-list stays conserved (the chaos oracle checks) and the
        engine can keep serving if the error was input-local — with
        new pools where the failed program had taken them donated."""
        c = self._cache
        if self._donate and c.pools_deleted():
            # the program that raised had taken the pools donated: they
            # are gone. Every sequence that had rows in them is failed
            # below, so fresh zeros are a sound state to serve on
            c.reset_pools()
            self._place_pools()
        failed = 0
        for slot in list(self._sched.running):
            seq = self._sched.remove(slot, "evicted")
            if not seq.future.done():
                seq.future._reject(e)
                failed += 1
        while self._sched.waiting:
            seq = self._sched.waiting.popleft()
            if not seq.future.done():
                seq.future._reject(e)
                failed += 1
        if failed:
            status = "failed" if e is self._abort_error else "rejected"
            self._c_requests.inc(failed, status=status)

    # -- stats / telemetry --------------------------------------------------
    def tokens_per_sec(self) -> Optional[float]:
        if self._t_started is None or not self._tokens_total:
            return None
        dt = time.monotonic() - self._t_started
        return self._tokens_total / dt if dt > 0 else None

    def ttft_ms(self, q: float) -> Optional[float]:
        s = self._h_ttft.quantile(q)
        return None if s is None else 1000.0 * s

    def stats(self) -> dict:
        """Flat numeric snapshot (the ``decode`` record's metrics map;
        every key ``tmpi_decode_``-prefixed — enforced by the schema
        checker)."""
        fl = self._cache.free_list
        out = {
            "tmpi_decode_queue_depth": float(self.queue_depth),
            "tmpi_decode_batch_occupancy": self._sched.occupancy,
            "tmpi_decode_kv_pages_used": float(self._cache.pages_used),
            "tmpi_decode_kv_pages_free": float(self._cache.pages_free),
            "tmpi_decode_kv_pages_out_total": float(fl.pages_out_total),
            "tmpi_decode_kv_pages_in_total": float(fl.pages_in_total),
            "tmpi_decode_kv_pool_bytes": float(self._cache.pool_bytes),
            "tmpi_decode_kv_bytes_per_position": float(
                self._cache.bytes_per_position),
            "tmpi_decode_iterations_total": float(self._iterations),
            "tmpi_decode_tokens_total": float(self._tokens_total),
            "tmpi_decode_served_total": self._c_requests.value(status="served"),
            "tmpi_decode_expired_total": self._c_requests.value(status="expired"),
            "tmpi_decode_evicted_total": self._c_evicted.value(),
            "tmpi_decode_rejected_total":
                self._c_requests.value(status="rejected"),
            "tmpi_decode_failed_total": self._c_requests.value(status="failed"),
            "tmpi_decode_reloads_total": self._c_reloads.value(),
            "tmpi_decode_reload_failures_total":
                self._c_reloads.value(status="failed"),
            "tmpi_decode_handover_submitted_total":
                self._c_handover.value(outcome="submitted"),
            "tmpi_decode_handover_timed_out_total":
                self._c_handover.value(outcome="timed_out"),
        }
        for kind, n in self._cache.pool_bytes_by_kind.items():
            if kind != self.cache_kind:  # held a slot, beside the pages
                out[f"tmpi_decode_{kind}_bytes"] = float(n)
        if self._visible_share is not None:
            out["tmpi_decode_visible_context_share"] = self._g_visible.value()
        tps = self.tokens_per_sec()
        if tps is not None:
            out["tmpi_decode_tokens_per_sec"] = tps
        for name, q in (("p50", 0.5), ("p99", 0.99)):
            ms = self.ttft_ms(q)
            if ms is not None:
                out[f"tmpi_decode_ttft_{name}_ms"] = ms
        tpot = self._h_tpot.quantile(0.5)
        if tpot is not None:
            out["tmpi_decode_tpot_ms"] = 1000.0 * tpot
        wait = self._h_queue_wait.quantile(0.5)
        if wait is not None:
            out["tmpi_decode_queue_wait_p50_ms"] = 1000.0 * wait
        # the loop's seven spans, mean over the last record_every
        # iterations the rings hold (read here, off the iteration's path)
        hi = self._iterations
        for name in LOOP_SPANS:
            ring = self._spans.span_rings.get(name)
            dur = None if ring is None else ring.durations(
                hi - self.record_every, hi)
            if dur is not None and len(dur):
                out[f"tmpi_decode_loop_{name}_ms"] = 1e-6 * float(dur.mean())
        return out

    def decode_record(self) -> dict:
        """The one constructor of a ``kind=decode`` record (schema:
        tools/check_obs_schema.py). Replica members stamp
        ``replica_id``."""
        rec = {"kind": "decode", "t": time.time(),
               "params_step": self.params_step, "cache_kind": self.cache_kind,
               "metrics": self.stats()}
        if self.replica_id is not None:
            rec["replica_id"] = self.replica_id
        return rec

    def _write_record(self, rec: dict) -> None:
        if self.obs_dir is None:
            return
        with self._sink_lock:
            if self._sink_retired:
                return
            if self._sink_f is None:
                os.makedirs(self.obs_dir, exist_ok=True)
                self._sink_f = open(
                    os.path.join(self.obs_dir, self.sink_name), "a"
                )
            self._sink_f.write(json.dumps(rec) + "\n")
            self._sink_f.flush()
