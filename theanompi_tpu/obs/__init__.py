"""Observability subsystem: metrics registry, span tracing, analytic
comm accounting, and run health (heartbeat + stall watchdog).

The reference's observability was ``lib/recorder.py``'s host wall-clock
brackets; on TPU the collective is fused inside one XLA program, so
this package supplies what host brackets cannot (SURVEY.md §5.1,
ISSUE 1):

- :mod:`~theanompi_tpu.obs.metrics` — labeled counters/gauges/
  histograms, Prometheus text exposition + JSONL snapshots;
- :mod:`~theanompi_tpu.obs.spans` — nestable trace spans with a
  per-rank JSONL log and a run-end time-fraction summary;
- :mod:`~theanompi_tpu.obs.comm` — closed-form bytes-on-the-wire per
  step for every sync rule (the comm-side peer of utils/flops.py MFU);
- :mod:`~theanompi_tpu.obs.health` — heartbeat files + a stall
  watchdog that dumps thread stacks and arms a post-mortem device
  trace when the global step stops advancing;
- :mod:`~theanompi_tpu.obs.numerics` — in-graph numerics sentinels
  (grad/update/param norms, fused non-finite count, per-rule
  divergence gauges) + host-side EWMA/NaN anomaly detection evaluated
  at dispatch-drain time;
- :mod:`~theanompi_tpu.obs.flight` — flight recorder: bounded ring of
  the last N drained step records, dumped as an ``anomaly_rank{r}/``
  triage bundle when a sentinel fires or the stall watchdog trips.

:class:`Observability` is the driver-facing facade
(``launch/worker.py``): one object that owns the per-run registry, the
span recorder, the health threads, and the snapshot cadence — and that
collapses to near-zero-cost no-ops when ``obs_dir`` is None, so the
training loop carries no conditionals.

On-disk layout under ``obs_dir`` (schemas:
``theanompi_tpu/tools/check_obs_schema.py``)::

    metrics.jsonl           rank-0 metric snapshots (kind=metrics) +
                            one kind=comm record per run: the engine's
                            declared wire model — rule, wire codec,
                            raw_bytes vs wire_bytes (sustained
                            per-step, fp32 vs post-codec) and their
                            compression_ratio; on a multislice mesh the
                            comm record also splits the raw AND
                            effective bytes by link class — ici_bytes /
                            dcn_bytes (effective, post-codec on the DCN
                            hop) and raw_ici_bytes / raw_dcn_bytes —
                            matching the tmpi_comm_ici_bytes_per_step /
                            tmpi_comm_dcn_bytes_per_step (+ raw_*)
                            gauges and the achieved tmpi_comm_ici_gbps /
                            tmpi_comm_dcn_gbps pair the step cadence
                            refreshes; snapshots also carry
                            the tmpi_comm_raw_bytes_per_step /
                            tmpi_comm_compression_ratio /
                            tmpi_comm_gbps_raw gauges next to the
                            effective tmpi_comm_* family; an elastic
                            resume that resharded a checkpoint onto a
                            changed mesh adds one kind=reshard record
                            (from_world/to_world, wall seconds, leaf
                            count, per-replica batch) next to the
                            tmpi_reshard_seconds / tmpi_reshards_total
                            gauges; runs whose engine declared a cost
                            model (obs/attribution.py) add one
                            kind=profile record per snapshot — the
                            step-time attribution: measured
                            step_seconds, the compute/comm/host/
                            residual fractions (sum 1.0 by
                            construction), roofline classification
                            (compute/hbm/comm/host-bound), mfu (or
                            mfu_calibrated on spec-less devices) and
                            achieved hbm_gbps — next to the live
                            tmpi_mfu / tmpi_hbm_gbps /
                            tmpi_step_*_frac gauges the dispatcher's
                            drain cadence refreshes; a `tmpi preflight`
                            run with --obs-dir appends one
                            kind=preflight record (model/engine/codec/
                            fused config, PREDICTED per-device
                            peak_bytes from the lowered-not-executed
                            step, budget + fit verdict when a budget
                            exists) next to a snapshot carrying the
                            tmpi_preflight_peak_bytes /
                            tmpi_preflight_fit gauges; runs
                            with a checkpoint scrubber active
                            (--scrub-interval, or the supervisor's
                            retry-time pass) add one kind=scrub record
                            per pass that ran — members checked,
                            corrupt count, the quarantined filenames
                            (comma-joined), pass seconds — next to the
                            tmpi_scrub_checked / tmpi_scrub_runs_total
                            / tmpi_scrub_quarantined_total gauges; a
                            `tmpi lint --obs-dir` run appends one
                            kind=shard record per analyzed engine x
                            codec x fused config (tools/analyze/
                            sharding.py): leaf counts, declared-vs-
                            compiled mismatches, and the GSPMD-inserted
                            hidden-collective bytes next to the
                            compiled/traced/declared wire totals —
                            the sharding analyzer's lint-report line;
                            the model-drift watchdog (obs/drift.py)
                            appends change-gated kind=drift records —
                            per-model EWMA relative error of predicted
                            vs measured (model_err_cost / model_err_
                            traffic / model_err_memory, matching the
                            tmpi_model_err_* gauges),
                            the worst-offending component per model
                            (per-link for traffic, per-leaf-family for
                            memory), the tolerance band, and the
                            breached sources comma-joined — one line
                            whenever an EWMA moves at the third
                            decimal or the breached set changes
    chaos.jsonl             chaos campaign log (tools/chaos.py, written
                            under the campaign's --out dir): one
                            kind=chaos record per fuzzed fault
                            schedule — seed, config, the schedule
                            itself, ok/violations verdict from the
                            invariant oracle, run count, and (for a
                            failing schedule) the shrunken minimal
                            repro as a ready-to-paste --inject-fault
                            line
    metrics.prom            rank-0 Prometheus text exposition (atomic)
    spans_rank{r}.jsonl     per-rank span + span_summary lines
    heartbeat_rank{r}.json  per-rank liveness (atomic rewrite; carries
                            dispatch_in_flight + last_drained_step so a
                            wedged device program — drains stop, ring
                            full — reads apart from a stalled host
                            driver, whose dispatches stop too)
    stall_rank{r}.json/.txt stall watchdog reports (thread stacks)
    postmortem_rank{r}/     jax.profiler trace armed at stall time
    numerics_rank{r}.jsonl  kind=numerics sentinel rows (one per
                            drained numerics step: tmpi gauge values
                            under ``metrics``, non-finite keys named in
                            ``nonfinite_keys``) + kind=anomaly records
                            + kind=rollback records (one per
                            ``--on-anomaly rollback`` restore: the
                            anomalous step, the verified checkpoint
                            step restored, budget left, batches
                            skipped)
    supervisor.jsonl        kind=retry records from the run supervisor
                            (launch/supervisor.py): one per failed or
                            preempted attempt — attempt index, the
                            verified resume-from step, the error, the
                            backoff applied, and the attempt's device
                            world size; elastic supervision adds one
                            kind=topology record per attempt (world +
                            prev_world: the probed device count each
                            attempt ran in, so the file alone shows
                            topology across retries); the supervisor
                            also appends a final kind=metrics snapshot
                            (source="supervisor") carrying
                            tmpi_retries_total to metrics.jsonl
    fleet.jsonl             fleet telemetry plane (obs/fleet.py): one
                            kind=fleet record per CHANGED merged view
                            (fleet step advance, or the straggler/
                            frozen/missed/skewed rank sets changing) —
                            fleet max step + spread, the step-time
                            distribution over ranks (min/p50/p99/max
                            of each rank's EWMA), slowest rank,
                            rank-id flag lists comma-joined, MFU
                            min/median, comm GB/s with its link class
                            (ici, or dcn on a multislice mesh).
                            Written only by a record-writing
                            FleetTailer — in practice the chief's
                            fleet exporter (obs/exporter.py), started
                            chief-only by --fleet-exporter-port (or
                            once per supervised run, outside the
                            retry loop) and stopped in the worker/
                            supervisor shutdown path after obs.close();
                            its tmpi-fleet-tail thread tails every
                            per-rank stream above byte-offset-
                            incrementally and its tmpi-fleet-exporter
                            thread serves /metrics (tmpi_fleet_*
                            Prometheus), /fleet.json and /healthz.
                            `tmpi top` reads the same streams but
                            NEVER writes this file (viewers must not
                            grow the dir they watch)
    serve.jsonl             serving engine telemetry (serve/engine.py,
                            written when ``tmpi serve`` runs with
                            --obs-dir): periodic + drain-time
                            kind=serve stats records (params step,
                            tmpi_serve_* latency p50/p99, queue depth,
                            batch fill, request totals) + one
                            kind=reload record per checkpoint
                            hot-reload the engine applied
    serve_r{N}.jsonl        per-replica member telemetry when ``tmpi
                            serve --replicas N`` runs a fleet
                            (serve/router.py): the same kind=serve
                            records as serve.jsonl, each stamped with
                            its ``replica_id`` — one file per member,
                            restarted members append to their
                            predecessor's file
    router.jsonl            replica-group router stream
                            (serve/router.py): kind=router health
                            transitions (healthy→down→restarting→
                            healthy), failover records (the in-flight
                            request's from/to replica), restart /
                            restart_failed records with the
                            decorrelated-jitter backoff drawn, drop
                            records (terminal failover failures — the
                            chaos oracle's zero-drop invariant watches
                            these), the drain-time kind=router
                            snapshot carrying the tmpi_router_* gauge
                            family, and one kind=reload record per
                            CENTRAL hot-reload fanned out to the
                            fleet; ``tmpi report`` adopts these into
                            its causal timeline (a replica restart
                            adopts the crash/failover chain that
                            preceded it)
    anomaly_rank{r}/        flight-recorder triage bundle (ring.jsonl,
                            report.json, stacks.txt, span_summary.json,
                            optional state/ checkpoint + postmortem/
                            trace) — written once per run at the FIRST
                            anomaly; a stall-watchdog trip writes its
                            own anomaly_rank{r}-stall/ bundle, and a
                            model-drift tolerance breach (obs/drift.py)
                            its own anomaly_rank{r}-drift/ bundle, so
                            neither consumes the anomaly's forensic
                            budget

``tmpi report OBS_DIR`` (tools/report.py) is the read-only post-mortem
over everything above: it merges every per-rank stream into one
monotonic event timeline, causally groups incidents (a retry adopts the
crash/anomaly/reshard evidence that precedes it), and renders the run
summary + drift trajectory + final verdict — like ``tmpi top``, it
never writes the dir it reads.

Every file above is schema-linted by ``tmpi lint`` (tools/lint.py),
whose ``--json`` report carries one SCHEMA001 finding per invalid
record — the same pass that statically cross-checks the declared
``kind=comm`` wire models against each engine's traced collective
schedule (rules SPMD101/SPMD102), so the telemetry this layout
promises cannot silently drift from the programs that emit it.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Optional

from theanompi_tpu.obs import spans as _spans_mod
from theanompi_tpu.obs.comm import (  # noqa: F401
    TrafficModel,
    bsp_traffic,
    easgd_traffic,
    gosgd_traffic,
    nd_traffic,
    pytree_num_elements,
    zero1_traffic,
)
from theanompi_tpu.obs.drift import DriftWatchdog  # noqa: F401
from theanompi_tpu.obs.flight import FlightRecorder, sanitize_record  # noqa: F401
from theanompi_tpu.obs.health import Heartbeat, StallWatchdog  # noqa: F401
from theanompi_tpu.obs.metrics import (  # noqa: F401
    REGISTRY,
    MetricsRegistry,
)
from theanompi_tpu.obs.numerics import (  # noqa: F401
    AnomalyDetector,
    NumericsAnomaly,
    NumericsModel,
    RollbackRequested,
)
from theanompi_tpu.obs.spans import SpanRecorder, obs_span  # noqa: F401

ANOMALY_POLICIES = ("record", "dump", "halt", "rollback")


class Observability:
    """Per-run facade over the obs modules (see module docstring).

    ``snapshot_freq``: write a metrics snapshot (JSONL + prom rewrite)
    every N completed steps; 0 = only at epoch boundaries/close (the
    driver calls :meth:`snapshot` at epoch end regardless).
    ``stall_timeout``: seconds without step progress before the
    watchdog fires; 0 disables it. Set it ABOVE the worst expected
    compile/eval pause — the watchdog only learns of progress through
    :meth:`on_step`, so a first-epoch XLA compile longer than the
    timeout reads as a stall.
    """

    def __init__(
        self,
        obs_dir: Optional[str],
        *,
        rank: int = 0,
        stall_timeout: float = 0.0,
        snapshot_freq: int = 0,
        heartbeat_interval: float = 5.0,
        arm_profiler: bool = True,
        numerics_freq: int = 0,
        flight_window: int = 64,
        on_anomaly: str = "dump",
        drift_tolerance: float = 0.25,
    ):
        if on_anomaly not in ANOMALY_POLICIES:
            raise ValueError(
                f"on_anomaly must be one of {ANOMALY_POLICIES}, "
                f"got {on_anomaly!r}"
            )
        self.obs_dir = obs_dir
        self.rank = rank
        self.enabled = obs_dir is not None
        self.snapshot_freq = max(0, int(snapshot_freq))
        self.numerics_freq = max(0, int(numerics_freq))
        self.on_anomaly = on_anomaly
        self.registry = MetricsRegistry()
        self.spans: Optional[SpanRecorder] = None
        self.heartbeat: Optional[Heartbeat] = None
        self.watchdog: Optional[StallWatchdog] = None
        self.traffic: Optional[TrafficModel] = None
        self.numerics: Optional[NumericsModel] = None
        self.flight: Optional[FlightRecorder] = None
        # step-time attribution (obs/attribution.py): the engine's
        # compiled-step cost model, the dispatcher handle the live
        # host-blocked fraction reads off, and the newest attribution
        # (refreshed at each drain sync, emitted at snapshot time)
        self.cost = None
        self._disp = None
        self._host_mark: Optional[tuple] = None  # (blocked_s, wall_t)
        self._last_attr = None
        # model-drift watchdog (obs/drift.py): per-model EWMA relative
        # error of predicted vs measured, refreshed at the same drain
        # cadence as attribution; fed the memory_model() declaration via
        # set_memory_model. _last_step gives its records a step number
        # (note_step_seconds arrives from the dispatcher without one).
        self.memory = None
        self.drift = DriftWatchdog(tolerance=drift_tolerance, rank=rank)
        self._last_step = 0
        # detection is a host-side float check per drained row — active
        # whenever sentinels are requested, even with no obs_dir (the
        # halt policy must work without telemetry output)
        self.detector = (
            AnomalyDetector() if self.numerics_freq > 0 else None
        )
        self.anomaly_count = 0
        self._anomaly_lines = 0
        self._anomaly_lines_max = 200  # NaN persists once params poison:
        # cap the per-rank anomaly log rather than writing one line per
        # step for the rest of the run
        self._metrics_f = None
        # serializes metrics.jsonl writes: the checkpoint scrubber's
        # kind=scrub records arrive from its background thread while
        # the driver thread snapshots
        self._metrics_lock = threading.Lock()
        self._numerics_f = None
        self._prom_path = None
        self._last_snapshot_step = 0
        self._closed = False
        if not self.enabled:
            return
        os.makedirs(obs_dir, exist_ok=True)
        self.spans = SpanRecorder(
            os.path.join(obs_dir, f"spans_rank{rank}.jsonl"), rank=rank
        )
        # install as the process-current recorder so deep layers
        # (utils/checkpoint.py, data/loader.py) can open spans without
        # plumbing a handle through every signature
        _spans_mod.set_current(self.spans)
        if rank == 0:
            # one metrics sink per run (reference: rank-0 recorder save)
            self._metrics_f = open(os.path.join(obs_dir, "metrics.jsonl"), "a")
            self._prom_path = os.path.join(obs_dir, "metrics.prom")
        if flight_window and flight_window > 0:
            self.flight = FlightRecorder(
                obs_dir, rank=rank, window=flight_window,
                arm_profiler=arm_profiler,
            )
            self.flight.spans = self.spans
        self.heartbeat = Heartbeat(obs_dir, rank=rank,
                                   interval=heartbeat_interval)
        if stall_timeout and stall_timeout > 0:
            flight = self.flight

            def on_stall(report: dict) -> None:
                # a tripped watchdog is a flight-dump trigger too: the
                # ring holds the last healthy steps before the hang.
                # No state save (a wedged device cannot be fetched) and
                # no second profiler arm (the watchdog armed one).
                if flight is not None:
                    flight.dump("stall", step=report.get("step"),
                                include_state=False, arm_profiler=False)

            self.watchdog = StallWatchdog(
                stall_timeout, obs_dir, rank=rank, arm_profiler=arm_profiler,
                on_stall=on_stall,
            )

    # -- driver hooks --------------------------------------------------------
    def set_traffic_model(self, tm: Optional[TrafficModel]) -> None:
        """Record the active sync rule's analytic wire model (engine-
        declared; see each engine's ``traffic_model``) as gauges, so
        every snapshot carries the per-step comm bytes next to the
        measured throughput — raw AND effective (post-codec), plus one
        ``kind=comm`` JSONL record naming the codec (strings cannot
        ride the numeric gauge map)."""
        self.traffic = tm
        if tm is None or not self.enabled:
            return
        for key, value in tm.as_metrics().items():
            self.registry.gauge(
                f"tmpi_{key}",
                help=f"analytic {tm.rule} wire model (obs/comm.py)",
            ).set(value)
        self.registry.gauge(
            "tmpi_comm_n_workers", help="sync-rule worker count"
        ).set(tm.n_workers)
        if self._metrics_f is not None:
            # one comm record per declaration (schema:
            # tools/check_obs_schema.py kind=comm): the codec proof line
            # plot_history reads back
            import json as _json
            import time as _time

            line = _json.dumps({"t": _time.time(), **tm.as_record()})
            # under the sink lock: the scrubber thread's kind=scrub
            # records share this file (RACE002 — a lock only some
            # writers take protects nothing)
            with self._metrics_lock:
                if not self._closed and self._metrics_f is not None:
                    self._metrics_f.write(line + "\n")
                    self._metrics_f.flush()

    def set_numerics_model(self, nm: Optional["NumericsModel"]) -> None:
        """Record the active rule's numerics declaration (engine-
        declared ``numerics_model()``, the ``traffic_model`` peer) as
        gauges, so snapshots say which sentinels ride the steps and
        whether a divergence gauge exists for this rule."""
        self.numerics = nm
        if nm is None or not self.enabled:
            return
        for key, value in nm.as_metrics().items():
            self.registry.gauge(
                f"tmpi_{key}",
                help=f"{nm.rule} numerics declaration (obs/numerics.py)",
            ).set(value)
        self.registry.gauge(
            "tmpi_numerics_freq",
            help="sentinel cadence (steps; 0 = numerics off)",
        ).set(self.numerics_freq)

    def set_cost_model(self, cm) -> None:
        """Record the engine's compiled-step cost model (utils/flops.py
        ``CostModel``, engine-declared via ``cost_model()``) as static
        ``tmpi_cost_*`` gauges and arm the live attribution path: every
        dispatcher drain sync then refreshes ``tmpi_mfu`` /
        ``tmpi_hbm_gbps`` / ``tmpi_step_*_frac`` (obs/attribution.py)
        from values the drain already fetched — zero new host syncs."""
        self.cost = cm
        if cm is None or not self.enabled:
            return
        for key, value in cm.as_metrics().items():
            self.registry.gauge(
                f"tmpi_{key}",
                help="compiled-step cost model (utils/flops.py)",
            ).set(value)

    def set_memory_model(self, mm) -> None:
        """Record the engine's declared state residency (utils/flops.py
        ``MemoryModel``, engine-declared via ``memory_model()``) as
        static ``tmpi_memory_*`` gauges, and hand it to the drift
        watchdog as the predicted HBM high-water its measured
        counterpart (``device.memory_stats()``) is diffed against."""
        self.memory = mm
        if mm is None or not self.enabled:
            return
        self.registry.gauge(
            "tmpi_memory_state_bytes_per_device",
            help="declared per-device persistent state bytes "
                 "(utils/flops.py MemoryModel)",
        ).set(int(mm.state_bytes_per_device))
        self.registry.gauge(
            "tmpi_memory_n_devices", help="memory-model device count",
        ).set(int(mm.n_devices))

    def set_flight_state_saver(self, saver) -> None:
        """Install the driver's ``saver(dump_dir)`` that checkpoints the
        current train state into an anomaly bundle (skipped for
        stall-triggered dumps — a wedged device cannot be fetched)."""
        if self.flight is not None:
            self.flight.state_saver = saver

    def attach_dispatcher(self, disp) -> None:
        """Expose the dispatch pipeline's live counters through the
        heartbeat: ``dispatch_in_flight`` + ``last_drained_step`` let a
        stall-report reader tell a wedged DEVICE program (dispatches
        advance then stop with the ring pinned full) from a stalled
        HOST driver (dispatches stop, in-flight falls to zero)."""
        # also the live host-blocked source for step attribution: the
        # drain-window delta of host_blocked_s is the measured per-step
        # host tax (obs/attribution.py books it as the host fraction)
        self._disp = disp
        if self.heartbeat is not None:
            self.heartbeat.set_extra(
                lambda: {"dispatch_in_flight": int(disp.in_flight),
                         "last_drained_step": int(disp.last_drained_step)}
            )

    def on_row(self, step: int, metrics: dict, numerics: dict) -> None:
        """Per drained row (utils/dispatch.py ``on_row``): feed the
        flight ring, refresh the sentinel gauges, and run anomaly
        detection — all on host floats the drain already fetched, so
        the hot loop gains zero syncs. Raises :class:`NumericsAnomaly`
        under ``--on-anomaly halt`` (after the dump landed)."""
        rec = sanitize_record(self.rank, step, {**metrics, **numerics})
        if self.flight is not None:
            self.flight.record(rec)
        if numerics and self.enabled:
            for k, v in numerics.items():
                self.registry.gauge(
                    f"tmpi_{k}", help="in-graph numerics sentinel "
                                      "(obs/numerics.py)"
                ).set(v)
        if numerics:
            self._write_numerics_line(rec)
        if self.detector is None:
            return
        anomalies = self.detector.observe(step, metrics, numerics)
        if anomalies:
            self._handle_anomalies(step, anomalies)

    def check_val_metrics(self, epoch: int, step: int, metrics: dict) -> None:
        """Epoch-end hook: a non-finite validation metric is an anomaly
        too (a train-side NaN can slip between sentinel steps when
        ``--numerics-freq > 1``; the val epoch always sees it)."""
        if self.detector is None:
            return
        import math as _math

        bad = {k: v for k, v in metrics.items()
               if not _math.isfinite(float(v))}
        if bad:
            self._handle_anomalies(step, [
                {"metric": f"val_{k}", "reason": "nonfinite",
                 "value_repr": repr(float(v)), "step": int(step),
                 "epoch": int(epoch)}
                for k, v in bad.items()
            ])

    def _numerics_sink(self):
        """Lazy-opened per-rank numerics/anomaly JSONL (shared by the
        sentinel-row and anomaly-record writers so the two streams can
        never diverge into different files)."""
        if self._numerics_f is None:
            self._numerics_f = open(
                os.path.join(self.obs_dir,
                             f"numerics_rank{self.rank}.jsonl"), "a"
            )
        return self._numerics_f

    def _write_numerics_line(self, rec: dict) -> None:
        if not self.enabled or self._closed:
            return
        import json as _json

        f = self._numerics_sink()
        f.write(_json.dumps(rec) + "\n")
        f.flush()

    def _handle_anomalies(self, step: int, anomalies: list) -> None:
        self.anomaly_count += len(anomalies)
        if self.enabled:
            self.registry.counter(
                "tmpi_anomalies_total",
                help="numerics anomalies detected at drain time",
            ).inc(len(anomalies))
        import json as _json
        import time as _time

        for a in anomalies:
            if self._anomaly_lines >= self._anomaly_lines_max:
                break
            self._anomaly_lines += 1
            line = {"kind": "anomaly", "rank": self.rank, "t": _time.time(),
                    "policy": self.on_anomaly, **a}
            if self.enabled and not self._closed:
                f = self._numerics_sink()
                f.write(_json.dumps(line) + "\n")
                f.flush()
            else:
                print(f"[rank {self.rank}] numerics anomaly: {line}",
                      file=sys.stderr, flush=True)
        if self.on_anomaly in ("dump", "halt", "rollback") and self.flight is not None:
            self.flight.dump("anomaly", step=step, anomalies=anomalies)
        if self.on_anomaly == "rollback":
            # the driver catches this, restores the last verified
            # checkpoint, and keeps training within its rollback budget
            # (launch/worker.py); escaping it degrades to halt semantics
            raise RollbackRequested(step, anomalies)
        if self.on_anomaly == "halt":
            names = sorted({a["metric"] for a in anomalies})
            raise NumericsAnomaly(
                f"numerics anomaly at step {step}: {names} "
                f"({len(anomalies)} trigger(s); triage bundle: "
                f"{self.flight.dir if self.flight else 'no obs_dir'})"
            )

    def note_reshard(self, step: int, from_world: int, to_world: int,
                     seconds: float, leaves: int,
                     per_replica_batch: Optional[int] = None) -> None:
        """Driver hook (elastic resume, launch/worker.py): one
        checkpoint was resharded onto a different mesh. Sets the
        ``tmpi_reshard_seconds`` gauge, counts ``tmpi_reshards_total``,
        and writes a ``kind=reshard`` JSONL record into metrics.jsonl
        (rank 0) — the per-run proof line the elastic acceptance test
        reads back."""
        if self.enabled:
            self.registry.gauge(
                "tmpi_reshard_seconds",
                help="wall seconds of the last checkpoint reshard "
                     "(elastic resume, utils/checkpoint.load_resharded)",
            ).set(float(seconds))
            self.registry.gauge(
                "tmpi_reshard_world",
                help="device world size after the last elastic reshard",
            ).set(int(to_world))
            self.registry.counter(
                "tmpi_reshards_total",
                help="checkpoints resharded onto a changed mesh "
                     "(elastic resume)",
            ).inc()
        import json as _json
        import time as _time

        line = {"kind": "reshard", "rank": self.rank, "t": _time.time(),
                "step": int(step), "from_world": int(from_world),
                "to_world": int(to_world), "seconds": float(seconds),
                "leaves": int(leaves)}
        if per_replica_batch is not None:
            line["per_replica_batch"] = int(per_replica_batch)
        if self._metrics_f is not None and not self._closed:
            # same sink lock as note_scrub/snapshot: the background
            # scrubber writes this file concurrently
            with self._metrics_lock:
                if not self._closed and self._metrics_f is not None:
                    self._metrics_f.write(_json.dumps(line) + "\n")
                    self._metrics_f.flush()
        else:
            print(f"[rank {self.rank}] elastic reshard: {line}",
                  file=sys.stderr, flush=True)

    def note_scrub(self, result: dict) -> None:
        """Scrubber hook (utils/checkpoint.CheckpointScrubber
        ``on_result``): one keep-chain scrub pass finished. Refreshes
        the ``tmpi_scrub_*`` gauges/counters and writes a ``kind=scrub``
        JSONL record into metrics.jsonl (rank 0) — called from the
        scrubber's background thread, so the metrics sink write is
        lock-serialized against driver-thread snapshots."""
        if self.enabled:
            self.registry.gauge(
                "tmpi_scrub_checked",
                help="keep-chain members verified by the last scrub "
                     "pass (utils/checkpoint.scrub_checkpoint_dir)",
            ).set(int(result["checked"]))
            self.registry.gauge(
                "tmpi_scrub_last_seconds",
                help="wall seconds of the last scrub pass",
            ).set(float(result["seconds"]))
            self.registry.counter(
                "tmpi_scrub_runs_total", help="scrub passes completed",
            ).inc()
            if result["corrupt"]:
                self.registry.counter(
                    "tmpi_scrub_quarantined_total",
                    help="corrupt checkpoint members moved to "
                         "quarantine/ by the scrubber",
                ).inc(int(result["corrupt"]))
        import json as _json
        import time as _time

        line = {"kind": "scrub", "rank": self.rank, "t": _time.time(),
                "checked": int(result["checked"]),
                "corrupt": int(result["corrupt"]),
                "quarantined": ",".join(result["quarantined"]),
                "seconds": float(result["seconds"])}
        if self._metrics_f is not None and not self._closed:
            with self._metrics_lock:
                if not self._closed:
                    self._metrics_f.write(_json.dumps(line) + "\n")
                    self._metrics_f.flush()
        elif result["corrupt"]:
            print(f"[rank {self.rank}] checkpoint scrub: {line}",
                  file=sys.stderr, flush=True)

    def note_rollback(self, anomaly_step: int, restore_step: int,
                      budget_left: int, skipped: int = 0) -> None:
        """Driver hook (``--on-anomaly rollback``, launch/worker.py):
        one restore happened. Counts ``tmpi_rollbacks_total``, writes a
        ``rollback`` JSONL record next to the anomaly records, and
        RESETS the anomaly detector — its EWMA baselines were fed by
        the poisoned steps the restore just erased, and the replayed
        steps must re-warm from clean values."""
        if self.enabled:
            self.registry.counter(
                "tmpi_rollbacks_total",
                help="anomaly rollbacks: restores of the last verified "
                     "checkpoint (--on-anomaly rollback)",
            ).inc()
        if self.detector is not None:
            self.detector = AnomalyDetector()
        import time as _time

        line = {"kind": "rollback", "rank": self.rank, "t": _time.time(),
                "step": int(anomaly_step), "restore_step": int(restore_step),
                "budget_left": int(budget_left), "skipped": int(skipped)}
        if self.enabled and not self._closed:
            self._write_numerics_line(line)
        else:
            print(f"[rank {self.rank}] anomaly rollback: {line}",
                  file=sys.stderr, flush=True)

    def on_step(self, step: int,
                step_seconds: Optional[float] = None) -> None:
        """Per completed dispatch: advance health + comm accounting."""
        self._last_step = int(step)
        if self.heartbeat is not None:
            self.heartbeat.set_step(step)
        if self.watchdog is not None:
            self.watchdog.notify_step(step)
        if not self.enabled:
            return
        self.registry.counter(
            "tmpi_steps_total", help="completed training steps"
        ).inc()
        if self.traffic is not None:
            per_step = self.traffic.bytes_per_step_amortized
            self.registry.counter(
                "tmpi_comm_bytes_total",
                help="cumulative analytic per-device wire bytes",
            ).inc(per_step)
            if step_seconds:
                gbps = self.traffic.achieved_gbps(step_seconds)
                if gbps is not None:
                    self._set_gbps_gauges(gbps, step_seconds)
        if (
            self.snapshot_freq
            and step - self._last_snapshot_step >= self.snapshot_freq
        ):
            self.snapshot(step=step)

    def note_step_seconds(self, per_step_seconds: Optional[float]) -> None:
        """Refresh the achieved-GB/s gauge — and, when the engine
        declared a cost model, the live MFU / HBM / step-fraction
        attribution gauges — from an amortized per-step time
        (utils/dispatch.py's spaced syncs). Under deferred dispatch
        :meth:`on_step` no longer knows the step time at push time —
        the dispatcher calls this at each sync point instead, so the
        gauges carry the same analytic-models / measured-time reading
        sync mode produced, just on the sync cadence (no new host
        syncs: every input is already host-side)."""
        if not self.enabled or not per_step_seconds:
            return
        if self.traffic is not None:
            gbps = self.traffic.achieved_gbps(per_step_seconds)
            if gbps is not None:
                self._set_gbps_gauges(gbps, per_step_seconds)
        # one host-frac read per drain: _live_host_frac CONSUMES the
        # dispatcher mark, so attribution and the drift watchdog must
        # share the same measured window
        host_frac = self._live_host_frac()
        if self.cost is not None:
            self._note_attribution(per_step_seconds, host_frac)
        self._note_drift(per_step_seconds, host_frac)

    def _live_host_frac(self) -> Optional[float]:
        """Host-blocked fraction of the wall since the previous drain
        sync (dispatcher cumulative counter deltas — measured, free)."""
        import time as _time

        if self._disp is None:
            return None
        now = _time.perf_counter()
        blocked = float(self._disp.host_blocked_s)
        mark, self._host_mark = self._host_mark, (blocked, now)
        if mark is None or now <= mark[1]:
            return None
        return max(0.0, min(1.0, (blocked - mark[0]) / (now - mark[1])))

    def _note_attribution(self, per_step_seconds: float,
                          host_frac: Optional[float]) -> None:
        """Refresh the live attribution gauges (obs/attribution.py) and
        keep the newest decomposition for the snapshot-time
        ``kind=profile`` record. Pure host-side float math per drain."""
        from theanompi_tpu.obs.attribution import attribute_step

        try:
            attr = attribute_step(
                per_step_seconds, cost=self.cost, traffic=self.traffic,
                host_frac=host_frac,
            )
        except Exception:  # noqa: BLE001 — gauges must never kill a drain
            return
        self._last_attr = attr
        for key, value in attr.as_metrics().items():
            self.registry.gauge(
                f"tmpi_{key}",
                help="step-time attribution (obs/attribution.py)",
            ).set(value)

    def _note_drift(self, per_step_seconds: float,
                    host_frac: Optional[float]) -> None:
        """Feed the model-drift watchdog (obs/drift.py) one drain's
        measurements: refresh the ``tmpi_model_err_*`` gauges, append
        the change-gated ``kind=drift`` record (rank 0), and on a
        tolerance breach write a ``drift`` anomaly line + flight bundle
        (``anomaly_rank{r}-drift/``). Runs with ANY subset of the three
        models declared — drift needs no cost model to watch traffic."""
        if (self.cost is None and self.traffic is None
                and self.memory is None):
            return
        try:
            record, breaches = self.drift.observe(
                per_step_seconds, step=self._last_step,
                cost=self.cost, traffic=self.traffic, memory=self.memory,
                host_frac=host_frac,
            )
            for key, value in self.drift.as_metrics().items():
                self.registry.gauge(
                    f"tmpi_{key}",
                    help="EWMA |predicted-measured|/measured of the "
                         "analytic model (obs/drift.py)",
                ).set(value)
        except Exception:  # noqa: BLE001 — gauges must never kill a drain
            return
        import json as _json
        import time as _time

        if record is not None and self._metrics_f is not None \
                and not self._closed:
            line = _json.dumps({**record, "t": _time.time()})
            with self._metrics_lock:
                if not self._closed and self._metrics_f is not None:
                    self._metrics_f.write(line + "\n")
                    self._metrics_f.flush()
        if not breaches:
            return
        anomalies = [
            {"metric": f"model_err_{src}", "reason": "drift",
             "value_repr": repr(float(self.drift.ewma[src])),
             "tolerance": self.drift.tolerance,
             "worst": str(self.drift.worst[src] or ""),
             "step": self._last_step}
            for src in breaches
        ]
        for a in anomalies:
            line = {"kind": "anomaly", "rank": self.rank,
                    "t": _time.time(), "policy": "record", **a}
            if not self._closed:
                f = self._numerics_sink()
                f.write(_json.dumps(line) + "\n")
                f.flush()
        self.registry.counter(
            "tmpi_drift_breaches_total",
            help="model-drift tolerance crossings (obs/drift.py)",
        ).inc(len(anomalies))
        if self.flight is not None:
            # own bundle dir (anomaly_rank{r}-drift/): a drifted model
            # is a finding, not a numerics failure — it must not spend
            # the anomaly path's once-per-run forensic budget
            self.flight.dump("drift", step=self._last_step,
                             anomalies=anomalies, include_state=False)

    def _set_gbps_gauges(self, gbps: float,
                         step_seconds: Optional[float] = None) -> None:
        """Effective GB/s gauge, plus the raw (uncompressed-equivalent)
        companion whenever a codec shrinks the wire — the pair is what
        makes codec runs visually distinguishable in plot_history's
        comm panel. On a multislice model the per-link-class pair
        (``tmpi_comm_ici_gbps`` / ``tmpi_comm_dcn_gbps``) splits the
        achieved rate by the link each byte rides — DCN is the
        oversubscribed hop, so its gauge is the one that saturates
        first."""
        self.registry.gauge(
            "tmpi_comm_gbps",
            help="achieved per-device interconnect GB/s "
                 "(analytic bytes / measured step time)",
        ).set(gbps)
        ratio = self.traffic.compression_ratio
        if ratio != 1.0:
            self.registry.gauge(
                "tmpi_comm_gbps_raw",
                help="GB/s an UNCOMPRESSED (fp32) wire would need for "
                     "the same step time — effective * compression "
                     "ratio (obs/comm.py)",
            ).set(gbps * ratio)
        if step_seconds and self.traffic.dcn_bytes_per_step > 0:
            ici = self.traffic.ici_gbps(step_seconds)
            dcn = self.traffic.dcn_gbps(step_seconds)
            if ici is not None:
                self.registry.gauge(
                    "tmpi_comm_ici_gbps",
                    help="achieved GB/s on in-slice (ICI) hops "
                         "(analytic per-link bytes / measured step time)",
                ).set(ici)
            if dcn is not None:
                self.registry.gauge(
                    "tmpi_comm_dcn_gbps",
                    help="achieved GB/s on cross-slice (DCN) hops "
                         "(analytic per-link bytes / measured step time)",
                ).set(dcn)

    def snapshot(self, step: Optional[int] = None) -> Optional[dict]:
        """Write one metrics snapshot line + refresh the Prometheus
        exposition (rank 0 only; other ranks no-op)."""
        if not self.enabled or self._metrics_f is None or self._closed:
            return None
        if step is not None:
            self._last_snapshot_step = step
        with self._metrics_lock:
            if self._last_attr is not None:
                # one kind=profile record per snapshot: the newest
                # step-time attribution (schema:
                # tools/check_obs_schema.py). Written BEFORE the
                # snapshot line: downstream readers (and tests) may
                # treat the file's last record as the metrics snapshot.
                import json as _json

                self._metrics_f.write(_json.dumps(self._last_attr.as_record(
                    step=step if step is not None else self._last_snapshot_step,
                    rank=self.rank,
                    rule=self.traffic.rule if self.traffic is not None else None,
                )) + "\n")
            rec = self.registry.emit_snapshot(self._metrics_f, step=step)
        try:
            self.registry.write_prometheus(self._prom_path)
        except OSError as e:
            print(f"[rank {self.rank}] metrics.prom write failed: {e!r}",
                  file=sys.stderr, flush=True)
        return rec

    def close(self) -> None:
        """Final snapshot, span summary, health-thread shutdown.
        Idempotent; must run even when training raises (the driver's
        ``finally``)."""
        if self._closed:
            return
        self.snapshot(step=None)
        self._closed = True
        if self.spans is not None:
            if _spans_mod.current() is self.spans:
                _spans_mod.set_current(None)
            self.spans.close()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.heartbeat is not None:
            self.heartbeat.stop()
        if self._metrics_f is not None:
            # under the lock: the scrubber thread may be mid-write
            with self._metrics_lock:
                self._metrics_f.close()
                self._metrics_f = None
        if self._numerics_f is not None:
            self._numerics_f.close()
            self._numerics_f = None
