"""Validate telemetry JSONL against the documented schemas.

Every machine-readable line this framework emits — Recorder history
(``<run>.jsonl``), span traces (``obs/spans_rank*.jsonl``), metric
snapshots (``obs/metrics.jsonl``), heartbeat
and stall reports, the serving engine's ``serve``/``reload`` records
(``obs/serve.jsonl``), the continuous-batching decode engine's
``decode`` records (``obs/decode.jsonl``, ``tmpi_decode_*`` metric
family) — must match ONE of the record kinds below, keyed
by the ``kind`` field. Downstream parsing (``tmpi report``, ``tmpi top``,
tools/plot_history.py) reads these streams; without an
enforced schema they drift silently and the first symptom is a broken
plot three PRs later. The schema table here is the single source of
truth (README "Observability" documents it for humans) and a test
validates every line the live system emits against it.

Usage::

    python -m theanompi_tpu.tools.check_obs_schema RUN_DIR [...]
    python -m theanompi_tpu.tools.check_obs_schema path/to/run.jsonl

Directories are walked for ``*.jsonl`` (including ``obs/``
subdirectories). Exit code 1 on any invalid line.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
from typing import Any, Optional

_NUM = (int, float)

# kind -> {field: (types, required)}; fields absent from a spec are
# allowed if numeric/str (the Recorder forwards model-defined metrics:
# loss/error/top5_error/lr/... — an open union by design)
SCHEMAS: dict[str, dict[str, tuple[tuple, bool]]] = {
    "train": {
        "step": ((int,), True),
    },
    "val": {
        "epoch": ((int,), True),
    },
    "epoch": {
        "epoch": ((int,), True),
        "seconds": (_NUM, True),
    },
    "span": {
        "name": ((str,), True),
        "rank": ((int,), True),
        "t0": (_NUM, True),
        "dur": (_NUM, True),
        "depth": ((int,), True),
        # amortized spans (utils/dispatch.py spaced syncs): duration is
        # ATTRIBUTED window time, not a begin/finish bracket — flagged
        # so trace readers can tell the two apart
        "amortized": ((bool,), False),
        # the training step a span belongs to (utils/recorder.py: the
        # driver's wait/dispatch/key_split/drain/emit brackets and the
        # amortized step); spans outside the train loop carry none.
        # ``t0`` is seconds of time.time_ns(), the profiler's clock
        "step": ((int,), False),
        # the serving loop's spans (serve/decode/engine.py, written by
        # drain()): the ``iteration`` a loop span belongs to (queue,
        # admit, prefill, upload, dispatch, drain, harvest; ``calls``:
        # the prefill calls inside a ``prefill`` span); a request's
        # queue_wait / first_token span carries its ``request`` number
        # and ``cause``, the iteration that admitted or answered it
        "iteration": ((int,), False),
        "calls": ((int,), False),
        "request": ((int,), False),
        "cause": ((int,), False),
    },
    "span_summary": {
        "rank": ((int,), True),
        "t0": (_NUM, True),
        "wall_s": (_NUM, True),
        "fractions": ((dict,), True),
        "totals_s": ((dict,), True),
        "counts": ((dict,), True),
    },
    "metrics": {
        "t": (_NUM, True),
        "metrics": ((dict,), True),
        "step": ((int,), False),
        "source": ((str,), False),
        "labels": ((dict,), False),
    },
    # compressed-collectives wire declaration (obs/comm.py, written by
    # Observability.set_traffic_model when the engine declares its
    # traffic model): the sustained per-step bytes a codec run moves
    # (`wire_bytes`) next to the fp32 equivalent (`raw_bytes`) and the
    # codec that did it — the per-run compression proof line.
    "comm": {
        "t": (_NUM, True),
        "rule": ((str,), True),
        "codec": ((str,), True),
        "n_workers": ((int,), True),
        "raw_bytes": (_NUM, True),
        "wire_bytes": (_NUM, True),
        "compression_ratio": (_NUM, True),
        # per-link-class split (amortized, per device): the cross-slice
        # DCN share of the effective and raw wire next to the in-slice
        # ICI remainder — ici+dcn == wire_bytes and raw_ici+raw_dcn ==
        # raw_bytes by construction (obs/comm.py TrafficModel). 0 on
        # single-slice meshes; optional so pre-multislice records stay
        # valid. Live companions: the tmpi_comm_{ici,dcn}_bytes_per_step
        # (+ raw_*) gauges and the achieved tmpi_comm_{ici,dcn}_gbps
        # pair (analytic per-link bytes / measured step seconds).
        "ici_bytes": (_NUM, False),
        "dcn_bytes": (_NUM, False),
        "raw_ici_bytes": (_NUM, False),
        "raw_dcn_bytes": (_NUM, False),
    },
    "heartbeat": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "pid": ((int,), True),
        # dispatch-pipeline liveness split (utils/dispatch.py): step
        # advancing while last_drained_step froze at in_flight=depth is
        # a wedged DEVICE program; both frozen is a stalled HOST driver
        "dispatch_in_flight": ((int,), False),
        "last_drained_step": ((int,), False),
    },
    # numerics flight recorder (obs/numerics.py, obs/flight.py): one
    # sentinel row per drained numerics step (also the flight ring's
    # entry format). Non-finite values cannot ride a JSON numeric map —
    # they are dropped from `metrics` and named in `nonfinite_keys`
    # (comma-joined); the fused non-finite COUNT stays numeric.
    "numerics": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "metrics": ((dict,), True),
        "nonfinite_keys": ((str,), False),
    },
    # one record per detected anomaly (NaN/Inf trigger or EWMA spike),
    # written at dispatch-drain time into numerics_rank{r}.jsonl
    "anomaly": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "metric": ((str,), True),
        "reason": ((str,), True),
        "policy": ((str,), False),
        "value": (_NUM, False),
        "value_repr": ((str,), False),  # non-finite values ride as text
        "ewma": (_NUM, False),
        "factor": (_NUM, False),
        "epoch": ((int,), False),
    },
    "stall": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "stall_s": (_NUM, True),
        "timeout_s": (_NUM, True),
        "stacks": ((dict,), True),
        "postmortem_trace": ((str,), False),
    },
    # fault-tolerant run supervisor (launch/supervisor.py): one record
    # per failed or preempted attempt, appended to supervisor.jsonl.
    # `step` is the VERIFIED checkpoint step the next attempt resumes
    # from (-1 = none: the retry restarts from scratch); `resumable`
    # marks a SIGTERM-grace exit that checkpointed cleanly.
    "retry": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "attempt": ((int,), True),
        "step": ((int,), True),
        "error": ((str,), True),
        # the backoff ACTUALLY slept — under --retry-jitter this is the
        # seeded decorrelated-jitter draw, the de-phasing proof line
        "backoff_s": (_NUM, True),
        "resumable": ((bool,), False),
        # instability attribution (chaos PR): which layer killed the
        # attempt — crash/preempt/topology/storage/anomaly
        # (launch/supervisor.classify_retry_cause)
        "cause": ((str,), False),
        # the attempt's device world size (elastic PR): present on
        # every elastic-supervised record so supervisor.jsonl alone
        # shows the topology trajectory across retries
        "world": ((int,), False),
    },
    # checkpoint scrubber (utils/checkpoint.scrub_checkpoint_dir /
    # CheckpointScrubber): one record per scrub pass — keep-chain
    # members re-verified, how many failed, the quarantined filenames
    # (comma-joined; empty string = clean pass), and the pass's wall
    # seconds. Written by the worker's background scrubber
    # (--scrub-interval) and by the supervisor's retry-time pass.
    "scrub": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "checked": ((int,), True),
        "corrupt": ((int,), True),
        "quarantined": ((str,), True),
        "seconds": (_NUM, True),
    },
    # thread-stress harness (tools/analyze/stress.py): one record per
    # StressHarness.run — the scenario name, the seed that reproduces
    # the schedule, rounds actually executed, the verdict (`ok` with
    # `violations` comma-joined; empty string = clean), the run's wall
    # seconds, and the finest switch interval applied. Written to
    # <obs_dir>/stress.jsonl by the tier-1 stress tests and ad-hoc
    # stress runs.
    "stress": {
        "t": (_NUM, True),
        "scenario": ((str,), True),
        "seed": ((int,), True),
        "rounds": ((int,), True),
        "ok": ((bool,), True),
        "violations": ((str,), False),
        "seconds": (_NUM, False),
        "switch_interval_min": (_NUM, False),
    },
    # chaos campaign runner (tools/chaos.py, `tmpi chaos`): one record
    # per fuzzed fault schedule — the seed that generated it, the
    # engine/codec config label, the schedule itself ('+'-joined
    # KIND@STEP specs), the invariant oracle's verdict (`ok` with
    # `violations` naming the failed invariants, comma-joined), how
    # many training runs the schedule cost (incl. process relaunches),
    # and — for a failing schedule — the shrunken minimal repro as a
    # ready-to-paste --inject-fault command-line fragment.
    "chaos": {
        "t": (_NUM, True),
        "seed": ((int,), True),
        "config": ((str,), True),
        "schedule": ((str,), True),
        "ok": ((bool,), True),
        "violations": ((str,), False),
        "runs": ((int,), False),
        "seconds": (_NUM, False),
        "repro": ((str,), False),
        "shrunk_schedule": ((str,), False),
    },
    # elastic supervision (launch/supervisor.py): one record per
    # attempt — the device world size the attempt was launched in,
    # probed from the live (sorted) device enumeration; prev_world
    # appears from the second attempt on, so a topology change reads
    # directly off the pair
    "topology": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "attempt": ((int,), True),
        "world": ((int,), True),
        "prev_world": ((int,), False),
    },
    # elastic resume (launch/worker.py + utils/checkpoint.py
    # load_resharded): one record per checkpoint actually resharded
    # onto a changed mesh — saved vs live world size, the reshard's
    # wall seconds, how many state leaves moved, and the implied
    # per-replica batch after the move
    "reshard": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "from_world": ((int,), True),
        "to_world": ((int,), True),
        "seconds": (_NUM, True),
        "leaves": ((int,), False),
        "per_replica_batch": ((int,), False),
    },
    # anomaly rollback (--on-anomaly rollback, launch/worker.py): one
    # record per restore, written to numerics_rank{r}.jsonl next to the
    # anomaly records that triggered it. `step` is the anomalous step,
    # `restore_step` the verified checkpoint step restored, `skipped`
    # the data batches the replay will skip at the anomalous step.
    "rollback": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "restore_step": ((int,), True),
        "budget_left": ((int,), True),
        "skipped": ((int,), False),
    },
    # step-time attribution (obs/attribution.py, written by
    # Observability.snapshot into metrics.jsonl when the engine
    # declared a cost model): one record per snapshot — the measured
    # step wall, the compute/comm/host/residual fractions (validated
    # below: they must sum to 1.0 +/- 0.02, the decomposition's own
    # invariant), the roofline classification, and the utilization
    # readings (mfu vs spec peak, or mfu_calibrated on devices without
    # one; achieved hbm_gbps).
    "profile": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "step_seconds": (_NUM, True),
        "fractions": ((dict,), True),
        "classification": ((str,), True),
        "peak_source": ((str,), False),
        "rule": ((str,), False),
        "mfu": (_NUM, False),
        "mfu_calibrated": (_NUM, False),
        "hbm_gbps": (_NUM, False),
    },
    # memory & precision pre-flight (`tmpi preflight`,
    # tools/preflight.py): one record per pre-flight run appended to
    # metrics.jsonl next to a metrics snapshot carrying the
    # tmpi_preflight_peak_bytes / tmpi_preflight_fit /
    # tmpi_preflight_state_bytes gauges.
    # `peak_bytes` is the PREDICTED per-device peak (XLA memory
    # analysis of the lowered step + the declared donation audit);
    # `fit`/`budget_bytes` appear when a budget exists (--budget-gb or
    # the device table's HBM capacity).
    "preflight": {
        "t": (_NUM, True),
        "model": ((str,), True),
        "engine": ((str,), True),
        "codec": ((str,), True),
        "n_devices": ((int,), True),
        "peak_bytes": (_NUM, True),
        "fused": ((bool,), False),
        "state_bytes": (_NUM, False),
        "budget_bytes": (_NUM, False),
        "budget_source": ((str,), False),
        "fit": ((bool,), False),
        "device_kind": ((str,), False),
        "findings": ((int,), False),
    },
    # sharding & layout analyzer (tools/analyze/sharding.py, `tmpi
    # lint --obs-dir`): one record per analyzed engine x codec x
    # --fused-update config. `leaves` is the declared spec-table size,
    # `mismatched` the leaves whose compiled input sharding disagrees
    # with the recipe, `hidden_bytes` the GSPMD-inserted collective
    # wire (per-device, amortized) absent from the traced program —
    # the SHARD002 hidden-wire total, next to the compiled/traced/
    # declared byte figures it was reconciled against.
    "shard": {
        "t": (_NUM, True),
        "engine": ((str,), True),
        "codec": ((str,), True),
        "n_devices": ((int,), True),
        "leaves": ((int,), True),
        "mismatched": ((int,), True),
        "hidden_bytes": (_NUM, True),
        "fused": ((bool,), False),
        "compiled_wire_bytes": (_NUM, False),
        "traced_wire_bytes": (_NUM, False),
        "declared_raw_bytes": (_NUM, False),
        "findings": ((int,), False),
    },
    # fleet telemetry plane (obs/fleet.py): one record per CHANGED
    # fleet view (step advance or a flag set changing), appended to
    # fleet.jsonl by a record-writing FleetTailer (the chief exporter;
    # `tmpi top` is read-only). `step` is the fleet max step, `ranks`
    # how many ranks reported telemetry; rank-id lists (stragglers /
    # frozen / missed / skewed) ride comma-joined like scrub's
    # `quarantined` (empty string = none). `step_seconds_*` is the
    # step-time distribution over ranks' smoothed step times;
    # `link_class` tags comm_gbps with the interconnect the bytes ride
    # (dcn when the __topology__ mesh is multislice, else ici).
    "fleet": {
        "t": (_NUM, True),
        "step": ((int,), True),
        "ranks": ((int,), True),
        "step_spread": ((int,), False),
        "step_seconds_min": (_NUM, False),
        "step_seconds_p50": (_NUM, False),
        "step_seconds_p99": (_NUM, False),
        "step_seconds_max": (_NUM, False),
        "slowest_rank": ((int,), False),
        "straggler_count": ((int,), False),
        "stragglers": ((str,), False),
        "frozen": ((str,), False),
        "missed": ((str,), False),
        "skewed": ((str,), False),
        "mfu_min": (_NUM, False),
        "mfu_median": (_NUM, False),
        "comm_gbps": (_NUM, False),
        "link_class": ((str,), False),
        "slices": ((int,), False),
        "retries": ((int,), False),
    },
    # serving engine (serve/engine.py): periodic + drain-time stats
    # records in <obs_dir>/serve.jsonl. `params_step` is the checkpoint
    # step being served (-1 before the first load); `metrics` is a flat
    # numeric map whose keys all carry the tmpi_serve_ prefix (latency
    # p50/p99 ms, queue depth, batch-fill, request/batch/reload totals)
    # — the prefix is ENFORCED below so serve telemetry stays greppable
    # under one name family.
    "serve": {
        "t": (_NUM, True),
        "params_step": ((int,), True),
        "metrics": ((dict,), True),
        # replica-group members (`tmpi serve --replicas N`) stamp which
        # member wrote the record (serve_r<id>.jsonl); absent on the
        # classic single-engine path (byte-compatible)
        "replica_id": ((int,), False),
    },
    # continuous-batching decode engine (serve/decode/engine.py):
    # periodic + drain-time stats records in <obs_dir>/decode.jsonl
    # (decode_r<id>.jsonl for replica-fleet members). Same shape as
    # kind=serve — `params_step` is the served checkpoint step, and
    # `metrics` is a flat numeric map — but the keys carry the
    # tmpi_decode_ prefix (TTFT p50/p99 ms, TPOT ms, tokens/sec, KV
    # page occupancy and free-list conservation totals, per-status
    # request totals) — ENFORCED below so token-serving telemetry
    # stays greppable under its own name family, distinct from the
    # eval-forward engine's.
    "decode": {
        "t": (_NUM, True),
        "params_step": ((int,), True),
        "metrics": ((dict,), True),
        "replica_id": ((int,), False),
        # what the paged pools hold (Model.cache_spec): "kv" per-head
        # keys and values, "latent" one latent and one rotated row,
        # "compressed" EVA's compressed rows (what a model holds a slot
        # beside them is in the metrics, by kind)
        "cache_kind": ((str,), False),
    },
    # replica-group router (serve/router.py): one record per routing
    # event in <obs_dir>/router.jsonl. `event` says which: "health"
    # (replica state transition, from_state/to_state), "failover" (an
    # in-flight request re-admitted off a dying replica, to_replica),
    # "restart" (supervisor revived a member, backoff_s is the
    # decorrelated-jitter delay it waited), "drop" (failover budget or
    # capacity exhausted — the oracle's zero-drop invariant greps
    # these), "reload"/"reload_failed" (central hot-reload fan-out),
    # and "snapshot" (drain-time stats; `metrics` keys carry the
    # tmpi_router_ prefix, ENFORCED below like serve's).
    "router": {
        "t": (_NUM, True),
        "event": ((str,), True),
        "replica_id": ((int,), False),
        "from_state": ((str,), False),
        "to_state": ((str,), False),
        "to_replica": ((int,), False),
        "backoff_s": (_NUM, False),
        "from_step": ((int,), False),
        "to_step": ((int,), False),
        "ms": (_NUM, False),
        "ok": ((bool,), False),
        "error": ((str,), False),
        "metrics": ((dict,), False),
    },
    # one record per checkpoint hot-reload applied by the serving
    # engine (serve/reload.py): the step served before, the verified
    # step swapped in, and the off-hot-path load+swap latency. A
    # reload that verified but failed to LOAD (keep-chain pruned the
    # file between discovery and open — the TOCTOU race) writes
    # ok=false with to_step=-1 and the error; serving never blinked,
    # the next poll retries.
    "reload": {
        "t": (_NUM, True),
        "from_step": ((int,), True),
        "to_step": ((int,), True),
        "ms": (_NUM, False),
        "ok": ((bool,), False),
        "error": ((str,), False),
    },
    # model-drift watchdog (obs/drift.py, written by the obs facade's
    # drain path into metrics.jsonl): one change-gated record per EWMA
    # movement — per-model relative error of predicted vs measured
    # (model_err_cost: roofline wall vs measured step; model_err_traffic:
    # priced comm seconds vs the measured remainder; model_err_memory:
    # declared state bytes vs device.memory_stats() high-water), the
    # worst-offending component per model (per-link for traffic,
    # per-leaf-family for memory), the tolerance band in force, and the
    # sources currently past it comma-joined (empty string = none).
    # `peak_source` says whether errors are vs spec peaks or the
    # first-drain calibration (CPU test meshes, like kind=profile).
    "drift": {
        "rank": ((int,), True),
        "t": (_NUM, True),
        "step": ((int,), True),
        "tolerance": (_NUM, True),
        "breached": ((str,), True),
        "step_seconds": (_NUM, False),
        "peak_source": ((str,), False),
        "model_err_cost": (_NUM, False),
        "model_err_traffic": (_NUM, False),
        "model_err_memory": (_NUM, False),
        "worst_cost": ((str,), False),
        "worst_traffic": ((str,), False),
        "worst_memory": ((str,), False),
    },
    # unified run report (tools/report.py, `tmpi report --json`): ONE
    # self-contained object per invocation — the run verdict
    # (completed/halted/degraded) with its evidence, the causally-
    # grouped incident list (each citing the file:line evidence records
    # it adopted), the merged monotonic event timeline, the per-phase
    # wall breakdown (span_summary rollup) and the drift trajectory.
    # Nested structures are DECLARED typed fields (like profile's
    # `fractions`), so the open-union scalar rule still holds for
    # extras. Deliberately byte-deterministic for a finished dir: no
    # wall-clock stamps ride the body (tests diff two invocations).
    "report": {
        "verdict": ((str,), True),
        "ranks": ((int,), True),
        "n_events": ((int,), True),
        "n_incidents": ((int,), True),
        "steps": ((int,), False),
        "evidence": ((list,), False),
        "timeline": ((list,), False),
        "incidents": ((list,), False),
        "phases": ((dict,), False),
        "drift": ((dict,), False),
        "fleet": ((dict,), False),
    },
}

# the serving metric name family (serve records may only carry these-
# prefixed keys; the engine's registry families are documented here so
# dashboards and the schema stay in one place):
#   tmpi_serve_latency_seconds   histogram  request submit->result
#   tmpi_serve_queue_depth       gauge      requests waiting
#   tmpi_serve_batch_fill        gauge      real/bucket rows, last batch
#   tmpi_serve_params_step       gauge      checkpoint step served
#   tmpi_serve_requests_total    counter    by status=served|expired|rejected
#   tmpi_serve_batches_total     counter    by bucket=N
#   tmpi_serve_reloads_total     counter    hot-reloads applied
SERVE_METRIC_PREFIX = "tmpi_serve_"

# the decode metric name family (kind=decode records may only carry
# these-prefixed keys — enforced below, same deal as serve's):
#   tmpi_decode_ttft_seconds    histogram  submit -> first token
#   tmpi_decode_tpot_seconds    histogram  per-token decode interval
#   tmpi_decode_queue_wait_seconds histogram  submit -> the admission
#                                          that gave the request a slot
#                                          (record: ..._queue_wait_p50_ms)
#   tmpi_decode_loop_<span>_ms  record only: mean of the loop thread's
#                                          queue|admit|prefill|upload|
#                                          dispatch|drain|harvest span over
#                                          the last record_every iterations
#   tmpi_decode_queue_depth     gauge      prompts waiting for a slot
#   tmpi_decode_batch_occupancy gauge      running seqs / max_seqs
#   tmpi_decode_kv_pages_used   gauge      KV pool pages outstanding
#   tmpi_decode_kv_pages_free   gauge      KV pool pages in free list
#   tmpi_decode_kv_pool_bytes   gauge      by kind=kv|latent|compressed
#                                          (the paged pools; EVA's pages
#                                          hold compressed rows) |compressed|
#                                          state|ring|conv|ssm (held a slot)
#   tmpi_decode_kv_bytes_per_position gauge  by kind, one more position
#                                          (a ring or a state: none)
#   tmpi_decode_visible_context_share gauge  share of their context the
#                                          sparse or EVA layers' queries saw
#   tmpi_decode_eva_window_positions gauge  EVA: exact keys a query read
#                                          (mean over running slots; the
#                                          sum an iteration in the span
#                                          store's counts, by that name)
#   tmpi_decode_eva_compressed_rows gauge  EVA: compressed rows it read
#   tmpi_decode_mqa_rows        gauge      Jamba: K/V rows a query read
#                                          over the attention layers
#   tmpi_decode_ssm_state_bytes gauge      Jamba: Mamba state a step read
#                                          and wrote a slot (conv|ssm held a
#                                          slot: ..._conv_bytes, ..._ssm_bytes)
#   tmpi_decode_requests_total  counter    by status=served|expired|
#                                          evicted|rejected|failed
#   tmpi_decode_tokens_total    counter    tokens sampled and returned
#   tmpi_decode_prefills_total  counter    by bucket=N
#   tmpi_decode_reloads_total   counter    hot-reloads applied
#   tmpi_decode_handover_total  counter    by outcome=submitted|timed_out:
#                                          the loop's bounded waits, after
#                                          an iteration that resolved a
#                                          request, for the client's next
#                                          submission before the queue is
#                                          read (record:
#                                          ..._handover_<outcome>_total)
DECODE_METRIC_PREFIX = "tmpi_decode_"

# the router metric name family (serve/router.py; kind=router snapshot
# records may only carry these-prefixed keys — enforced below, same
# deal as SERVE_METRIC_PREFIX). Counters are fleet totals; gauges are
# refreshed by the supervisor's health pass:
#   tmpi_router_requests_total  counter  by status=served|dropped|
#                                        rejected|expired|stale_retry|
#                                        stale_served
#   tmpi_router_failovers_total counter  in-flight re-admissions that
#                                        landed on a healthy replica
#   tmpi_router_restarts_total  counter  supervisor revivals (+ by
#                                        status=failed for factory
#                                        errors, retried with backoff)
#   tmpi_router_reloads_total   counter  central hot-reload fan-outs
#   tmpi_router_healthy         gauge    replicas in rotation
#   tmpi_router_replicas        gauge    configured group size
#   tmpi_router_queue_depth     gauge    fleet backlog (sum of members)
#   tmpi_router_capacity_rps    gauge    surviving-capacity EWMA (the
#                                        503 Retry-After denominator)
#   tmpi_router_step_floor      gauge    served-step monotone floor
ROUTER_METRIC_PREFIX = "tmpi_router_"

# the step-attribution gauge family (obs/attribution.py; set live at
# every dispatcher drain sync, documented here next to its record kind —
# snapshot metric maps are an open union by design, so unlike
# SERVE_METRIC_PREFIX these names are documentation, not enforcement):
#   tmpi_mfu                  gauge  achieved/peak FLOP/s (spec peak)
#   tmpi_mfu_calibrated       gauge  compute fraction vs calibrated peak
#   tmpi_hbm_gbps             gauge  achieved HBM GB/s (any backend)
#   tmpi_step_compute_frac    gauge  model compute share of the step
#   tmpi_step_comm_frac       gauge  model collective share
#   tmpi_step_host_frac       gauge  measured host-blocked share
#   tmpi_step_residual_frac   gauge  unattributed remainder
#   tmpi_cost_flops_per_step  gauge  XLA cost-analysis FLOPs/step
#   tmpi_cost_hbm_bytes_per_step  gauge  XLA bytes-accessed/step
# per-link-class comm gauges (obs/comm.py TrafficModel.as_metrics +
# the obs facade's step cadence; 0 / absent on single-slice meshes):
#   tmpi_comm_ici_bytes_per_step      gauge  in-slice effective B/step
#   tmpi_comm_dcn_bytes_per_step      gauge  cross-slice effective B/step
#   tmpi_comm_raw_ici_bytes_per_step  gauge  in-slice fp32 B/step
#   tmpi_comm_raw_dcn_bytes_per_step  gauge  cross-slice fp32 B/step
#   tmpi_comm_ici_gbps        gauge  achieved in-slice GB/s
#   tmpi_comm_dcn_gbps        gauge  achieved cross-slice GB/s
# the model-drift gauge family (obs/drift.py via the obs facade's drain
# cadence; documentation like the tmpi_mfu block — kind=drift records
# are the enforced surface). Values are EWMA relative errors, so 0.0 is
# a perfect model and 0.25 is the default anomaly tolerance:
#   tmpi_model_err_cost      gauge  |roofline wall - step wall| / step
#   tmpi_model_err_traffic   gauge  |priced comm - measured comm| / comm
#   tmpi_model_err_memory    gauge  |declared state - HBM high-water| / HW
#   tmpi_drift_breaches_total counter  drift anomalies raised this run
# kind=profile fractions must sum to 1 within this absolute tolerance
PROFILE_FRACTION_SUM_TOL = 0.02

# the fleet-aggregation gauge family (obs/fleet.py; refreshed on every
# tailer pass, served by obs/exporter.py `/metrics`; documentation like
# the tmpi_mfu block — kind=fleet records are the enforced surface):
#   tmpi_fleet_ranks             gauge  ranks reporting telemetry
#   tmpi_fleet_step              gauge  fleet max step
#   tmpi_fleet_step_spread       gauge  max-min step over ranks
#   tmpi_fleet_step_seconds      gauge  by q=min|p50|p99|max over ranks
#   tmpi_fleet_slowest_rank      gauge  highest smoothed step time
#   tmpi_fleet_stragglers        gauge  persistent-straggler count
#   tmpi_fleet_frozen            gauge  silent ranks behind the fleet
#   tmpi_fleet_missed_heartbeats gauge  ranks with stale heartbeats
#   tmpi_fleet_skewed            gauge  numerics-skewed ranks
#   tmpi_fleet_healthy           gauge  1 healthy / 0 unhealthy
#   tmpi_fleet_mfu_min           gauge  min MFU over ranks
#   tmpi_fleet_mfu_median        gauge  median MFU over ranks
#   tmpi_fleet_comm_gbps         gauge  by link=ici|dcn
#   tmpi_fleet_rank_step         gauge  by rank=R, per-rank progress
#   tmpi_fleet_slice_step        gauge  by slice=S (multislice only)
#   tmpi_fleet_retries           gauge  supervisor retries observed
#   tmpi_fleet_refresh_errors    gauge  suppressed tailer exceptions
FLEET_METRIC_PREFIX = "tmpi_fleet_"


def _check_numeric_map(d: dict, what: str) -> list[str]:
    errs = []
    for k, v in d.items():
        if not isinstance(k, str):
            errs.append(f"{what} key {k!r} is not a string")
        if not isinstance(v, _NUM) or isinstance(v, bool):
            errs.append(f"{what}[{k!r}] = {v!r} is not numeric")
        elif not math.isfinite(float(v)):
            errs.append(f"{what}[{k!r}] = {v!r} is not finite")
    return errs


def validate_record(obj: Any) -> list[str]:
    """Error strings for one parsed JSONL record (empty = valid)."""
    if not isinstance(obj, dict):
        return [f"record is {type(obj).__name__}, not an object"]
    kind = obj.get("kind")
    if kind not in SCHEMAS:
        return [f"unknown kind {kind!r} (known: {sorted(SCHEMAS)})"]
    spec = SCHEMAS[kind]
    errs = []
    for field, (types, required) in spec.items():
        if field not in obj:
            if required:
                errs.append(f"{kind}: missing required field {field!r}")
            continue
        v = obj[field]
        # bool is an int subclass; an int-typed field must reject True
        if isinstance(v, bool) and bool not in types:
            errs.append(f"{kind}.{field} = {v!r} is bool, want "
                        f"{'/'.join(t.__name__ for t in types)}")
        elif not isinstance(v, types):
            errs.append(f"{kind}.{field} = {v!r} is "
                        f"{type(v).__name__}, want "
                        f"{'/'.join(t.__name__ for t in types)}")
    for field, v in obj.items():
        if field == "kind" or field in spec:
            continue
        # open-union extras must stay scalar (nested structures belong
        # in a typed field, or downstream flattening breaks)
        if not isinstance(v, (str, int, float, bool)) and v is not None:
            errs.append(f"{kind}: extra field {field!r} has non-scalar "
                        f"type {type(v).__name__}")
    if not errs:
        if kind in ("metrics", "numerics"):
            errs += _check_numeric_map(obj["metrics"], "metrics")
        elif kind == "serve":
            errs += _check_numeric_map(obj["metrics"], "metrics")
            for k in obj["metrics"]:
                if isinstance(k, str) and not k.startswith(SERVE_METRIC_PREFIX):
                    errs.append(
                        f"serve.metrics key {k!r} lacks the "
                        f"{SERVE_METRIC_PREFIX!r} prefix"
                    )
        elif kind == "decode":
            errs += _check_numeric_map(obj["metrics"], "metrics")
            for k in obj["metrics"]:
                if isinstance(k, str) and not k.startswith(DECODE_METRIC_PREFIX):
                    errs.append(
                        f"decode.metrics key {k!r} lacks the "
                        f"{DECODE_METRIC_PREFIX!r} prefix"
                    )
        elif kind == "router" and isinstance(obj.get("metrics"), dict):
            errs += _check_numeric_map(obj["metrics"], "metrics")
            for k in obj["metrics"]:
                if isinstance(k, str) and not k.startswith(ROUTER_METRIC_PREFIX):
                    errs.append(
                        f"router.metrics key {k!r} lacks the "
                        f"{ROUTER_METRIC_PREFIX!r} prefix"
                    )
        elif kind == "profile":
            errs += _check_numeric_map(obj["fractions"], "fractions")
            if not errs:
                total = sum(obj["fractions"].values())
                if abs(total - 1.0) > PROFILE_FRACTION_SUM_TOL:
                    errs.append(
                        f"profile fractions sum to {total:.6f}, not "
                        f"1.0 +/- {PROFILE_FRACTION_SUM_TOL} — the "
                        "attribution lost a component"
                    )
        elif kind == "span_summary":
            errs += _check_numeric_map(obj["fractions"], "fractions")
            errs += _check_numeric_map(obj["totals_s"], "totals_s")
            # the acceptance invariant: owner-thread top-level fractions
            # cover disjoint stretches of the run wall clock
            total = sum(obj["fractions"].values())
            if total > 1.0 + 1e-6:
                errs.append(
                    f"span_summary fractions sum to {total:.6f} > 1.0"
                )
        elif kind == "stall":
            for name, frames in obj["stacks"].items():
                if not isinstance(frames, list) or not all(
                    isinstance(f, str) for f in frames
                ):
                    errs.append(f"stall.stacks[{name!r}] is not a list "
                                "of frame strings")
    return errs


def check_file(path: str) -> list[str]:
    """``'path:line: error'`` strings for every invalid line."""
    errs = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                errs.append(f"{path}:{i}: unparseable JSON ({e})")
                continue
            for e in validate_record(obj):
                errs.append(f"{path}:{i}: {e}")
    return errs


def discover(paths: list[str]) -> list[str]:
    files = []
    for p in paths:
        if os.path.isdir(p):
            found = sorted(
                glob.glob(os.path.join(p, "**", "*.jsonl"), recursive=True)
            ) + sorted(
                glob.glob(os.path.join(p, "**", "heartbeat_rank*.json"),
                          recursive=True)
            ) + sorted(
                glob.glob(os.path.join(p, "**", "stall_rank*.json"),
                          recursive=True)
            )
            if not found:
                raise FileNotFoundError(f"no telemetry files under {p!r}")
            files += found
        else:
            files.append(p)
    return files


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="telemetry .jsonl/.json files, or directories to "
                         "walk (run save-dirs, obs dirs)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="print only the summary line")
    args = ap.parse_args(argv)
    files = discover(args.paths)
    all_errs = []
    n_lines = 0
    for f in files:
        with open(f) as fh:
            n_lines += sum(1 for line in fh if line.strip())
        all_errs += check_file(f)
    if not args.quiet:
        for e in all_errs:
            print(e)
    print(
        f"checked {n_lines} records in {len(files)} files: "
        + ("OK" if not all_errs else f"{len(all_errs)} schema errors")
    )
    return 1 if all_errs else 0


if __name__ == "__main__":
    sys.exit(main())
