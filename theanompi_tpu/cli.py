"""``tmpi`` — the command-line launcher.

Rebuild of the reference CLI (reference: ``tmpi`` — approx.
``tmpi <RULE> <n> <devices> <modelfile> <modelclass>``, which built an
``mpirun`` command line; SURVEY.md §3.1). No mpirun on TPU: the rule
runs one SPMD program over a device mesh in-process.

Usage::

    tmpi BSP 8 theanompi_tpu.models.model_zoo.wrn WRN
    tmpi EASGD 8 theanompi_tpu.models.model_zoo.resnet50 ResNet50 --avg-freq 8
    tmpi GOSGD 8 theanompi_tpu.models.model_zoo.vgg VGG16
    tmpi BSP 8 my_model.py MyModel --strategy asa16 --epochs 5

``tmpi serve`` is the inference subcommand (serve/cli.py): serve a
training run's checkpoints with dynamic micro-batching and hot-reload;
``--replicas N`` runs a replica-group fleet behind the same endpoint
(serve/router.py: health-checked least-loaded routing, bounded
failover, supervised restarts)::

    tmpi serve --ckpt-dir runs/ck --model cifar10 --watch --port 8300
    tmpi serve --ckpt-dir runs/ck --model cifar10 --replicas 3 --watch

``tmpi lint`` runs every repo lint plus the SPMD safety analyzer
(tools/lint.py): collective-signature verification against goldens,
traffic-model cross-checks, donation audit, rank-divergence lint::

    tmpi lint --json            # CI report with stable rule IDs
    tmpi lint --update-golden   # accept a reviewed signature change

``tmpi profile`` is the step-time attribution profiler
(tools/profile.py): warm steps of one engine+model, reconciled against
the XLA cost model, the declared traffic model and the traced jaxpr
into a compute/comm/host/residual split with a roofline verdict::

    tmpi profile --model mlp --steps 8            # CPU-runnable
    tmpi profile --model alexnet --steps 20 --trace

``tmpi preflight`` is the memory & precision pre-flight
(tools/preflight.py): static peak-HBM budgeting (lowered, never
executed) with a per-leaf byte table, donation-realization audit and
dtype-flow lint, gated on the device's HBM capacity or an explicit
budget::

    tmpi preflight --model mlp --engine bsp --budget-gb 16
    tmpi preflight --model transformer_lm --engine nd --mesh 2x4

``tmpi chaos`` is the chaos campaign runner (tools/chaos.py): fuzzed
fault schedules over the full matrix (process, data AND storage
faults), each run under the supervisor and checked against a recovery
invariant oracle; failing schedules are shrunk to a minimal
``--inject-fault`` repro::

    tmpi chaos --seeds 25               # full matrix, all configs
    tmpi chaos --smoke --seeds 5        # tier-1 CPU smoke
    tmpi chaos --schedule 'crash@5+bitrot@3'
    tmpi chaos --serve --seeds 10       # serving-path campaign: fuzzed
                                        # replica crash/stall/corrupt-
                                        # reload faults against a live
                                        # router fleet under load

``tmpi report`` is the unified post-mortem (tools/report.py): merge a
run's per-rank obs streams into one causally-grouped event timeline —
incidents cite their evidence records — plus the drift trajectory,
per-phase wall breakdown and a completed/halted/degraded verdict::

    tmpi report runs/obs                 # markdown to stdout
    tmpi report runs/obs --out report.md
    tmpi report runs/obs --json          # machine-readable (schema'd)
"""

from __future__ import annotations

import argparse
import ast
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmpi",
        description="TPU-native Theano-MPI: distributed training launcher",
        # no prefix abbreviation: an abbreviated --npro would survive
        # _strip_flags in the respawn path and fork forever
        allow_abbrev=False,
    )
    p.add_argument("rule", choices=["BSP", "EASGD", "GOSGD", "bsp", "easgd", "gosgd"])
    p.add_argument("n_devices", type=int, help="number of chips (0 = all)")
    p.add_argument("modelfile", help="module path or .py file with the model class")
    p.add_argument("modelclass", help="model class name (e.g. WRN)")
    p.add_argument("--strategy", default="psum",
                   help="gradient exchange strategy (psum|ring|ring_bf16|ring_int8|"
                        "psum_bf16|hier or reference names ar|asa32|asa16|nccl32|"
                        "nccl16). 'hier' is the topology-aware "
                        "hierarchical exchange for --slices N meshes: "
                        "in-slice reduce-scatter over ICI, cross-slice "
                        "allreduce over DCN on only the scattered shard "
                        "(--wire-codec applies to that DCN hop alone), "
                        "then in-slice all-gather; composes with "
                        "--allreduce-buckets")
    p.add_argument("--wire-codec", default="none", metavar="CODEC[:ef]",
                   help="compressed-collectives codec (parallel/codec.py) "
                        "for EVERY engine's exchange: none|bf16|int8, "
                        "optional ':ef' suffix for error-feedback "
                        "residual accumulators (e.g. int8:ef — the "
                        "convergence-safe default for int8). Applies to "
                        "the BSP grad psum/ring wire, ZeRO's reduce-"
                        "scatter + all-gather, EASGD's elastic psum, "
                        "GoSGD's gossip message, and the ND engine's "
                        "sharded-axis grad psums; traffic gauges report "
                        "effective vs raw bytes")
    p.add_argument("--fused-update", action="store_true",
                   help="fuse the optimizer epilogue (weight decay + "
                        "global-norm clip + momentum/Nesterov + param "
                        "write) into ONE Pallas pass over donated "
                        "buffers (ops/pallas_update.py) — one HBM "
                        "round-trip per leaf instead of ~4; every "
                        "engine opts in; SGD-family recipes only "
                        "(momentum/nesterov/sgd)")
    p.add_argument("--allreduce-buckets", type=float, default=0.0,
                   metavar="MB",
                   help="BSP rule: chunk the gradient allreduce into "
                        "~MB-sized buckets whose psums launch inside "
                        "backward, overlapping comm with the tail of "
                        "the backward pass (GC3-style scheduling; "
                        "parallel/strategies.py). Same numerics as the "
                        "single psum; composes with --wire-codec "
                        "(':ef' syncs post-backward, bucketed). 0 = "
                        "off; 4-32 MB is the useful range — biggest "
                        "win multi-chip/DCN, a no-op on one chip")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="fuse this many steps into one compiled dispatch "
                        "(one H2D transfer + one host dispatch per group) — "
                        "works for every rule: EASGD embeds its avg_freq "
                        "exchange in the scan, GoSGD keeps its gossip "
                        "cadence per substep; amortizes per-step host "
                        "dispatch latency")
    p.add_argument("--dispatch-depth", type=int, default=2,
                   help="async dispatch pipeline: keep up to K steps in "
                        "flight before the host blocks on a metrics "
                        "fetch (utils/dispatch.py). 2 (default): step N "
                        "is queued before step N-1's metrics are "
                        "drained, so the device never waits for the "
                        "host between two steps; rows, the anomaly "
                        "policies and the heartbeat's drained step lag "
                        "the dispatch by one step. 1 = classic per-step "
                        "sync; recorder JSONL rows are bit-identical "
                        "either way. State is donated (no second copy); "
                        "see README 'Async dispatch pipeline'")
    p.add_argument("--accum-steps", type=int, default=1,
                   help="gradient accumulation: split each (per-device) "
                        "batch into this many microbatches inside the step "
                        "— large-batch SGD trajectory at small-batch "
                        "activation memory")
    p.add_argument("--slices", type=int, default=None,
                   help="BSP over a 2-D (dcn, data) multi-slice mesh with this "
                        "many slices (pod-scale: allreduce rides ICI within a "
                        "slice, DCN across)")
    p.add_argument("--zero", type=int, default=0, choices=[0, 1],
                   help="BSP with ZeRO-1: optimizer state sharded over the "
                        "data axis (psum_scatter grads -> segment update -> "
                        "all_gather params; same wire volume as allreduce)")
    p.add_argument("--tp", type=int, default=1,
                   help="LM models: Megatron tensor-parallel axis size "
                        "(heads/FFN/vocab sharded; one psum per sub-block)")
    p.add_argument("--sp", type=int, default=1,
                   help="LM models: sequence-parallel axis size (ring or "
                        "Ulysses attention per the recipe's attn=)")
    p.add_argument("--pp", type=int, default=1,
                   help="LM models: GPipe pipeline stages (layers sharded; "
                        "microbatches stream via ppermute)")
    p.add_argument("--expert", type=int, default=1,
                   help="MoELMModel: expert-parallel axis size (Switch-MoE "
                        "all-to-all dispatch; doubles as the batch axis)")
    p.add_argument("--microbatches", type=int, default=None,
                   help="with --pp: microbatch count per step (default = pp; "
                        "bubble fraction is (pp-1)/(M+pp-1))")
    p.add_argument("--pp-interleave", type=int, default=1,
                   help="with --pp: virtual stages per device (Megatron "
                        "interleaved schedule; bubble shrinks to "
                        "(pp-1)/(M*v+pp-1); layers must divide pp*v)")
    p.add_argument("--epochs", type=int, default=None, help="override recipe n_epochs")
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None, help="override recipe batch")
    p.add_argument("--dataset", default=None, help="override recipe dataset")
    p.add_argument("--synthetic", action="store_true",
                   help="shortcut: --dataset synthetic (smoke runs, no data on disk)")
    p.add_argument("--dataset-arg", action="append", default=[], metavar="K=V",
                   help="dataset constructor kwarg (repeatable), e.g. "
                        "--dataset-arg n_train=512 --dataset-arg root=/data")
    p.add_argument("--recipe-arg", action="append", default=[], metavar="K=V",
                   help="recipe override (repeatable, JSON values), e.g. "
                        "--recipe-arg 'input_shape=[16,16,3]' "
                        "--recipe-arg num_classes=1000 (the model owns its "
                        "recipe; this is the session's override hook)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--save-dir", default=None, help="recorder output dir (JSONL + pickle)")
    p.add_argument("--tensorboard", action="store_true",
                   help="also emit TensorBoard scalars under <save-dir>/tb "
                        "(soft dependency on tensorboardX)")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--sync-ckpt", action="store_true",
                   help="write epoch checkpoints synchronously instead "
                        "of on the background writer thread: the save "
                        "is durable before the next step dispatches "
                        "(deterministic durability for preemption-prone "
                        "runs, at the cost of stalling the loop for the "
                        "full gather+write)")
    p.add_argument("--ckpt-sharded", action="store_true",
                   help="per-host sharded checkpoints (each controller "
                        "writes only its shards — no cross-host gather or "
                        "rank-0 memory spike; restore works under any "
                        "process count)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--print-freq", type=int, default=40)
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler device trace of a few steps "
                        "into this dir (view with tensorboard; the in-step "
                        "comm/compute split the reference read from host "
                        "brackets)")
    p.add_argument("--profile-steps", type=int, default=4)
    p.add_argument("--obs-dir", default=None,
                   help="observability output dir (obs/ subsystem): metric "
                        "snapshots (JSONL + Prometheus text), per-rank span "
                        "trace, heartbeat files, stall-watchdog reports — "
                        "schemas in theanompi_tpu/tools/check_obs_schema.py")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="seconds without global-step progress before the "
                        "stall watchdog dumps all thread stacks and arms a "
                        "post-mortem device trace (0 = disabled; set it "
                        "above the worst expected compile/eval pause)")
    p.add_argument("--metrics-snapshot-freq", type=int, default=0,
                   help="write a metrics snapshot every N steps (0 = epoch "
                        "boundaries only); requires --obs-dir")
    p.add_argument("--fleet-exporter-port", type=int, default=0,
                   help="chief-only fleet telemetry exporter (obs/"
                        "exporter.py): serve /metrics, /fleet.json and "
                        "/healthz on this port, aggregated across ranks "
                        "by tailing the obs dir (0 = off; requires "
                        "--obs-dir). Under --max-retries the exporter "
                        "outlives retries. Watch interactively with "
                        "`tmpi top OBS_DIR`")
    p.add_argument("--numerics-freq", type=int, default=0,
                   help="numerics flight recorder: compute in-graph "
                        "sentinels (grad/update/param norms, fused "
                        "non-finite count, per-rule divergence gauge) "
                        "every N steps inside the compiled step — they "
                        "drain through the dispatch pipeline, zero new "
                        "host syncs; 0 = off. GoSGD's divergence gauge "
                        "costs a param-sized pmean per numerics step, so "
                        "raise N on that rule")
    p.add_argument("--flight-window", type=int, default=64,
                   help="flight recorder: keep the last N drained step "
                        "records in a ring; an anomaly or stall dumps "
                        "them as <obs-dir>/anomaly_rank{r}/ with thread "
                        "stacks, span summary, optional state checkpoint "
                        "and an armed device trace")
    p.add_argument("--drift-tolerance", type=float, default=0.25,
                   help="model-drift watchdog (obs/drift.py): EWMA "
                        "relative-error band the tmpi_model_err_"
                        "{cost,traffic,memory} gauges may wander inside "
                        "before a drift anomaly fires (flight bundle "
                        "anomaly_rank{r}-drift/, kind=drift records in "
                        "metrics.jsonl); compare predictions vs "
                        "measured with `tmpi report OBS_DIR`")
    p.add_argument("--on-anomaly",
                   choices=["record", "dump", "halt", "rollback"],
                   default="dump",
                   help="what a detected numerics anomaly (NaN/Inf, EWMA "
                        "spike) does: record = anomaly JSONL + gauges "
                        "only; dump = also write the flight-recorder "
                        "triage bundle (default); halt = dump, then stop "
                        "training with a NumericsAnomaly error; rollback "
                        "= dump, then restore the last VERIFIED "
                        "checkpoint and keep training (needs --ckpt-dir; "
                        "see --rollback-budget/--rollback-skip)")
    p.add_argument("--rollback-budget", type=int, default=2,
                   help="with --on-anomaly rollback: how many restores a "
                        "run may absorb before the anomaly escalates to "
                        "a halt (budget exhausted = stop)")
    p.add_argument("--rollback-skip", type=int, default=1,
                   help="with --on-anomaly rollback: skip this many data "
                        "batches at the anomalous step on replay, so a "
                        "persistently bad batch cannot re-poison every "
                        "attempt (0 = replay everything)")
    p.add_argument("--max-retries", type=int, default=0,
                   help="run under the fault-tolerant supervisor "
                        "(launch/supervisor.py): retry a crashed run up "
                        "to N times, auto-resuming each attempt from the "
                        "newest VERIFIED checkpoint with exponential "
                        "backoff (requires --ckpt-dir; 0 = no supervisor)")
    p.add_argument("--retry-backoff", type=float, default=1.0,
                   help="supervisor backoff base in seconds: retry k "
                        "sleeps base * 2**(k-1), capped at 60s")
    p.add_argument("--retry-jitter", action="store_true",
                   help="decorrelated-jitter retry backoff instead of "
                        "the plain exponential ladder (sleep_k = "
                        "uniform(base, 3*sleep_{k-1}), capped): the "
                        "ladder is identical across controllers, so a "
                        "pod-wide fault retries as a synchronized "
                        "stampede — jitter de-phases the fleet; "
                        "deterministic under --seed, and the value "
                        "actually slept is recorded in the retry "
                        "JSONL record")
    p.add_argument("--scrub-interval", type=float, default=0.0,
                   help="background checkpoint scrubber: re-verify the "
                        "keep-chain every N seconds and quarantine "
                        "corrupt members (bit-rot, torn writes) into "
                        "<ckpt-dir>/quarantine/ so resume discovery "
                        "never re-pays a walk past a known-bad file "
                        "(kind=scrub records + tmpi_scrub_* gauges; "
                        "0 = off — the supervisor still scrubs once "
                        "before each retry)")
    p.add_argument("--fault-ledger", default=None, metavar="PATH",
                   help="fired-fault ledger file for --inject-fault: "
                        "fired specs are appended (fsynced BEFORE the "
                        "fault's side effect) and specs already in the "
                        "ledger arm as fired — once-only fault "
                        "semantics ACROSS process relaunches (the "
                        "chaos runner's sandbox relies on it)")
    p.add_argument("--elastic", action="store_true",
                   help="elastic world size (launch/supervisor.py + "
                        "utils/checkpoint.load_resharded): with "
                        "--max-retries, every retry re-probes the live "
                        "device world and RESHARDS the newest verified "
                        "checkpoint onto the new mesh instead of dying "
                        "on a topology change (n_devices acts as a "
                        "cap); with --resume alone, one-shot: resume a "
                        "checkpoint saved under a different topology "
                        "onto the current mesh (e.g. train-on-pod -> "
                        "serve-on-one-chip handoff). Requires "
                        "--ckpt-dir; checkpoints are always stamped "
                        "with their topology manifest, elastic or not")
    p.add_argument("--elastic-lr-scale", choices=["none", "linear"],
                   default="none",
                   help="with --elastic: rescale the recipe's base LR "
                        "by n_new/n_old on a world change (linear "
                        "scaling rule — meant for the per-worker-batch "
                        "rules whose GLOBAL batch grows with the "
                        "world; BSP's global batch is mesh-invariant, "
                        "so 'none' keeps its trajectory comparable)")
    p.add_argument("--sigterm-grace", type=float, default=0.0,
                   help="preemption grace window in seconds: > 0 "
                        "installs a SIGTERM handler that checkpoints, "
                        "marks the run resumable (resumable.json in "
                        "--ckpt-dir), and exits cleanly instead of dying "
                        "mid-step (0 = default SIGTERM disposition)")
    p.add_argument("--inject-fault", action="append", default=[],
                   metavar="KIND@STEP",
                   help="deterministic fault injection (repeatable; "
                        "utils/faults.py): crash@K, sigterm@K, "
                        "sigkill@K, ckpt_truncate@K, nan_batch@K, "
                        "loader_stall@K:SECONDS — each fires once, "
                        "before dispatching step K; exercises the "
                        "supervisor/rollback/integrity recovery paths")
    p.add_argument("--avg-freq", type=int, default=None,
                   help="EASGD/GoSGD: steps between exchanges (reference avg_freq)")
    p.add_argument("--group-size", type=int, default=None,
                   help="EASGD/GoSGD: chips per worker — each async worker is "
                        "a data-parallel group (16 workers on 256 chips = "
                        "--group-size 16)")
    p.add_argument("--alpha", type=float, default=None, help="EASGD elastic rate")
    p.add_argument("--p-push", type=float, default=None, help="GoSGD push probability")
    p.add_argument("--nproc", type=int, default=None,
                   help="spawn N controller processes on THIS machine (multi-host "
                        "simulation over virtual CPU devices; the mpirun equivalent). "
                        "On a real pod, run one tmpi per host with TMPI_* env or "
                        "TMPI_AUTO_INIT=1 instead.")
    p.add_argument("--devices-per-proc", type=int, default=None,
                   help="with --nproc: virtual CPU devices per process (default: "
                        "n_devices / nproc)")
    return p


def _strip_flags(argv: list, flags: tuple) -> list:
    """Remove ``--flag value`` / ``--flag=value`` pairs from argv."""
    out, skip = [], False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in flags:
            skip = True
            continue
        if any(a.startswith(f + "=") for f in flags):
            continue
        out.append(a)
    return out


def main(argv=None) -> int:
    import os

    argv = list(argv) if argv is not None else sys.argv[1:]
    if argv[:1] == ["lint"]:
        # static analysis subcommand (tools/lint.py); it sets up its own
        # multi-device virtual CPU platform before tracing, so every
        # entry point (tmpi lint, python -m, the lint_all alias) works
        # on a bare environment
        from theanompi_tpu.tools.lint import main as lint_main

        return lint_main(argv[1:])
    if argv[:1] == ["profile"]:
        # step-time attribution profiler (tools/profile.py): its own
        # parser + driver, dispatched before the training parser
        from theanompi_tpu.tools.profile import profile_main
        from theanompi_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

        return profile_main(argv[1:])
    if argv[:1] == ["preflight"]:
        # memory & precision pre-flight (tools/preflight.py): static
        # peak-HBM budgeting + dtype-flow lint of one engine x model x
        # mesh configuration — lowers, never executes; sets up its own
        # multi-device platform like `tmpi lint`
        from theanompi_tpu.tools.preflight import preflight_main

        return preflight_main(argv[1:])
    if argv[:1] == ["chaos"]:
        # chaos campaign runner (tools/chaos.py): fuzzed fault
        # schedules + invariant oracle + shrinker; sets up its own
        # multi-device virtual CPU platform like `tmpi lint`
        from theanompi_tpu.tools.chaos import chaos_main

        return chaos_main(argv[1:])
    if argv[:1] == ["top"]:
        # fleet console (tools/top.py): read-only viewer over an obs
        # dir (live or post-mortem) — no jax, no platform setup
        from theanompi_tpu.tools.top import top_main

        return top_main(argv[1:])
    if argv[:1] == ["report"]:
        # unified run report (tools/report.py): merge every per-rank
        # stream into one causally-grouped timeline + verdict —
        # read-only like `tmpi top`; no jax, no platform setup
        from theanompi_tpu.tools.report import report_main

        return report_main(argv[1:])
    if argv[:1] == ["serve"]:
        # inference subcommand: its own parser + driver (serve/cli.py);
        # dispatched before the training parser, whose first positional
        # is a sync rule
        from theanompi_tpu.serve.cli import serve_main
        from theanompi_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()

        return serve_main(argv[1:])
    args = build_parser().parse_args(argv)

    if args.nproc and args.nproc > 1 and (
        "TMPI_PROCESS_ID" in os.environ or "TMPI_NUM_PROCESSES" in os.environ
    ):
        # already a spawned controller: never respawn (fork-bomb guard)
        print(
            "tmpi: ignoring --nproc inside an already-spawned controller "
            f"(TMPI_PROCESS_ID={os.environ.get('TMPI_PROCESS_ID')})",
            file=sys.stderr,
        )
        args.nproc = None

    if args.nproc and args.nproc > 1:
        # mpirun equivalent: re-invoke this CLI as nproc cooperating
        # controller processes over sliced virtual CPU devices
        import shlex

        from theanompi_tpu.launch.multihost import spawn_local

        child_argv = list(argv) if argv is not None else sys.argv[1:]
        child_argv = _strip_flags(child_argv, ("--nproc", "--devices-per-proc"))
        per_proc = args.devices_per_proc or max(1, (args.n_devices or args.nproc) // args.nproc)
        codes = spawn_local(
            args.nproc,
            ["-m", "theanompi_tpu.cli", *child_argv],
            devices_per_proc=per_proc,
        )
        if any(codes):
            print(f"controller exit codes: {codes} "
                  f"({shlex.join(child_argv)})", file=sys.stderr)
        # signal deaths have NEGATIVE returncodes — max() would report 0
        # when another rank exited cleanly; any non-zero code is failure
        return 1 if any(codes) else 0

    # persistent compile cache + compile-time accounting BEFORE the
    # first compile (JAX_COMPILATION_CACHE_DIR wins; utils/compile_cache.py)
    from theanompi_tpu.utils.compile_cache import (
        CompileClock,
        enable_compile_cache,
    )

    enable_compile_cache()
    compile_clock = CompileClock()

    # join the multi-controller world BEFORE any backend use (no-op when
    # not configured; reference: MPI_GPU_Process init at worker start)
    from theanompi_tpu.parallel.distributed import initialize_distributed

    initialize_distributed()

    from theanompi_tpu.launch.session import resolve_model
    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.utils.faults import Preempted as _Preempted

    model_cls = resolve_model(args.modelfile, args.modelclass)

    overrides = {}
    if args.batch_size:
        overrides["batch_size"] = args.batch_size
    if args.synthetic:
        args.dataset = "synthetic"

    def parse_kv(pairs, flag):
        out = {}
        for kv in pairs:
            k, sep, v = kv.partition("=")
            if not sep:
                raise SystemExit(f"{flag} expects K=V, got {kv!r}")
            try:
                out[k] = json.loads(v)
            except json.JSONDecodeError:
                try:
                    # accept Python literals too: input_shape=(16,16,3)
                    out[k] = ast.literal_eval(v)
                except (ValueError, SyntaxError):
                    out[k] = v
        return out

    dataset_kwargs = parse_kv(args.dataset_arg, "--dataset-arg")
    for k, v in parse_kv(args.recipe_arg, "--recipe-arg").items():
        # recipes store shapes as tuples; JSON gives lists
        overrides[k] = tuple(v) if isinstance(v, list) else v

    rule_kwargs = {}
    if args.avg_freq is not None:
        rule_kwargs["avg_freq"] = args.avg_freq
    if args.group_size is not None:
        rule_kwargs["group_size"] = args.group_size
    if args.alpha is not None:
        rule_kwargs["alpha"] = args.alpha
    if args.p_push is not None:
        rule_kwargs["p_push"] = args.p_push

    if args.tensorboard and not args.save_dir:
        print("WARNING: --tensorboard needs --save-dir; no TB output will "
              "be written", flush=True)
    if (args.stall_timeout or args.metrics_snapshot_freq) and not args.obs_dir:
        print("WARNING: --stall-timeout/--metrics-snapshot-freq need "
              "--obs-dir; observability is off", flush=True)
    if args.fleet_exporter_port and not args.obs_dir:
        print("WARNING: --fleet-exporter-port needs --obs-dir (the "
              "exporter tails the obs dir); the fleet exporter is off",
              flush=True)
    # (--numerics-freq without --obs-dir warns inside run_training,
    # which covers API callers too)
    if args.scrub_interval and not args.ckpt_dir:
        print("WARNING: --scrub-interval needs --ckpt-dir; the "
              "checkpoint scrubber is off", flush=True)
    if args.on_anomaly == "rollback" and not args.ckpt_dir:
        raise SystemExit("--on-anomaly rollback requires --ckpt-dir "
                         "(the rollback restores a checkpoint)")
    if args.max_retries and not args.ckpt_dir:
        raise SystemExit("--max-retries requires --ckpt-dir (retries "
                         "auto-resume from the newest verified checkpoint)")
    if args.elastic and not args.ckpt_dir:
        raise SystemExit("--elastic requires --ckpt-dir (an elastic "
                         "resume reshards a checkpoint; without one "
                         "there is nothing to carry across the "
                         "topology change)")
    if args.sigterm_grace and not args.ckpt_dir:
        # without a ckpt dir the grace path has nothing to save and no
        # marker to drop — exiting 75/"resumable" would promise a
        # scheduler an auto-resume that silently restarts from step 0
        raise SystemExit("--sigterm-grace requires --ckpt-dir (the grace "
                         "window checkpoints and marks the run resumable)")

    if args.max_retries > 0:
        # fault-tolerant supervisor: bounded retry + verified
        # auto-resume + preemption-marker handling around run_training
        from theanompi_tpu.launch.supervisor import supervise_training

        def _run(**kw):
            return supervise_training(
                max_retries=args.max_retries,
                backoff_base=args.retry_backoff,
                retry_jitter=args.retry_jitter,
                **kw,
            )
        # elastic binds to the SUPERVISOR's kwarg (it re-probes the
        # world per attempt and forwards elastic=True to run_training
        # itself); the unsupervised branch below hands it straight to
        # run_training for the one-shot reshard-resume case
    else:
        _run = run_training

    inject_faults = args.inject_fault or None
    if inject_faults is not None and args.fault_ledger:
        # ledger-armed injector: once-only semantics survive process
        # relaunches (utils/faults.py module docstring) — the chaos
        # sandbox's resume launches pass the same ledger
        from theanompi_tpu.utils.faults import FaultInjector

        inject_faults = FaultInjector(inject_faults,
                                      ledger=args.fault_ledger)

    try:
        summary = _run(
            rule=args.rule.lower(),
            model_cls=model_cls,
            devices=args.n_devices or None,
            strategy=args.strategy,
            wire_codec=args.wire_codec,
            fused_update=args.fused_update,
            allreduce_buckets=args.allreduce_buckets,
            n_slices=args.slices,
            steps_per_dispatch=args.steps_per_dispatch,
            dispatch_depth=args.dispatch_depth,
            accum_steps=args.accum_steps,
            tp=args.tp,
            sp=args.sp,
            pp=args.pp,
            expert=args.expert,
            microbatches=args.microbatches,
            pp_interleave=args.pp_interleave,
            zero=args.zero,
            n_epochs=args.epochs,
            max_steps=args.max_steps,
            dataset=args.dataset,
            dataset_kwargs=dataset_kwargs,
            recipe_overrides=overrides,
            seed=args.seed,
            save_dir=args.save_dir,
            ckpt_dir=args.ckpt_dir,
            async_checkpoint=not args.sync_ckpt,
            sharded_ckpt=args.ckpt_sharded,
            resume=args.resume,
            print_freq=args.print_freq,
            tensorboard=args.tensorboard,
            profile_dir=args.profile_dir,
            profile_steps=args.profile_steps,
            obs_dir=args.obs_dir,
            stall_timeout=args.stall_timeout,
            metrics_snapshot_freq=args.metrics_snapshot_freq,
            fleet_exporter_port=args.fleet_exporter_port,
            numerics_freq=args.numerics_freq,
            flight_window=args.flight_window,
            on_anomaly=args.on_anomaly,
            drift_tolerance=args.drift_tolerance,
            rollback_budget=args.rollback_budget,
            rollback_skip=args.rollback_skip,
            sigterm_grace=args.sigterm_grace,
            inject_faults=inject_faults,
            scrub_interval=args.scrub_interval,
            elastic=args.elastic,
            elastic_lr_scale=args.elastic_lr_scale,
            **rule_kwargs,
        )
    except _Preempted as e:
        # graceful preemption: checkpointed + marked resumable inside
        # the grace window. EX_TEMPFAIL tells the scheduler this exit
        # is retryable; the next invocation (supervisor or --resume)
        # picks the run back up from the marker.
        print(json.dumps({"preempted": True, "step": e.step,
                          "resumable": True}))
        return 75  # EX_TEMPFAIL
    summary.update(compile_clock.report())
    print(json.dumps({k: v for k, v in summary.items() if k != "state"}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
