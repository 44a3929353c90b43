"""``tmpi serve`` — the serving subcommand (dispatched from cli.py).

Serve a training run's checkpoints over HTTP with dynamic
micro-batching and (``--watch``) checkpoint hot-reload::

    tmpi serve --ckpt-dir runs/ck --model cifar10 --watch \\
               --buckets 1,8,32,128 --max-queue 256 --deadline-ms 250 \\
               --obs-dir runs/obs --port 8300

Two engine kinds share this command:

- **Eval-forward** (default): one logits row per request
  (serve/engine.py). ``--buckets`` are its BATCH buckets — requests
  pad UP to the smallest fitting batch size, one compiled program per
  bucket. This flag applies to the eval engine ONLY.
- **LM decode** (``--decode``): continuous-batching generation over a
  paged KV-cache (serve/decode/) — requests are 1-D token prompts,
  responses are generated continuations. Its compiled-program knobs
  are ``--prefill-buckets`` (prompt-length buckets, page-size
  multiples) and ``--kv-pages`` (total KV pool pages) — NOT
  ``--buckets``. ``--shard tensor`` serves Megatron tensor-sharded
  params placed by ``ShardingRecipe.serve_tensor`` (degenerates to
  replicated on one device)::

      tmpi serve --decode --shard tensor --ckpt-dir runs/ck \\
                 --model runs/lm.py:TransformerLMModel \\
                 --prefill-buckets 16,64 --kv-pages 256

SIGTERM drains gracefully: admission stops (healthz flips 503, so a
load balancer rotates the replica out), the queued backlog is served —
for decode, every admitted generation runs to completion — then the
process exits. ``--selftest N`` skips the HTTP server and drives N
closed-loop local requests instead (smoke/CI path; prints the final
stats line and exits).

``--replicas N`` (N > 1) fronts an N-member replica group through
serve/router.py instead of one engine — for BOTH engine kinds (the
decode engine exposes the same submit/drain/set_params surface, so the
router is unchanged): health-checked least-loaded routing with bounded
failover, a supervisor restarting crashed members with jitter backoff,
central hot-reload under ``--watch``, and ``kind=router`` records in
``<obs-dir>/router.jsonl`` (members write ``serve_r<id>.jsonl`` /
``decode_r<id>.jsonl``). The final stdout line is then a schema-valid
``router`` snapshot record rather than a ``serve``/``decode`` one.
"""

from __future__ import annotations

import argparse
import json
import sys


def build_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tmpi serve",
        description="TPU inference: dynamic micro-batching engine with "
                    "checkpoint hot-reload",
        allow_abbrev=False,
    )
    p.add_argument("--ckpt-dir", required=True,
                   help="training run's checkpoint dir; the newest "
                        "VERIFIED checkpoint is served (keep-chain walk)")
    p.add_argument("--model", required=True,
                   help="zoo short name (cifar10, alexnet, ...), or "
                        "module:Class / path.py:Class — must match the "
                        "recipe that trained the checkpoints (the resume "
                        "contract)")
    p.add_argument("--recipe-arg", action="append", default=[], metavar="K=V",
                   help="recipe override (repeatable, JSON values) — must "
                        "mirror the overrides the training run used")
    p.add_argument("--buckets", default="1,8,32,128",
                   help="EVAL-FORWARD engine only: comma-separated batch "
                        "buckets; requests pad UP to the smallest fitting "
                        "bucket, one compiled program per bucket, all "
                        "AOT-warmed at startup (the decode engine's "
                        "program knobs are --prefill-buckets/--kv-pages)")
    p.add_argument("--decode", action="store_true",
                   help="LM decode serving (serve/decode/): requests are "
                        "1-D token prompts, responses generated "
                        "continuations via continuous batching over a "
                        "paged KV-cache; needs a model with the "
                        "incremental decode surface (transformer_lm zoo "
                        "family)")
    p.add_argument("--prefill-buckets", default="16,64",
                   help="DECODE engine only: comma-separated prompt-length "
                        "buckets (page-size multiples); one compiled "
                        "prefill program per bucket + ONE decode program, "
                        "all AOT-warmed")
    p.add_argument("--kv-pages", type=int, default=256,
                   help="DECODE engine only: total pages in the "
                        "preallocated KV pool (admission reserves "
                        "worst-case pages per generation)")
    p.add_argument("--page-size", type=int, default=16,
                   help="DECODE engine only: positions per KV page")
    p.add_argument("--max-seqs", type=int, default=8,
                   help="DECODE engine only: decode batch width "
                        "(concurrent generations)")
    p.add_argument("--max-new-tokens", type=int, default=32,
                   help="DECODE engine only: default per-request output "
                        "budget")
    p.add_argument("--shard", choices=("none", "tensor"), default="none",
                   help="DECODE engine only: 'tensor' serves Megatron "
                        "tensor-sharded params over all local devices "
                        "(ShardingRecipe.serve_tensor; checkpoints load "
                        "through load_resharded onto the serving mesh); "
                        "'none' = replicated single-device serving")
    p.add_argument("--max-queue", type=int, default=256,
                   help="admission bound: a full queue rejects with "
                        "retry-after instead of growing latency unbounded")
    p.add_argument("--deadline-ms", type=float, default=1000.0,
                   help="default per-request deadline (0 = none): expired "
                        "requests are rejected, not served")
    p.add_argument("--watch", action="store_true",
                   help="hot-reload: poll the keep-chain and atomically "
                        "swap to newer verified checkpoints while serving")
    p.add_argument("--poll-interval", type=float, default=2.0,
                   help="--watch poll cadence in seconds")
    p.add_argument("--obs-dir", default=None,
                   help="telemetry dir: serve.jsonl records "
                        "(kind=serve/reload; tools/check_obs_schema.py)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8300,
                   help="HTTP port (serve/frontend.py)")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="replica-group serving (serve/router.py): N "
                        "engines behind one endpoint with health-checked "
                        "least-loaded routing, bounded failover, and a "
                        "supervisor that restarts crashed members; "
                        "checkpoint hot-reload becomes central (one load, "
                        "fleet-wide swap). 1 = the classic single engine")
    p.add_argument("--selftest", type=int, default=0, metavar="N",
                   help="no HTTP: run N closed-loop local requests, print "
                        "stats JSON, exit (smoke path)")
    return p


def _resolve_serve_model(spec: str, recipe_args: list):
    """Model instance from a zoo short name or module:Class spec."""
    import ast

    from theanompi_tpu.launch.session import resolve_model
    from theanompi_tpu.models import MODEL_REGISTRY

    if ":" in spec:
        modelfile, _, classname = spec.rpartition(":")
        cls = resolve_model(modelfile, classname)
    elif spec.lower() in MODEL_REGISTRY:
        modelfile, classname = MODEL_REGISTRY[spec.lower()]
        cls = resolve_model(modelfile, classname)
    else:
        raise SystemExit(
            f"--model {spec!r}: not a zoo short name "
            f"({sorted(MODEL_REGISTRY)}) and not module:Class"
        )
    overrides = {}
    for kv in recipe_args:
        k, sep, v = kv.partition("=")
        if not sep:
            raise SystemExit(f"--recipe-arg expects K=V, got {kv!r}")
        try:
            val = json.loads(v)
        except json.JSONDecodeError:
            try:
                val = ast.literal_eval(v)
            except (ValueError, SyntaxError):
                val = v
        overrides[k] = tuple(val) if isinstance(val, list) else val
    recipe = cls.default_recipe()
    if overrides:
        recipe = recipe.replace(**overrides)
    return cls(recipe)


def replica_sharding(rid, base=None):
    """The serving recipe of fleet member ``rid``: its own local device,
    ``rid % n_local`` — an N-replica fleet on a four-chip host must not
    sit on chip 0. A single engine (``rid`` None) and tensor-sharded
    serving (``base`` spans every local device already) keep ``base``."""
    if rid is None or base is not None:
        return base
    import jax

    from theanompi_tpu.parallel.recipe import ShardingRecipe

    local = jax.local_devices()
    return ShardingRecipe.serve(device=local[rid % len(local)])


def serve_main(argv=None) -> int:
    args = build_serve_parser().parse_args(argv)

    from theanompi_tpu.serve.engine import ServeEngine
    from theanompi_tpu.serve.reload import CheckpointReloader
    from theanompi_tpu.utils.compile_cache import CompileClock

    compile_clock = CompileClock()

    model = _resolve_serve_model(args.model, args.recipe_arg)
    buckets = tuple(int(b) for b in args.buckets.split(","))
    replicas = max(1, int(args.replicas))

    if args.decode:
        from theanompi_tpu.serve.decode import DecodeEngine

        prefill_buckets = tuple(
            int(b) for b in args.prefill_buckets.split(","))
        sharding = None
        if args.shard == "tensor":
            # specs are born in parallel/recipe.py (source guard:
            # serve/* never constructs a PartitionSpec)
            from theanompi_tpu.parallel.recipe import ShardingRecipe

            sharding = ShardingRecipe.serve_tensor(model)

        def _make(rid=None):
            return DecodeEngine(
                model,
                prefill_buckets=prefill_buckets,
                kv_pages=args.kv_pages,
                page_size=args.page_size,
                max_seqs=args.max_seqs,
                max_new_tokens=args.max_new_tokens,
                max_queue=args.max_queue,
                default_deadline_ms=args.deadline_ms or None,
                obs_dir=args.obs_dir,
                replica_id=rid,
                sink_name=("decode.jsonl" if rid is None
                           else f"decode_r{rid}.jsonl"),
                sharding=replica_sharding(rid, sharding),
            )

        engine_kind, program_note = "decode", (
            f"prefill buckets {prefill_buckets} + 1 decode program")
    else:
        def _make(rid=None):
            return ServeEngine(
                model,
                buckets=buckets,
                max_queue=args.max_queue,
                default_deadline_ms=args.deadline_ms or None,
                obs_dir=args.obs_dir,
                replica_id=rid,
                sink_name=("serve.jsonl" if rid is None
                           else f"serve_r{rid}.jsonl"),
                sharding=replica_sharding(rid),
            )

        engine_kind, program_note = "serve", f"buckets {buckets}"

    if replicas == 1:
        engine = _make()
        step = engine.load_initial(args.ckpt_dir)
        compiled = engine.warmup()
        print(f"[serve] {engine_kind} engine: {model.name} step {step}; "
              f"{compiled} programs AOT-warmed ({program_note})",
              flush=True)
        # where the served params actually live, read off the placed
        # arrays (chip_smoke.py checks it) + what the warm-up compiled
        print("[serve] placement " + json.dumps(
            {**engine.params_device(), **compile_clock.report()}),
            flush=True)
        engine.start()
        final_record = (engine.decode_record if args.decode
                        else engine.serve_record)
    else:
        from theanompi_tpu.serve.router import Router

        def _member(rid):
            # the replica factory: the supervisor reuses it to restart
            # crashed members from the newest verified checkpoint
            eng = _make(rid)
            eng.load_initial(args.ckpt_dir)
            eng.warmup()
            eng.start()
            return eng

        engine = Router(
            _member, replicas,
            obs_dir=args.obs_dir,
            default_deadline_ms=args.deadline_ms or None,
        )
        engine.start()
        print(f"[serve] {replicas}-replica {engine_kind} fleet serving "
              f"{model.name} step {engine.params_step}; {program_note} "
              "AOT-warmed per member", flush=True)
        final_record = engine.router_record
    reloader = None
    if args.watch:
        # fronting a Router this is CENTRAL hot-reload: one checkpoint
        # load, one set_params fan-out, every replica swaps to the
        # same step (the Router duck-types the reloader's engine)
        reloader = CheckpointReloader(
            engine, args.ckpt_dir, interval=args.poll_interval
        )
        reloader.start()

    def _shutdown():
        # reloader FIRST: a poll landing after the final record would
        # print past the "last stdout line is a schema-valid serve
        # record" contract; then drain (idempotent, like stop)
        if reloader is not None:
            reloader.stop()
        engine.drain(timeout=30.0)

    try:
        if args.selftest:
            import numpy as np

            rng = np.random.RandomState(0)
            if args.decode:
                # decode selftest: a 1-token prompt (decode program
                # only), then the longest prompt each prefill bucket
                # admits (its last token rides the decode step), in
                # rotation — len(buckets) + 1 requests run every program
                vocab = int(model.recipe.num_classes)
                sizes = [1] + [b + 1 for b in prefill_buckets]
                for i in range(args.selftest):
                    n = sizes[i % len(sizes)]
                    res = engine.infer(
                        rng.randint(0, vocab, size=n, dtype=np.int32))
                    print(f"[serve.selftest] request {i}: prompt {n} -> "
                          f"{len(res.tokens)} tokens at step {res.step}",
                          flush=True)
            else:
                shape = tuple(model.recipe.input_shape)
                for _ in range(args.selftest):
                    engine.infer(rng.randn(*shape))
            _shutdown()
            if replicas == 1:
                # still the warm-up's count: no request retraced anything
                print(f"[serve.selftest] {engine.compile_count} programs "
                      "traced in all", flush=True)
            # LAST stdout line = one schema-valid stats record
            # (kind=serve/decode, or kind=router for a replica fleet)
            print(json.dumps(final_record()))
            return 0

        from theanompi_tpu.serve.frontend import serve_http

        httpd = serve_http(engine, host=args.host, port=args.port)

        import signal
        import threading

        def _graceful(signum, frame):
            # SIGTERM: flip to draining (healthz -> 503 rotates the
            # replica out), serve the queued backlog, then stop the
            # accept loop — all off the signal handler's thread.
            # _shutdown (not a bare drain): the reloader must stop
            # BEFORE the engine retires its sink, or a poll landing
            # mid-drain prints past the final serve record and its
            # reload record is silently dropped
            def _drain_then_stop():
                _shutdown()
                httpd.shutdown()

            threading.Thread(target=_drain_then_stop,
                             name="tmpi-serve-drain", daemon=True).start()

        signal.signal(signal.SIGTERM, _graceful)
        print(f"[serve] http on {args.host}:{httpd.server_address[1]} "
              "(POST /infer, GET /healthz, GET /metrics)", flush=True)
        try:
            httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            httpd.server_close()
        _shutdown()
        print(json.dumps(final_record()), flush=True)
        return 0
    finally:
        _shutdown()


if __name__ == "__main__":
    sys.exit(serve_main())
