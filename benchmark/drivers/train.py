"""The training driver: times ``run_training`` from outside.

Entry the window drives: ``theanompi_tpu.launch.worker.run_training`` (what
``tmpi BSP n <modelfile> <modelclass>`` calls) with the rule of the workload
file and program defaults everywhere else (dispatch depth 1, prefetch depth
2, no fused update, no buckets, no checkpoint, no obs dir). The window is
closed by the dataset (``harness/window_dataset.py``); ``train_step_ms`` is
the window's wall time over all the steps completed in it, by the
benchmark's own stamps.

One hook reaches into the program: ``StepProbe`` wraps the engine's
``train_step`` method so that the harness can read the state of the SAME
object the window drives after its first steps (the contract's training
comparison) and, in the harness's own checks, plant a fault under it. The
compiled step, its arguments and its call are untouched.
"""

import glob
import importlib
import os
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, manifest, peaks, trace
from harness.window_dataset import WindowDataset

FOLLOW = 3  # steps the reference follows


class StepProbe:
    """Reads, from the engine the window drives: the parameters before step
    1, the optimizer's state after step 1 (the first gradient as the
    optimizer got it) and the parameters after step ``FOLLOW`` (before step
    ``FOLLOW + 1`` donates them). Copies go to the host, so the device's peak
    memory is the program's own."""

    def __init__(self, engine, moment, fault=None, trace=None):
        self.engine_cls = _attr(engine)  # the workload file's "engine": module.Class
        self.moment = moment  # the optimizer state's key that holds the first gradient
        self.fault = fault
        self.trace = trace  # (directory, first traced call, traced steps) or None
        self.calls = 0
        self.dispatch_s = []  # host seconds inside each call of the step: the dispatch
        self.p0 = self.opt1 = self.pn = None
        self._orig = None

    def __enter__(self):
        self._orig = orig = self.engine_cls.train_step
        probe = self

        def train_step(engine, state, images, labels, rng, numerics=False):
            return probe._step(orig, engine, state, images, labels, rng, numerics)

        self.engine_cls.train_step = train_step
        return self

    def _step(self, orig, engine, state, images, labels, rng, numerics):
        self.calls += 1
        if self.trace is not None:
            self._trace_tick()
        if self.calls == 1:
            self.p0 = jax.device_get(state.params)
        if self.fault == "half_batch":
            # half of the batch left out, the mean taken over the rest
            h = images.shape[0] // 2
            images = jnp.concatenate([images[:h], images[:h]])
            labels = jnp.concatenate([labels[:h], labels[:h]])
        t_call = time.perf_counter()
        if self.fault == "state_unchanged":
            # the step runs and reports, and hands back the state it was given
            new_state = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = orig(engine, state, images, labels, rng, numerics)
        else:
            new_state, metrics = orig(engine, state, images, labels, rng, numerics)
        self.dispatch_s.append(time.perf_counter() - t_call)
        if self.calls == 1:
            self.opt1 = jax.device_get(new_state.opt_state[self.moment])
        if self.calls == FOLLOW:
            self.pn = jax.device_get(new_state.params)
        return new_state, metrics

    def _trace_tick(self):
        """The device trace of ``n`` steady steps of the real loop, taken
        during warm-up so that the measured window stays clean. The host's
        tracers stay off: with them the host-side transposes of one loader-fed
        AlexNet batch alone wrote a gigabyte of events."""
        directory, first, n = self.trace
        if self.calls == first:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(directory, profiler_options=opts)
        elif self.calls == first + n:
            jax.profiler.stop_trace()
            self.trace = None

    def __exit__(self, *exc):
        self.engine_cls.train_step = self._orig
        if self.trace is not None and self.calls >= self.trace[1]:
            jax.profiler.stop_trace()  # the run ended mid-capture


def _attr(path):
    """``package.module.Name`` -> the object."""
    mod, _, name = path.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def _named(tree, values, scale=1.0):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): scale * float(v) for (p, _), v in zip(flat, values)}


def _leaf_norms(tree, scale=1.0):
    """{path: norm} by leaf, reduced on the device (free by then)."""
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(a))) for a in jax.tree_util.tree_leaves(t)])
    return _named(tree, fn(tree), scale)


def _leaf_diff(a, b, how):
    """{path: norm or largest magnitude of the leaf's difference}."""
    red = {"norm": lambda d: jnp.sqrt(jnp.sum(jnp.square(d))), "max": lambda d: jnp.max(jnp.abs(d))}[how]
    fn = jax.jit(lambda x, y: [red(p - q) for p, q in zip(jax.tree_util.tree_leaves(x),
                                                         jax.tree_util.tree_leaves(y))])
    return _named(a, fn(a, b))


def program_numbers(probe, recorder, opt):
    """What the timed call's first steps produced, in the reference's terms."""
    rows = recorder.history["train"][:FOLLOW]
    return {
        "losses": [float(r["loss"]) for r in rows],
        "grad_norms": _leaf_norms(probe.opt1, opt["first_gradient"]["norm_times"]),
        "change_norms": _leaf_diff(probe.pn, probe.p0, "norm"),
    }


def device_peak_bytes(device):
    """The chip's peak as the backend reports it: the most its live buffers
    held plus the most it reserved as scratch for a loaded program. On the TPU
    a program's temporaries (4.57 GB for the LM step) are counted under
    ``peak_bytes_reserved``, not under ``peak_bytes_in_use``."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def program_seed(seed):
    """The program's loaders seed ``RandomState(seed * 100003 + epoch)``, which
    takes 32 bits, and its key is ``PRNGKey(seed)``: fold the driver's large
    seeds into the range both accept. The data itself is made from the whole
    seed."""
    return int(seed) % 40009


def effective(config, workload, tiny):
    """The configuration and workload as run: ``--tiny`` lays each file's
    ``tiny`` block over it (CPU rehearsal only; never ``correct``)."""
    if tiny:
        config = {**config, **config.get("tiny", {})}
        workload = {**workload, **workload.get("tiny", {})}
        workload["data"] = {**workload["data"], **workload.get("tiny_data", {})}
    return config, workload


def batches_differ(got, want):
    """How many of the loader's first batches are not the reference's, bit for bit."""
    return abs(len(got) - len(want)) + sum(
        not (np.array_equal(gx, wx) and np.array_equal(gy, wy))
        for (gx, gy), (wx, wy) in zip(got, want))


def measure(ctx):
    """Set-up, window and comparison of one run. -> dict of everything the
    last line, the metric readers and the readings tool need. ``ctx["fault"]``
    (the harness's own checks) plants a fault under the timed path."""
    from theanompi_tpu.data.datasets import register_dataset
    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.utils.compile_cache import CompileClock, enable_compile_cache

    config, workload = effective(ctx["config"], ctx["workload"], ctx["tiny"])
    chips = int(workload["chips"])
    devices = jax.devices()[:chips]
    if ctx.get("clock") is None:
        enable_compile_cache()
        ctx["clock"] = CompileClock()  # ONE per process, before the first compile
    clock = ctx["clock"]
    compile_s_before = clock.seconds

    workdir = os.path.join(manifest.ROOT, ".bench_work", workload["name"])
    data_mod = manifest.load_module("data", workload["data"]["kind"])
    inner = data_mod.make(ctx["seed"], workload["data"], config, os.path.join(workdir, "data"))
    t_data = time.perf_counter()
    warmup, tracing, trace_dir = int(workload["warmup_steps"]), None, None
    if ctx["trace"]:
        # the traced steps follow the compared ones and precede the window
        trace_dir = os.path.join(workdir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing = (trace_dir, FOLLOW + 2, int(workload["trace_steps"]))
        # the loader stamps a batch up to prefetch depth + 1 = 3 steps before
        # its step: the window's first stamp has to follow the profiler's stop
        # (seconds of writing), and a few steady steps after it
        warmup = max(warmup, tracing[1] + tracing[2] + 6)
    window = WindowDataset(inner, ctx["seconds"], warmup,
                           compile_clock=clock, keep_first=FOLLOW)
    register_dataset(WindowDataset.name, lambda: window)

    model_cls = _attr(config["model"])
    pseed = program_seed(ctx["seed"])
    with StepProbe(workload["engine"], config["optimizer"]["first_gradient"]["state_key"],
                   fault=ctx.get("fault"), trace=tracing) as probe:
        summary = run_training(
            rule=workload["rule"], model_cls=model_cls, devices=devices,
            dataset=WindowDataset.name, dataset_kwargs={},
            recipe_overrides=dict(config.get("recipe_overrides", {})),
            seed=pseed, n_epochs=1, ckpt_dir=None, print_freq=0,
            return_recorder=True)
    recorder = summary.pop("recorder")
    recorder.close()
    steps, seconds = window.window()
    if not steps:
        raise SystemExit("the window closed without a step: no result")
    peak = max(device_peak_bytes(d) for d in devices)

    # the program's state is gone with run_training's frame; now the reference
    prog = program_numbers(probe, recorder, config["optimizer"])
    p0_prog, first = probe.p0, window.first
    probe.p0 = probe.pn = probe.opt1 = None
    reference = manifest.load_module("reference", config["name"])
    t_ref = time.perf_counter()
    if hasattr(data_mod, "reference_batches"):
        # batches that come out of the program's loader are part of the
        # comparison: the data kind rebuilds them apart from it
        ref_batches = data_mod.reference_batches(ctx["seed"], workload["data"], config, pseed, FOLLOW)
        differ = batches_differ(first, ref_batches)
    else:
        ref_batches, differ = first[:FOLLOW], None  # the benchmark's own, handed over as made
    ref = reference.run(config, pseed, ref_batches)
    init_gap = max(_leaf_diff(p0_prog, ref["init"], "max").values())
    del p0_prog
    ref_s = time.perf_counter() - t_ref

    nums = compare.numbers(prog, ref)
    in_window = (None if window.programs_at_open is None or window.programs_at_close is None
                 else window.programs_at_close - window.programs_at_open)
    return {
        "config": config, "workload": workload, "summary": summary, "recorder": recorder,
        "window": window, "steps": steps, "seconds": seconds, "t_open": window.stamps[window.warmup],
        "t_data": t_data,
        "peak_bytes": peak, "devices": devices, "clock": clock,
        "compile_s": (window.compile_s_at_open or 0.0) - compile_s_before, "trace_dir": trace_dir,
        "prog": prog, "ref": ref, "ref_batches": ref_batches, "pseed": pseed,
        "dispatch_s": probe.dispatch_s, "numbers": nums, "init_gap": init_gap, "input_batches_differ": differ,
        "compiles_in_window": in_window, "reference_s": ref_s,
    }


def checks_of(m):
    """{name: (value, limit)}: each number compared beside its limit."""
    limits = m["workload"]["limits"]
    out = {k: (v, limits[k]) for k, (v, _) in m["numbers"].items()}
    out["init_gap"] = (m["init_gap"], limits["init_gap"])
    if m["input_batches_differ"] is not None:
        out["input_batches_differ"] = (m["input_batches_differ"], 0)
    out["steps_not_on_device"] = (abs(m["summary"]["steps"] - m["summary"]["device_steps"]), 0)
    out["compiles_in_window"] = (-1 if m["compiles_in_window"] is None else m["compiles_in_window"], 0)
    return out


def is_correct(checks):
    return all(v == v and 0 <= v <= lim for v, lim in checks.values())


def find_trace(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def run(ctx):
    """One benchmark run -> the last line's fields."""
    m = measure(ctx)
    config, workload = m["config"], m["workload"]
    steps, seconds = m["steps"], m["seconds"]
    step_ms = 1e3 * seconds / steps
    setup_s = m["t_open"] - ctx["t_process_start"]
    dev0 = m["devices"][0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(m["devices"]), "memory_peak_bytes": m["peak_bytes"]}

    rec = m["recorder"]
    w, last = m["window"].warmup, len(m["window"].stamps) - 1
    waits = sum(rec.timings["wait"][w + 1:last + 1])
    bracket = sum(rec.timings["step"][w + 1:last + 1]) + waits
    print(f"[bench] {workload['name']}: {steps} steps in {seconds:.3f} s -> "
          f"{step_ms:.3f} ms/step, {workload['items_per_step'] * steps / seconds:.0f} "
          f"{workload['item']}/s; recorder brackets over the same steps "
          f"{1e3 * bracket / steps:.3f} ms/step, of it loader wait "
          f"{1e3 * waits / steps:.3f}; setup {setup_s:.2f} s "
          f"(compile {m['compile_s']:.2f} s, {m['clock'].programs} programs, "
          f"{m['clock'].cache_hits} cache hits); inner epochs {m['window'].inner_epochs}; "
          f"reference {m['reference_s']:.1f} s", flush=True)
    t0, stamps = ctx["t_process_start"], m["window"].stamps
    t_backend = ctx.get("t_backend", t0)
    print(f"[bench] set-up parts: interpreter and backend {t_backend - t0:.2f} s, "
          f"data {m['t_data'] - t_backend:.2f} s, model and engine build to the "
          f"first batch {stamps[0] - m['t_data']:.2f} s, compile or cache load and "
          f"{m['window'].warmup} warm-up steps {m['t_open'] - stamps[0]:.2f} s", flush=True)
    brackets = rec.timings["step"]
    longest = sorted(range(w + 1, last + 1), key=lambda i: -brackets[i])[:3]
    print("[bench] longest step brackets in the window: " + ", ".join(
        f"{1e3 * brackets[i]:.1f} ms at process +{stamps[i] - t0:.1f} s" for i in longest)
        + f"; host time inside the step's call (dispatch) {1e3 * sum(m['dispatch_s'][w + 1:last + 1]) / steps:.3f} ms a step",
        flush=True)
    for name, (v, where) in m["numbers"].items():
        print(f"[bench] {name} = {v:.6g} at {where}", flush=True)
    print(f"[bench] losses program {m['prog']['losses']} reference {m['ref']['losses']}", flush=True)

    values = {"train_step_ms": step_ms, "setup_s": setup_s}
    breakdown = None
    man = ctx["manifest"]
    if ctx["trace"]:
        path = find_trace(m["trace_dir"]) if m["trace_dir"] else None
        reduced = trace.reduce(path, len(m["devices"])) if path else None
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = trace.breakdown(reduced)
        rctx = {"trace": reduced, "recorder": rec, "summary": m["summary"], "cell": workload,
                "config": config, "steps": steps, "seconds": seconds, "first_step": w + 1,
                "last_step": last, "compile_s": m["compile_s"], "chips": len(m["devices"]),
                "peaks": None if ctx["tiny"] else peaks.peaks_for(dev0.device_kind),
                "flops": manifest.load_module("flops", config["name"])}
        group = manifest.metrics_for(man, "per_layer", workload["name"])
        for entry in group:
            reader = manifest.load_module("metrics", entry["name"])
            v = reader.read(rctx)
            if v is not None:
                values[entry["name"]] = v
    else:
        group = manifest.metrics_for(man, "end_to_end", workload["name"])
    metrics = {e["name"]: (values[e["name"]], e["unit"]) for e in group if e["name"] in values}

    checks = checks_of(m)
    correct = is_correct(checks) and not ctx["tiny"]
    return {"correct": correct, "attempted": steps,
            "failed": abs(m["summary"]["steps"] - m["summary"]["device_steps"]),
            "metrics": metrics, "device": device, "checks": checks, "breakdown": breakdown}
