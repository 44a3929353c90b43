"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
published widths of models this repo supports, and checks what comes out:

    python chip_smoke.py             # one chip: the three phases below
    python chip_smoke.py --chips 4   # four chips: data-parallel training only
    python chip_smoke.py --tiny      # rehearsal: toy widths, any platform

One chip (each phase is one child process, so the chip is free in between):

- ``train-lm``       ``tmpi BSP 1 theanompi_tpu.models.lm TransformerLM_136M``
                     (12 x 768, T=1024, vocab 32768, bf16, Pallas flash
                     attention forward and backward), a few steps, one
                     verified checkpoint.
- ``serve-decode``   ``tmpi serve --decode`` on that checkpoint: paged
                     KV-cache, two prefill buckets + the decode program.
- ``train-alexnet``  ``tmpi BSP 1 theanompi_tpu.models.alex_net AlexNet`` at
                     the zoo's single-chip batch (1024), fed by the recipe's
                     own ``imagenet`` pipeline (uint8 shards made from a seed,
                     host crop/mirror, normalisation on the device).

Four chips (``--chips 4``, one child process drives all four): BSP-4 against
BSP-1 on the same batches, then EASGD-4 and GoSGD-4 through ``run_training``.

The LAST line of standard output is always one JSON object
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it; everything else (losses, step counts, compile
seconds, per-phase verdicts) is printed on earlier lines. ``ok`` is true only
when the platform is ``tpu``, the widths are the published ones and every
phase passed: without an accelerator the script refuses at once, and
``--tiny`` never says ``ok``. The exit code is 0 exactly when ``ok`` is true.

The parent process never imports JAX (a parent that touched it would hold
the chip its children need); it prints its verdict after every child has
been reaped, so nothing can write after the last line.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
TMPI = [sys.executable, "-m", "theanompi_tpu.cli"]
SELF = [sys.executable, os.path.abspath(__file__)]  # the --_child bodies

# the contract allows 1200 s, compilation included; keep a margin for the
# interpreter start-ups and the clean-up
TOTAL_BUDGET_S = 1080.0

UNKNOWN_DEVICE = {"platform": None, "kind": None, "count": 0}

# Environment switches that would route the smoke path around the kernels or
# the native loader it is meant to prove; never handed to a child.
HIDING_ENV = ("TMPI_PALLAS", "TMPI_NATIVE")


def say(line: str) -> None:
    print(line, flush=True)


# -- children -----------------------------------------------------------------


def child_env() -> dict:
    env = dict(os.environ)
    for name in HIDING_ENV:
        if name in env:
            say(f"[smoke] not passing {name}={env.pop(name)!r} to the phases: "
                "it would hide the path under test")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def run_child(cmd: list, timeout: float, env: dict) -> tuple:
    """Run one child to its end, re-printing its stdout as it arrives.
    Returns ``(returncode, stdout_lines, timed_out)``. The child leads its own
    process group, which is killed at the time limit and again on the way out,
    so nothing it started can outlive the phase (or write after our verdict)."""
    proc = subprocess.Popen(
        cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=None,
        text=True, bufsize=1, start_new_session=True,
    )
    timed_out = threading.Event()

    def _on_timeout():
        timed_out.set()
        _kill_group(proc)

    timer = threading.Timer(max(1.0, timeout), _on_timeout)
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            say("  | " + line[:400])
        rc = proc.wait()
    finally:
        timer.cancel()
        timer.join()
        _kill_group(proc)
        proc.stdout.close()
    return rc, lines, timed_out.is_set()


def last_json(lines: list, key: str) -> Optional[dict]:
    """The last stdout line that is a JSON object holding ``key``."""
    for line in reversed(lines):
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if isinstance(d, dict) and key in d:
                return d
    return None


# -- phases -------------------------------------------------------------------


@dataclass
class Phase:
    name: str
    cmd: list
    # (stdout lines, facts) -> {check name: bool}; numbers worth a line
    # go into facts, which the phase's verdict line carries
    check: Callable
    timeout: float = 600.0
    needs: tuple = ()  # phases whose output this one reads
    facts: dict = field(default_factory=dict)


def _finite(xs) -> bool:
    return bool(xs) and all(isinstance(x, (int, float)) and math.isfinite(x)
                            for x in xs)


def train_checks(lines: list, facts: dict, *, rec_dir: str, steps: int,
                 n_classes: int, platform: str,
                 ckpt_dir: Optional[str] = None) -> dict:
    summary = last_json(lines, "device_steps") or {}
    losses = []
    for path in glob.glob(os.path.join(rec_dir, "*.jsonl")):
        with open(path) as f:
            rows = [json.loads(l) for l in f if l.strip()]
        losses += [r["loss"] for r in rows if r.get("kind") == "train"]
    dev = summary.get("device") or {}
    chance = math.log(n_classes)
    facts.update(
        losses=[round(x, 4) for x in losses], chance_loss=round(chance, 4),
        steps=summary.get("steps"), device_steps=summary.get("device_steps"),
        device=dev, compile_seconds=summary.get("compile_seconds"),
        compile_cache_hits=summary.get("compile_cache_hits"),
        host_loader=next((l.split("host loader: ", 1)[1] for l in lines
                          if "host loader: " in l), None),
    )
    checks = {
        "summary_printed": bool(summary),
        "ran_on_expected_device": dev.get("platform") == platform
        and dev.get("count") == 1,
        "all_steps_dispatched": summary.get("steps") == steps,
        "device_steps_equal_steps": summary.get("device_steps") == steps,
        "one_finite_loss_per_step": len(losses) == steps and _finite(losses),
        # untrained weights: the first loss sits near the chance-level
        # cross-entropy ln(classes) (AlexNet's unit biases put it a little
        # above); garbage out of a kernel shows up here before anywhere
        "first_loss_near_chance": bool(losses)
        and abs(losses[0] - chance) <= 0.25 * chance,
    }
    if ckpt_dir is not None:
        checks["checkpoint_written"] = os.path.exists(
            os.path.join(ckpt_dir, f"ckpt_{steps}.npz"))
    return checks


def serve_checks(lines: list, facts: dict, *, n_requests: int,
                 n_buckets: int, platform: str, params_step: int) -> dict:
    warmed = next((l for l in lines if "programs AOT-warmed" in l), "")
    placement = {}
    for l in lines:
        if l.startswith("[serve] placement "):
            placement = json.loads(l.split("placement ", 1)[1])
    answered = [int(l.split("-> ", 1)[1].split()[0]) for l in lines
                if l.startswith("[serve.selftest] request")]
    record = last_json(lines, "metrics") or {}
    m = record.get("metrics", {})
    facts.update(
        placement=placement, tokens_per_request=answered,
        compile_seconds=placement.get("compile_seconds"),
        compile_cache_hits=placement.get("compile_cache_hits"),
        kv_pages_out=m.get("tmpi_decode_kv_pages_out_total"),
        kv_pages_in=m.get("tmpi_decode_kv_pages_in_total"),
    )
    return {
        # warmed before the first request, and no request retraced any
        "compiled_programs_is_buckets_plus_one":
            f"; {n_buckets + 1} programs AOT-warmed" in warmed
            and f"[serve.selftest] {n_buckets + 1} programs traced in all"
            in lines,
        "params_on_expected_platform": placement.get("platform") == platform,
        "every_request_returned_tokens": len(answered) == n_requests
        and all(n >= 1 for n in answered),
        "served_count_matches": m.get("tmpi_decode_served_total") == n_requests
        and m.get("tmpi_decode_failed_total") == 0
        and m.get("tmpi_decode_tokens_total") == sum(answered),
        "kv_conserved": m.get("tmpi_decode_kv_pages_out_total")
        == m.get("tmpi_decode_kv_pages_in_total")
        and m.get("tmpi_decode_kv_pages_used") == 0,
        "serves_the_trained_step": record.get("params_step") == params_step,
    }


def multichip_checks(lines: list, facts: dict, *, platform: str) -> dict:
    result = last_json(lines, "multichip") or {}
    facts.update(result.get("multichip", {}))
    checks = dict(result.get("checks", {}))
    checks["result_printed"] = bool(result)
    checks["ran_on_expected_platform"] = result.get("platform") == platform
    return checks


def one_chip_phases(tiny: bool, out: str, device: dict) -> list:
    platform = device["platform"]
    ck, shards = os.path.join(out, "ck"), os.path.join(out, "shards")
    if tiny:
        lm_recipe = ["--recipe-arg", "input_shape=[64]",
                     "--recipe-arg", "num_classes=32",
                     "--recipe-arg", "d_model=32", "--recipe-arg", "n_heads=2",
                     "--recipe-arg", "n_layers=2", "--recipe-arg", "d_ff=64"]
        lm = dict(batch=4, steps=3, vocab=32, model="transformer_lm",
                  buckets=(16, 32), serve=["--page-size", "4", "--kv-pages",
                                           "64", "--max-seqs", "4",
                                           "--max-new-tokens", "8"])
        alex = dict(batch=8, steps=2, stored=80,
                    recipe=["--recipe-arg", "input_shape=[67,67,3]",
                            "--dataset-arg", "crop=67"])
    else:
        # the recipes' own widths; only row counts are cut
        lm_recipe = []
        lm = dict(batch=8, steps=4, vocab=32768, model="transformer_lm_136m",
                  buckets=(128, 512), serve=["--page-size", "16", "--kv-pages",
                                             "512", "--max-seqs", "8",
                                             "--max-new-tokens", "64"])
        alex = dict(batch=1024, steps=3, stored=256, recipe=[])
    n_requests = 2 * (len(lm["buckets"]) + 1)

    rec_lm, rec_alex = os.path.join(out, "rec-lm"), os.path.join(out, "rec-alexnet")
    return [
        Phase("train-lm", [
            *TMPI, "BSP", "1", "theanompi_tpu.models.lm", "TransformerLM_136M",
            "--synthetic", *lm_recipe, "--batch-size", str(lm["batch"]),
            "--max-steps", str(lm["steps"]), "--epochs", "1",
            "--dataset-arg", f"n_train={lm['batch'] * lm['steps']}",
            "--dataset-arg", f"n_val={lm['batch']}",
            "--ckpt-dir", ck, "--sync-ckpt",
            "--save-dir", rec_lm, "--print-freq", "1",
        ], functools.partial(
            train_checks, rec_dir=rec_lm, steps=lm["steps"],
            n_classes=lm["vocab"], platform=platform, ckpt_dir=ck)),
        Phase("serve-decode", [
            *TMPI, "serve", "--decode", "--model", lm["model"], *lm_recipe,
            "--ckpt-dir", ck,
            "--prefill-buckets", ",".join(str(b) for b in lm["buckets"]),
            *lm["serve"], "--deadline-ms", "60000",
            "--selftest", str(n_requests),
        ], functools.partial(
            serve_checks, n_requests=n_requests, n_buckets=len(lm["buckets"]),
            platform=platform, params_step=lm["steps"]),
            needs=("train-lm",)),
        Phase("make-shards", [
            *SELF, "--_child", "shards", "--_out", shards,
            "--_rows", str(alex["batch"] * alex["steps"]),
            "--_val-rows", str(alex["batch"]), "--_side", str(alex["stored"]),
        ], lambda lines, facts: {"shards_written": bool(
            glob.glob(os.path.join(shards, "train_images_*.npy")))}),
        Phase("train-alexnet", [
            *TMPI, "BSP", "1", "theanompi_tpu.models.alex_net", "AlexNet",
            "--dataset", "imagenet", "--dataset-arg", f"root={shards}",
            *alex["recipe"], "--batch-size", str(alex["batch"]),
            "--max-steps", str(alex["steps"]), "--epochs", "1",
            "--save-dir", rec_alex, "--print-freq", "1",
        ], functools.partial(
            train_checks, rec_dir=rec_alex, steps=alex["steps"],
            n_classes=1000, platform=platform),
            needs=("make-shards",)),
    ]


def four_chip_phases(tiny: bool, out: str, device: dict) -> list:
    return [Phase("train-4chip", [
        *SELF, "--_child", "multichip", "--_out", out,
        *(["--tiny"] if tiny else []),
    ], functools.partial(multichip_checks, platform=device["platform"]),
        timeout=900.0)]


def run_phases(phases: list, env: dict, deadline: float) -> bool:
    passed_by_name = {}
    for ph in phases:
        t0 = time.monotonic()
        missing = [n for n in ph.needs if not passed_by_name.get(n)]
        left = deadline - t0
        if missing:
            verdict = {"phase": ph.name, "passed": False,
                       "skipped": f"needs {missing}"}
        elif left <= 5.0:
            verdict = {"phase": ph.name, "passed": False,
                       "skipped": "the script's time budget is spent"}
        else:
            say(f"[smoke] phase {ph.name}: {' '.join(ph.cmd)}")
            rc, lines, timed_out = run_child(ph.cmd, min(ph.timeout, left), env)
            try:
                checks = dict(ph.check(lines, ph.facts))
            except Exception as e:  # noqa: BLE001 — a phase whose output
                # cannot even be read has failed; the verdict line must
                # still be printed and the contract line still comes last
                checks = {"output_readable": False}
                ph.facts["error"] = repr(e)
            checks["exit_code_0"] = rc == 0 and not timed_out
            verdict = {
                "phase": ph.name, "passed": all(checks.values()),
                "seconds": round(time.monotonic() - t0, 1), "rc": rc,
                "timed_out": timed_out, **ph.facts,
                "failed_checks": sorted(k for k, v in checks.items() if not v),
            }
        passed_by_name[ph.name] = verdict["passed"]
        say(json.dumps(verdict, default=str))
    return all(passed_by_name.values())


# -- the parent ---------------------------------------------------------------


def probe_device(env: dict) -> dict:
    """Ask JAX, in a child, what it finds; also makes the child look the
    device up in the repo's peak table, which refuses an unknown TPU."""
    rc, lines, _ = run_child([*SELF, "--_child", "probe"], 120.0, env)
    found = last_json(lines, "platform") if rc == 0 else None
    if not found:
        say("[smoke] the device probe failed (no JAX backend, or this "
            "directory holds no theanompi_tpu package)")
        return dict(UNKNOWN_DEVICE)
    return {k: found[k] for k in ("platform", "kind", "count")}


def main(argv=None, phases_for=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at toy widths on any platform; every "
                         "phase runs, the final ok is false by construction")
    ap.add_argument("--_child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--_out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--_rows", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--_val-rows", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--_side", type=int, default=256, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args._child:
        return CHILDREN[args._child](args)

    t_start = time.monotonic()
    env = child_env()
    device = dict(UNKNOWN_DEVICE)
    ok = False
    out = None
    try:
        device = probe_device(env)
        say(f"[smoke] device: {json.dumps(device)}")
        if device["platform"] != "tpu" and not args.tiny:
            say(f"[smoke] refusing: platform {device['platform']!r} is not "
                "'tpu' (use --tiny to rehearse the control flow elsewhere)")
        elif (device["count"] or 0) < args.chips:
            say(f"[smoke] refusing: --chips {args.chips} needs {args.chips} "
                f"devices, JAX reports {device['count']}")
        else:
            out = tempfile.mkdtemp(prefix="tmpi_chip_smoke_")
            build = phases_for or (four_chip_phases if args.chips == 4
                                   else one_chip_phases)
            all_passed = run_phases(build(args.tiny, out, device), env,
                                    t_start + TOTAL_BUDGET_S)
            ok = all_passed and device["platform"] == "tpu" and not args.tiny
            if args.tiny:
                say("[smoke] --tiny: toy widths prove nothing about the "
                    "chip; ok stays false")
    finally:
        if out is not None:
            shutil.rmtree(out, ignore_errors=True)
        say(f"[smoke] {time.monotonic() - t_start:.0f} s in all")
        # the contract line: last, once, after every child has been reaped
        say(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


# -- child bodies (these import the package, and JAX with it) -----------------


def _child_probe(args) -> int:
    import jax

    from theanompi_tpu import native
    from theanompi_tpu.utils.flops import peak_flops

    d = jax.devices()[0]
    say(json.dumps({
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
        "peak_bf16_flops": peak_flops(d),  # raises on a TPU it does not know
        "jax": jax.__version__, "native_loader": native.describe(),
    }))
    return 0


def _child_shards(args) -> int:
    """Seeded uint8 ImageNet-format shards for the recipe's own pipeline."""
    import numpy as np

    from theanompi_tpu.data.imagenet import write_shards

    rng = np.random.RandomState(0)
    for split, rows in (("train", args._rows), ("val", args._val_rows)):
        write_shards(
            args._out, split,
            rng.randint(0, 256, size=(rows, args._side, args._side, 3),
                        dtype=np.uint8),
            rng.randint(0, 1000, size=rows).astype(np.int64),
            shard_size=1024,
        )
    say(f"[shards] {args._rows} train + {args._val_rows} val rows of "
        f"{args._side}x{args._side}x3 uint8 under {args._out}")
    return 0


def _child_multichip(args) -> int:
    """BSP-4 vs BSP-1 on the same batches (the parity tests/test_bsp.py pins
    on the CPU mesh), then EASGD-4 and GoSGD-4 through run_training — one
    process drives all four chips."""
    import jax
    import numpy as np

    from theanompi_tpu import nn
    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.alex_net import AlexNet
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.bsp import BSPEngine
    from theanompi_tpu.parallel.mesh import put_global_batch
    from theanompi_tpu.utils.compile_cache import (
        CompileClock,
        enable_compile_cache,
    )

    enable_compile_cache()
    clock = CompileClock()
    devs = jax.devices()
    steps, seed = 3, 0
    if args.tiny:
        overrides = dict(input_shape=(67, 67, 3), batch_size=16)
        worker_batch = 4
    else:
        overrides = dict(batch_size=1024)  # the zoo's global batch
        worker_batch = 128  # the recipe's own per-worker batch
    recipe = AlexNet.default_recipe().replace(**overrides)

    class AlexNetNoDropout(AlexNet):
        # BSP folds each device's index into the dropout key, so the masks of
        # 4 devices and of 1 differ by design; parity is defined without them
        def build(self):
            net = super().build()
            for layer in net.layers:
                if isinstance(layer, nn.Dropout):
                    layer.rate = 0.0
            return net

    model = AlexNetNoDropout(recipe)
    B = recipe.batch_size
    rng = np.random.RandomState(seed)
    x = rng.randn(B, *recipe.input_shape).astype(np.float32)
    y = rng.randint(0, recipe.num_classes, size=B).astype(np.int32)

    def bsp_run(n):
        mesh = make_mesh(devs[:n])
        eng = BSPEngine(model, mesh, steps_per_epoch=steps)
        state = eng.init_state(jax.random.PRNGKey(seed))
        xg, yg = put_global_batch(mesh, x), put_global_batch(mesh, y)
        key, losses, first = jax.random.PRNGKey(seed + 1), [], None
        hlo = eng._steps[False].lower(state, xg, yg, key).compile().as_text()
        for _ in range(steps):
            key, sub = jax.random.split(key)
            state, m = eng.train_step(state, xg, yg, sub)
            losses.append(float(m["loss"]))
            if first is None:  # host copies: the next step donates these
                first = [np.asarray(a)
                         for a in jax.tree_util.tree_leaves(state.params)]
        devices = sorted({d.id for a in jax.tree_util.tree_leaves(state.params)
                          for d in a.devices()})
        return losses, first, eng.get_step(state), devices, hlo

    l4, p4, n4, devices4, hlo4 = bsp_run(4)
    l1, p1, n1, _, _ = bsp_run(1)
    # tests/test_bsp.py::test_bsp8_matches_single_device pins this parity
    # after ONE step: loss rtol 1e-4, params rtol 2e-3 / atol 2e-4 (bf16
    # compute rounding). Later losses are held to the params' rtol.
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    excess = max(float(np.max(np.abs(a - b) - 2e-3 * np.abs(b) - 2e-4))
                 for a, b in zip(p4, p1))
    checks = {
        "bsp_losses_finite": _finite(l4) and _finite(l1),
        "bsp_first_loss_parity": rel[0] <= 1e-4,
        "bsp_later_loss_parity": all(r <= 2e-3 for r in rel[1:]),
        "bsp_params_parity_after_first_step": excess <= 0.0,
        "bsp_device_steps_equal_steps": n4 == steps and n1 == steps,
        "bsp4_params_on_four_devices": len(devices4) == 4,
        "bsp4_step_has_all_reduce": "all-reduce" in hlo4,
    }
    facts = {
        "global_batch": B, "bsp4_losses": l4, "bsp1_losses": l1,
        "bsp_loss_rel_diff": rel, "bsp_params_excess_over_tolerance": excess,
        "bsp4_param_device_ids": devices4,
    }

    for rule, kw in (("easgd", {"avg_freq": 2}), ("gosgd", {"p_push": 0.5})):
        ck = os.path.join(args._out, f"ck-{rule}")
        summary = run_training(
            rule=rule, model_cls=AlexNet, devices=4,
            dataset="imagenet_synthetic",
            dataset_kwargs=dict(n_train=4 * worker_batch * 4,
                                n_val=4 * worker_batch),
            recipe_overrides=dict(overrides, batch_size=worker_batch),
            n_epochs=1, max_steps=4, seed=seed, print_freq=1,
            return_recorder=True,
            ckpt_dir=ck if rule == "gosgd" else None, async_checkpoint=False,
            **kw,
        )
        rec = summary["recorder"]
        losses = [r["loss"] for r in rec.history["train"]]
        facts[f"{rule}_losses"] = losses
        checks[f"{rule}_losses_finite"] = len(losses) == 4 and _finite(losses)
        checks[f"{rule}_device_steps_equal_steps"] = (
            summary["steps"] == 4 and summary["device_steps"] == 4)
        checks[f"{rule}_on_four_devices"] = summary["device"]["count"] == 4
        if rule == "easgd":
            # the driver brackets every elastic exchange as 'comm'
            facts["easgd_exchanges"] = len(rec.timings.get("comm", []))
            checks["easgd_exchanged"] = facts["easgd_exchanges"] >= 1
        else:
            # a delivered push moves share weight between workers: the
            # checkpointed shares still sum to 1 but are no longer uniform
            with np.load(os.path.join(ck, "ckpt_4.npz")) as z:
                alpha = z[".alpha"].astype(float)
            facts["gosgd_shares"] = alpha.tolist()
            checks["gosgd_exchanged"] = (
                abs(alpha.sum() - 1.0) < 1e-5
                and float(np.max(np.abs(alpha - 0.25))) > 1e-3)
    facts.update(clock.report())
    say(json.dumps({"multichip": facts, "checks": checks,
                    "platform": devs[0].platform}))
    return 0


CHILDREN = {"probe": _child_probe, "shards": _child_shards,
            "multichip": _child_multichip}


if __name__ == "__main__":
    sys.exit(main())
