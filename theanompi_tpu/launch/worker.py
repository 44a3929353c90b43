"""The training driver: epoch loop, validation, checkpointing.

Rebuild of the reference's sync-rule worker processes (reference: BSP
``Worker.run`` epoch/iteration loop with data wait -> train_iter ->
exchange -> record, per-epoch validation, ``adjust_hyperp``, rank-0
checkpoint; SURVEY.md §3.2, §2.1 "Sync-rule drivers"). One driver covers
all rules — the rule picks which compiled step function it runs:

- ``bsp``:   BSP step over a ``('data',)`` mesh (parallel/bsp.py)
- ``easgd``: elastic-averaging step over a worker mesh (parallel/easgd.py)
- ``gosgd``: gossip step (parallel/gosgd.py)

There are no worker processes to manage: the mesh is the workers, and
the driver is plain single-controller Python around jitted SPMD steps
(multi-controller runs call this same function once per host).
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.data import get_dataset
from theanompi_tpu.data.loader import PrefetchLoader
from theanompi_tpu.models.contract import Model
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.mesh import host_local_batch_slice, put_global_batch
from theanompi_tpu.utils import (
    Recorder,
    checkpoint_step,
    latest_checkpoint,
    load_checkpoint,
    save_checkpoint,
)
from theanompi_tpu.utils.checkpoint import (
    AsyncCheckpointer,
    clear_resumable_marker,
    save_checkpoint_sharded,
    write_resumable_marker,
)
from theanompi_tpu.utils.faults import FaultInjector, Preempted
from theanompi_tpu.obs.numerics import NumericsAnomaly, RollbackRequested


def _layout_mismatch(a: dict, b: dict) -> bool:
    """One comparator for pipeline stack layout dicts, shared by the
    sidecar pre-flight check and the in-checkpoint embedded check so the
    two defenses can never silently diverge."""
    return (a.get("interleave", 1), a.get("n_stages")) != (
        b.get("interleave", 1), b.get("n_stages")
    )


def pipeline_layout_guard(
    ckpt_dir: str, pp: int, pp_interleave: int, resume: bool
) -> dict:
    """Interleaved pipeline stacking PERMUTES layers on the stacked axis
    (parallel/pipeline.py::stack_pipeline_params), and every layout
    produces identical leaf shapes — so a checkpoint written under one
    ``--pp/--pp-interleave`` would silently load layer-permuted under
    another. A ``pipeline_layout.json`` sidecar records the stacking
    layout; resume refuses a mismatch loudly. Plain GPipe stacking
    (interleave=1) is layout-invariant across ``--pp``, so only the
    interleaved case pins the stage count.

    The sidecar is the fast pre-flight check only — the layout is ALSO
    embedded in each checkpoint's metadata (``extra_meta``) and
    cross-checked at load, so checkpoints copied without the sidecar
    still refuse to resume layer-permuted. Returns the current layout
    dict for that embedding."""
    import json as _json
    import tempfile

    path = os.path.join(ckpt_dir, "pipeline_layout.json")
    current = {
        "interleave": int(pp_interleave),
        "n_stages": int(pp) if pp_interleave > 1 else None,
    }
    stored = {"interleave": 1, "n_stages": None}
    try:
        # open directly (no exists() pre-check): rank 0 may legitimately
        # remove a stale sidecar while another rank is here, and a
        # vanished file is the layout-invariant default, not corruption
        with open(path) as f:
            stored = _json.load(f)
    except FileNotFoundError:
        pass
    except (ValueError, OSError):
        # unreadable sidecar: only fatal if there are checkpoints it
        # was supposed to describe
        if latest_checkpoint(ckpt_dir) is not None:
            raise ValueError(
                f"{path!r} is unreadable but {ckpt_dir!r} holds "
                "checkpoints whose pipeline stack layout it should "
                "record — delete the checkpoints (or restore the "
                "sidecar) before reusing this dir"
            )
        stored = current  # nothing at stake; rewrite below
    mismatch = _layout_mismatch(stored, current)
    if resume and mismatch:
        raise ValueError(
            f"checkpoints in {ckpt_dir!r} use pipeline stack layout "
            f"{stored} but this run requests {current} — resuming "
            "would silently permute transformer layers; rerun with "
            "the matching --pp/--pp-interleave (or a fresh ckpt-dir)"
        )
    if not resume and mismatch and latest_checkpoint(ckpt_dir) is not None:
        # refusing here (not just overwriting the sidecar) is what
        # keeps a LATER --resume from pairing the rewritten sidecar
        # with the old differently-permuted checkpoints
        raise ValueError(
            f"{ckpt_dir!r} already holds checkpoints with pipeline "
            f"stack layout {stored}; this run requests {current} — "
            "use a fresh --ckpt-dir (or delete the old checkpoints)"
        )
    if jax.process_index() == 0:
        os.makedirs(ckpt_dir, exist_ok=True)
        if current["interleave"] > 1:
            fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                _json.dump(current, f)
            os.replace(tmp, path)  # atomic: no truncated sidecar
        elif os.path.exists(path):
            try:
                os.remove(path)  # back to the layout-invariant default
            except FileNotFoundError:
                pass  # another run cleaning the same dir got there first
    return current


def run_training(
    rule: str = "bsp",
    model_cls: type[Model] = None,
    devices=None,
    *,
    strategy: str = "psum",
    # compressed-collectives wire codec (parallel/codec.py):
    # none|bf16|int8, optional ':ef' suffix for error feedback — every
    # engine's exchange path consumes it (BSP psum/ring, ZeRO
    # scatter+gather, EASGD elastic psum, GoSGD gossip, ND grad psums)
    wire_codec: str = "none",
    # MFU-push knobs (ROADMAP item 2a/2b): fused_update swaps the
    # optimizer epilogue for the one-pass Pallas kernel
    # (ops/pallas_update.py) on EVERY engine; allreduce_buckets (MB,
    # 0 = off) chunks the BSP gradient allreduce into buckets whose
    # psums launch inside backward (parallel/strategies.py)
    fused_update: bool = False,
    allreduce_buckets: float = 0.0,
    n_slices: Optional[int] = None,
    steps_per_dispatch: int = 1,
    # async dispatch pipeline (utils/dispatch.py): keep up to this many
    # steps in flight before the host blocks on a metrics D2H. 2 (the
    # default): step N is queued before step N-1's metrics are drained,
    # so the device goes from one step to the next without the host;
    # 1 = the classic per-step sync (bit-identical recorder rows either
    # way; at 2 a row, and what reads it, lags its dispatch by one step)
    dispatch_depth: int = 2,
    accum_steps: int = 1,
    # N-D parallelism axes (BSP rule only; LM models — parallel/nd.py):
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    expert: int = 1,
    microbatches: Optional[int] = None,
    pp_interleave: int = 1,
    # ZeRO-1 optimizer-state sharding (BSP rule only; parallel/zero.py)
    zero: int = 0,
    n_epochs: Optional[int] = None,
    max_steps: Optional[int] = None,
    dataset: Optional[str] = None,
    dataset_kwargs: Optional[dict] = None,
    recipe_overrides: Optional[dict] = None,
    seed: int = 0,
    save_dir: Optional[str] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every_epochs: int = 1,
    async_checkpoint: bool = True,
    sharded_ckpt: bool = False,
    # background checkpoint scrubber (chaos PR,
    # utils/checkpoint.CheckpointScrubber): re-verify the keep-chain
    # every N seconds and quarantine corrupt members (at-rest bit-rot,
    # torn writes) into <ckpt_dir>/quarantine/ — kind=scrub records +
    # tmpi_scrub_* gauges ride the obs stream; 0 = off (the supervisor
    # still runs one synchronous pass before each retry's resume)
    scrub_interval: float = 0.0,
    resume: bool = False,
    print_freq: int = 40,
    run_name: Optional[str] = None,
    tensorboard: bool = False,
    prefetch_depth: int = 2,
    return_recorder: bool = False,
    profile_dir: Optional[str] = None,
    profile_steps: int = 4,
    # observability subsystem (obs/): metrics snapshots + span trace +
    # heartbeat under obs_dir; stall watchdog when stall_timeout > 0
    obs_dir: Optional[str] = None,
    stall_timeout: float = 0.0,
    metrics_snapshot_freq: int = 0,
    # fleet telemetry exporter (obs/exporter.py): chief-only HTTP
    # server on this port tailing obs_dir into the merged FleetView
    # (/metrics, /fleet.json, /healthz); 0 = off. Under the supervisor
    # the exporter is started ONCE outside the retry loop instead
    # (launch/supervisor.py), so it survives retries.
    fleet_exporter_port: int = 0,
    # numerics flight recorder (obs/numerics.py, obs/flight.py):
    # numerics_freq > 0 compiles the sentinel gauges into every Nth
    # step (grad/update/param norms, fused non-finite count, per-rule
    # divergence) — they drain through the dispatch pipeline, zero new
    # host syncs; anomalies (NaN/Inf, EWMA spikes) are detected at
    # drain time and handled per on_anomaly: 'record' (log + gauges),
    # 'dump' (also write the anomaly_rank{r}/ triage bundle), 'halt'
    # (dump, then stop training), 'rollback' (dump, then restore the
    # last verified checkpoint and keep training — see rollback_budget/
    # rollback_skip below). flight_window sizes the ring of drained
    # step records the bundle preserves.
    numerics_freq: int = 0,
    flight_window: int = 64,
    on_anomaly: str = "dump",
    # model-drift watchdog (obs/drift.py): EWMA band the tmpi_model_err_*
    # gauges may wander inside before a drift anomaly fires (and the
    # flight recorder writes its anomaly_rank{r}-drift/ bundle)
    drift_tolerance: float = 0.25,
    # anomaly rollback (--on-anomaly rollback): on a confirmed anomaly
    # restore the last VERIFIED checkpoint and keep training — at most
    # rollback_budget times per run; on replay, skip rollback_skip data
    # batches at the anomalous step (a persistent bad batch must not
    # re-poison every attempt)
    rollback_budget: int = 2,
    rollback_skip: int = 1,
    # elastic world size (elastic PR): resume may land on a DIFFERENT
    # mesh than the checkpoint was saved under — instead of dying on
    # the shape/sharding mismatch, reshard the state onto the current
    # mesh via the checkpoint's topology manifest
    # (utils/checkpoint.load_resharded). Per-replica batch rescales
    # implicitly (the BSP global batch is mesh-invariant);
    # elastic_lr_scale='linear' additionally scales the recipe's base
    # LR by n_new/n_saved (the per-worker-batch rules grow their
    # GLOBAL batch with the world, where linear scaling is the
    # standard correction; default 'none' leaves the schedule alone).
    elastic: bool = False,
    elastic_lr_scale: str = "none",
    # SIGTERM grace (preemption): > 0 installs a handler; the train
    # loop then checkpoints, marks the run resumable, and exits cleanly
    # (Preempted) instead of dying mid-step
    sigterm_grace: float = 0.0,
    # deterministic fault injection (utils/faults.py): KIND@STEP specs —
    # crash/sigterm/sigkill/ckpt_truncate/nan_batch/loader_stall — so
    # recovery paths are exercised by tests, not trusted on faith
    inject_faults: Optional[list] = None,
    # rule-specific kwargs (EASGD avg_freq etc.) forwarded to the rule's
    # step builder
    **rule_kwargs: Any,
) -> dict:
    """Train ``model_cls`` under a sync rule; returns a summary dict.

    The recipe is the model's own (reference: model-owned hyperparams,
    SURVEY.md §5.6); ``recipe_overrides`` is the session's override hook.

    ``async_checkpoint`` (default True) writes epoch checkpoints on a
    background thread overlapped with the next epoch's steps (reference
    parity is the synchronous rank-0 save; SURVEY.md §5.4) — ordering,
    durability-on-return, and the multi-host synchronous fallback are
    handled by :class:`~theanompi_tpu.utils.checkpoint.AsyncCheckpointer`.
    """
    if model_cls is None:
        raise ValueError("model_cls is required")

    recipe = model_cls.default_recipe()
    if recipe_overrides:
        recipe = recipe.replace(**recipe_overrides)
    if elastic_lr_scale not in ("none", "linear"):
        raise ValueError(
            f"elastic_lr_scale must be 'none' or 'linear', "
            f"got {elastic_lr_scale!r}"
        )
    # Elastic resume: peek the newest verified checkpoint's topology
    # manifest BEFORE the model/engine build — the saved world size
    # drives the LR-rescale hook (and nothing else; the reshard itself
    # happens against the live state template at resume time below).
    saved_world = None
    # The LR-rescale anchor: the world size the run's base LR was tuned
    # for. Forwarded through every manifest as elastic.base_world so the
    # scale stays n_target/base across ANY number of reshard/resume
    # cycles — anchoring to the resumed checkpoint's own world instead
    # would silently drop the scale after the first post-reshard save
    # (that checkpoint is stamped with the NEW world).
    base_world = None
    # Peek on EVERY resume (not just elastic ones): a plain --resume in
    # the middle of an elastic sequence must keep forwarding the
    # original anchor, or the next elastic resume rescales against the
    # wrong base.
    if resume and ckpt_dir:
        from theanompi_tpu.utils.checkpoint import read_topology_manifest

        _peek = latest_checkpoint(ckpt_dir, verify=True)
        _manifest = read_topology_manifest(_peek) if _peek else None
        if _manifest and _manifest.get("mesh"):
            saved_world = int(np.prod(_manifest["mesh"]["shape"]))
            base_world = int(
                (_manifest.get("elastic") or {}).get("base_world")
                or saved_world
            )
    if elastic and saved_world and elastic_lr_scale == "linear":
        # deterministic probe (sorted device enumeration) shared with
        # the supervisor — the scale must be rank-uniform
        from theanompi_tpu.launch.supervisor import _probe_world

        if isinstance(devices, int) and devices:
            _n_target = devices
        elif devices is not None:
            # explicit device list: the mesh below is built over exactly
            # these (make_mesh supports lists) — probing ALL live
            # devices here would scale the LR by the wrong ratio
            _n_target = len(devices)
        else:
            _n_target = _probe_world(None, None)
        if _n_target != base_world and "lr" in (recipe.sched_kwargs or {}):
            _sk = dict(recipe.sched_kwargs)
            _sk["lr"] = float(_sk["lr"]) * _n_target / base_world
            recipe = recipe.replace(sched_kwargs=_sk)
            print(
                f"[elastic] linear LR rescale: world {base_world} -> "
                f"{_n_target}, base lr now {_sk['lr']:g}", flush=True,
            )
    if (
        rule.lower() in ("easgd", "gosgd")
        and int(rule_kwargs.get("group_size", 1)) > 1
        and recipe.bn_axis_name is None
        and "bn_axis_name" not in (recipe_overrides or {})
    ):
        # a worker GROUP must be statistically one worker: sync BN batch
        # stats across the group's data axis (override explicitly via
        # recipe_overrides={'bn_axis_name': None} for per-chip BN)
        from theanompi_tpu.parallel.mesh import DATA_AXIS

        recipe = recipe.replace(bn_axis_name=DATA_AXIS)
    model = model_cls(recipe)

    dataset = dataset or recipe.dataset
    if dataset == "synthetic" and getattr(model, "is_lm", False):
        # `tmpi ... --synthetic` on an LM means "synthetic tokens", not
        # float image batches (which would crash tracing the embedding
        # lookup with a float indexer)
        dataset = "lm_synthetic"
    dataset_kwargs = dict(dataset_kwargs or {})
    if dataset in ("synthetic", "imagenet_synthetic"):
        # Synthetic stand-ins default to the MODEL's shapes, so
        # `tmpi ... --synthetic` works for ImageNet-shaped models instead
        # of failing deep in a matmul on 32x32 defaults.
        if dataset == "synthetic":
            dataset_kwargs.setdefault("image_shape", tuple(recipe.input_shape))
        else:
            dataset_kwargs.setdefault("crop", recipe.input_shape[0])
        dataset_kwargs.setdefault("n_classes", recipe.num_classes)
    elif dataset in ("lm_synthetic", "lm_text"):
        # token datasets default to the MODEL's sequence length / vocab
        dataset_kwargs.setdefault("seq_len", recipe.input_shape[0])
        if dataset == "lm_synthetic":
            dataset_kwargs.setdefault("vocab", recipe.num_classes)
    rule = rule.lower()
    from theanompi_tpu.parallel.codec import get_codec

    codec = get_codec(wire_codec)  # validate the spec before any build
    fuse = max(1, int(steps_per_dispatch))
    tp, sp, pp, expert = int(tp), int(sp), int(pp), int(expert)
    zero = int(zero or 0)
    nd_active = max(tp, sp, pp, expert) > 1
    if nd_active or zero:
        what = "--tp/--sp/--pp/--expert" if nd_active else "--zero"
        if rule != "bsp":
            raise ValueError(f"{what} compose with the BSP rule only")
        if strategy != "psum":
            raise ValueError(f"{what} use the in-step psum sync (strategy 'psum')")
        if n_slices and n_slices > 1:
            raise ValueError(f"{what} do not compose with --slices yet")
        if accum_steps != 1:
            raise ValueError(f"{what} do not compose with --accum-steps yet")
        if rule_kwargs:
            raise ValueError(f"{what} got unexpected options {sorted(rule_kwargs)}")
    if nd_active and zero:
        raise ValueError("--zero composes with plain BSP only (ND shards "
                         "optimizer state per its own param specs already)")
    allreduce_buckets = float(allreduce_buckets or 0.0)
    if allreduce_buckets and (rule != "bsp" or zero or nd_active):
        raise ValueError(
            "--allreduce-buckets buckets the BSP in-step gradient "
            "allreduce only (ZeRO's scatter/gather and the ND sharded-"
            "axis psums own their own schedules; EASGD/GoSGD exchange "
            "periodically — there is no every-step allreduce to bucket)"
        )
    if microbatches is not None and pp <= 1:
        raise ValueError("--microbatches requires --pp (GPipe microbatching)")
    if pp_interleave > 1 and pp <= 1:
        raise ValueError("--pp-interleave requires --pp (virtual stages)")
    if nd_active:
        if not getattr(model, "is_lm", False):
            raise ValueError(
                "--tp/--sp/--pp/--expert need an LM model "
                "(theanompi_tpu.models.lm TransformerLMModel / MoELMModel); "
                f"{model_cls.__name__} is classifier-shaped"
            )
        if (expert > 1) != bool(getattr(model, "is_moe", False)):
            raise ValueError(
                "--expert N trains MoELMModel (Switch-MoE); dense "
                "TransformerLMModel uses --tp/--sp/--pp"
                if expert > 1
                else "MoELMModel trains via --expert N"
            )
    if n_slices and n_slices > 1:
        if rule == "bsp":
            from theanompi_tpu.parallel.mesh import make_multislice_mesh

            mesh = make_multislice_mesh(devices, n_slices=n_slices)
        else:
            # EASGD/GoSGD across slices (BASELINE config #4's pod shape:
            # worker groups inside a slice, async exchange over DCN):
            # the engine builds the (worker, data) mesh itself — hand it
            # the flat slice-major device list + the slice count to
            # validate group/slice alignment (make_worker_group_mesh)
            mesh = make_mesh(devices)
            rule_kwargs["n_slices"] = n_slices
    elif nd_active:
        # ND mesh: exactly the active axes, data-major (slice-major
        # device order comes from make_mesh; collectives over the
        # trailing axes stay densest on ICI)
        base = make_mesh(devices)
        devs = np.asarray(base.devices).reshape(-1)
        from jax.sharding import Mesh as _Mesh

        from theanompi_tpu.parallel.nd import DP_AXIS, SP_AXIS, TP_AXIS

        if expert > 1:
            from theanompi_tpu.models.moe import EXPERT_AXIS

            if pp > 1:
                raise ValueError(
                    "--expert composes with data parallelism, --tp and "
                    "--sp (expert x pp is not implemented)"
                )
            if len(devs) % (expert * sp * tp):
                raise ValueError(
                    f"{len(devs)} devices do not divide "
                    f"--expert {expert} x --sp {sp} x --tp {tp}"
                )
            dp = len(devs) // (expert * sp * tp)
            # dp major: the (dp, expert) joint batch sharding keeps each
            # controller's host rows contiguous (NDEngine.host_batch_part);
            # tp innermost: its per-block psum pairs ride adjacent chips
            names = ((DP_AXIS,) if dp > 1 else ()) + (EXPERT_AXIS,) + (
                (SP_AXIS,) if sp > 1 else ()
            ) + ((TP_AXIS,) if tp > 1 else ())
            shape = ((dp,) if dp > 1 else ()) + (expert,) + (
                (sp,) if sp > 1 else ()
            ) + ((tp,) if tp > 1 else ())
            nd_axes = dict(ep_axis=EXPERT_AXIS,
                           dp_axis=DP_AXIS if dp > 1 else None,
                           sp_axis=SP_AXIS if sp > 1 else None,
                           tp_axis=TP_AXIS if tp > 1 else None)
        elif pp > 1:
            if len(devs) % (pp * tp * sp):
                raise ValueError(
                    f"{len(devs)} devices do not divide "
                    f"--pp {pp} x --tp {tp} x --sp {sp}"
                )
            dp = len(devs) // (pp * tp * sp)
            # tp innermost: the per-layer psum pairs ride adjacent
            # devices (densest ICI); pipe outermost — its ppermute runs
            # once per schedule tick, not twice per layer
            names = ("pipe",) + ((DP_AXIS,) if dp > 1 else ()) + (
                (SP_AXIS,) if sp > 1 else ()
            ) + ((TP_AXIS,) if tp > 1 else ())
            shape = (pp,) + ((dp,) if dp > 1 else ()) + (
                (sp,) if sp > 1 else ()
            ) + ((tp,) if tp > 1 else ())
            nd_axes = dict(pipe_axis="pipe",
                           dp_axis=DP_AXIS if dp > 1 else None,
                           sp_axis=SP_AXIS if sp > 1 else None,
                           tp_axis=TP_AXIS if tp > 1 else None,
                           microbatches=microbatches,
                           pp_interleave=pp_interleave)
        else:
            if len(devs) % (tp * sp):
                raise ValueError(
                    f"{len(devs)} devices do not divide --tp {tp} x --sp {sp}"
                )
            dp = len(devs) // (tp * sp)
            names = (DP_AXIS,) + ((TP_AXIS,) if tp > 1 else ()) + (
                (SP_AXIS,) if sp > 1 else ()
            )
            shape = (dp,) + ((tp,) if tp > 1 else ()) + ((sp,) if sp > 1 else ())
            nd_axes = dict(dp_axis=DP_AXIS,
                           tp_axis=TP_AXIS if tp > 1 else None,
                           sp_axis=SP_AXIS if sp > 1 else None)
        mesh = _Mesh(devs.reshape(shape), names)
    else:
        mesh = make_mesh(devices)
    n_dev = mesh.devices.size
    # Batch semantics per rule (reference meaning, SURVEY.md §3.3/§3.5):
    # - bsp:  recipe.batch_size is the GLOBAL batch, sharded across the
    #         mesh (lockstep SGD is defined by its global batch).
    # - easgd/gosgd: recipe.batch_size is the PER-WORKER batch — every
    #         worker (device) trains on its own full batch each local
    #         step, exactly like the reference's per-rank streams; the
    #         global images/step is n_workers x batch_size.
    per_worker_rules = ("easgd", "gosgd")
    if rule not in ("bsp", *per_worker_rules):
        raise ValueError(f"unknown rule {rule!r}; available: bsp, easgd, gosgd")
    if rule == "bsp" and rule_kwargs:
        raise ValueError(
            f"rule 'bsp' got unexpected options {sorted(rule_kwargs)} "
            "(avg_freq/alpha/p_push/group_size apply to EASGD/GoSGD only)"
        )
    if rule in per_worker_rules and strategy != "psum":
        raise ValueError("strategy applies to the BSP rule only")
    if strategy == "hier" and not (n_slices and n_slices > 1):
        raise ValueError(
            "strategy 'hier' is the cross-slice hierarchical exchange — "
            "it needs a multislice mesh (--slices N with N > 1); on a "
            "single slice the flat 'psum' is already optimal"
        )
    # fuse>1 works for every rule: BSP scans allreduce-inside steps;
    # EASGD embeds its elastic exchange at the avg_freq boundaries
    # inside the scan; GoSGD ships per-substep gossip-cadence flags
    # Async-rule worker groups: each worker = group_size chips, so the
    # worker count (and the global batch multiplier) is n_dev / group_size
    # (bsp with group_size already raised above)
    group_size = (
        int(rule_kwargs.get("group_size", 1)) if rule in per_worker_rules else 1
    )
    if group_size > 1 and n_dev % group_size:
        raise ValueError(
            f"{n_dev} devices do not divide into groups of {group_size}"
        )
    n_workers = n_dev // max(1, group_size)
    batch = recipe.batch_size * (n_workers if rule in per_worker_rules else 1)

    data = get_dataset(dataset, **dataset_kwargs)
    if tuple(data.image_shape) != tuple(recipe.input_shape):
        raise ValueError(
            f"dataset {dataset!r} yields images {tuple(data.image_shape)} but "
            f"model {model_cls.__name__} expects {tuple(recipe.input_shape)}; "
            "pass dataset_kwargs/--dataset matching the recipe (or override "
            "recipe.input_shape)"
        )
    if data.n_classes != recipe.num_classes:
        raise ValueError(
            f"dataset {dataset!r} has {data.n_classes} classes but model head "
            f"expects {recipe.num_classes} (override recipe.num_classes or the "
            "dataset's n_classes)"
        )
    steps_per_epoch = data.n_train_batches(batch)
    if steps_per_epoch == 0:
        raise ValueError(
            f"dataset has {data.n_train} train examples < the global batch "
            f"{batch} ({'= n_workers x recipe.batch_size' if rule in per_worker_rules else '= recipe.batch_size'})"
        )
    n_epochs = n_epochs if n_epochs is not None else recipe.n_epochs

    vbatch = recipe.val_batch_size or batch
    if nd_active:
        # tokens shard P(batch_axis, seq_axis); seq divides sp, batch
        # divides the batch axis x (for pipelines) the microbatch count
        T = recipe.input_shape[0]
        if sp > 1 and T % sp:
            raise ValueError(f"sequence length {T} not divisible by --sp {sp}")
        batch_div = expert * max(1, n_dev // (expert * sp * tp)) if expert > 1 else (
            (microbatches or pp) * max(1, n_dev // (pp * tp * sp)) if pp > 1
            else n_dev // (tp * sp)
        )
        for name, b in (("batch", batch), ("val batch", vbatch)):
            if batch_div and b % batch_div:
                raise ValueError(
                    f"global {name} {b} not divisible by {batch_div} "
                    "(batch-axis devices x microbatches)"
                )
    else:
        if batch % n_dev:
            raise ValueError(f"global batch {batch} not divisible by {n_dev} devices")
        if vbatch % n_dev:
            raise ValueError(f"val batch {vbatch} not divisible by {n_dev} devices")
    if data.n_val and vbatch > data.n_val:
        # n_val_batches() would be 0: the val loop would yield NOTHING
        # and summary['val'] silently never set (this exact failure
        # shipped in an early n=64 experiment run)
        raise ValueError(
            f"val batch {vbatch} exceeds the dataset's {data.n_val} val "
            "examples — validation would silently run zero batches "
            "(set recipe val_batch_size or enlarge the val split)"
        )

    # Device-side normalization (dataset opt-in): the loader ships
    # compact uint8 batches and (x - mean) * scale fuses into the
    # compiled step — 4x less H2D than float32 (the reference normalized
    # in the host loader; on TPU the wire is the scarcer resource).
    eval_views = int(getattr(data, "val_views", 1))
    input_transform = None
    dtf = getattr(data, "device_transform", None)
    if dtf is not None:
        mean_c = jnp.asarray(dtf["mean"], jnp.float32)
        scale_c = jnp.float32(dtf["scale"])

        def input_transform(x):
            return (x.astype(jnp.float32) - mean_c) * scale_c

    if nd_active:
        from theanompi_tpu.parallel.nd import NDEngine

        engine = NDEngine(
            model, mesh, steps_per_epoch=steps_per_epoch,
            wire_codec=codec, fused_update=fused_update, **nd_axes,
        )
    elif zero:
        from theanompi_tpu.parallel.zero import ZeroEngine

        engine = ZeroEngine(
            model, mesh, steps_per_epoch=steps_per_epoch,
            input_transform=input_transform, eval_views=eval_views,
            wire_codec=codec, fused_update=fused_update,
        )
    elif rule == "bsp":
        from theanompi_tpu.parallel.bsp import BSPEngine

        engine = BSPEngine(
            model, mesh, steps_per_epoch=steps_per_epoch, strategy=strategy,
            input_transform=input_transform, eval_views=eval_views,
            accum_steps=accum_steps, wire_codec=codec,
            fused_update=fused_update, allreduce_buckets=allreduce_buckets,
        )
    elif rule == "easgd":
        from theanompi_tpu.parallel.easgd import EASGDEngine

        engine = EASGDEngine(
            model, mesh, steps_per_epoch=steps_per_epoch,
            input_transform=input_transform, eval_views=eval_views,
            accum_steps=accum_steps, wire_codec=codec,
            fused_update=fused_update, **rule_kwargs,
        )
    else:
        from theanompi_tpu.parallel.gosgd import GOSGDEngine

        engine = GOSGDEngine(
            model, mesh, steps_per_epoch=steps_per_epoch,
            input_transform=input_transform, eval_views=eval_views,
            accum_steps=accum_steps, wire_codec=codec,
            fused_update=fused_update, **rule_kwargs,
        )

    # Topology stamp for every checkpoint this run writes (elastic PR):
    # the ENGINE's mesh identity (EASGD/GoSGD group mode reshapes the
    # driver mesh internally) + the engine's per-leaf elastic reshard
    # policies — what load_resharded needs to move the checkpoint onto
    # a different world later. Stamping is unconditional and cheap (a
    # small JSON entry per save); elasticity is an attribute of the
    # RESUME, not the save.
    from theanompi_tpu.parallel.mesh import mesh_topology

    topo_meta = {"mesh": mesh_topology(getattr(engine, "mesh", mesh))}
    _espec = getattr(engine, "elastic_spec", None)
    if _espec is not None:
        topo_meta["elastic"] = _espec()
    # the engine's ShardingRecipe identity (parallel/recipe.py) rides
    # the manifest too: the stamp then records both the DECLARED spec
    # source and the live-array specs it placed, so the sharding
    # analyzer's train->serve handoff check reads one artifact
    _srecipe = getattr(engine, "sharding_recipe", None)
    if _srecipe is not None:
        topo_meta["recipe"] = _srecipe().as_json()
    # Forward the run's LR-scale anchor (see base_world above): resumed
    # runs keep the ORIGINAL world; fresh runs anchor to the world they
    # launch on.
    topo_meta.setdefault("elastic", {})["base_world"] = int(
        base_world or getattr(engine, "mesh", mesh).devices.size
    )

    # Multi-controller: this host produces only its slice of every
    # global batch (reference: per-rank loader feed, lib/proc_load_mpi.py)
    n_proc = jax.process_count()
    if n_proc > 1 and nd_active:
        # ND token layouts own their host slice: contiguous dp/expert
        # row ranges where the sharding permits, full-batch feed where
        # tokens are replicated across hosts (pure tp/sp) or microbatch-
        # major interleaving makes slices non-contiguous (pipelines) —
        # see NDEngine.host_batch_part
        part = engine.host_batch_part(batch)
        vpart = engine.host_batch_part(vbatch)
    else:
        part = host_local_batch_slice(mesh, batch) if n_proc > 1 else None
        vpart = host_local_batch_slice(mesh, vbatch) if n_proc > 1 else None
        if n_proc > 1 and (batch % n_proc or vbatch % n_proc):
            raise ValueError(
                f"global batch {batch} / val batch {vbatch} must divide the "
                f"{n_proc} controller processes"
            )

    rec = Recorder(
        rank=jax.process_index(), print_freq=print_freq,
        # files are written by the rank-0 controller only (reference:
        # rank-0 recorder save); console prints keep their rank prefix
        save_dir=save_dir if jax.process_index() == 0 else None,
        # run_name override: committed experiments name artifacts after
        # the EXPERIMENT, not the model class (round-3 weak item 6:
        # results/digits_bsp/ held files named cifar10_bsp.jsonl)
        run_name=run_name or f"{model.name}_{rule}",
        tensorboard=tensorboard,
    )
    if profile_dir and jax.process_index() == 0:
        # reference: the recorder WAS the profiler (host brackets); the
        # XLA in-step comm/compute split needs a device trace (§5.1).
        # Offset is relative to the first tick, so resume is handled.
        rec.enable_profile(profile_dir, start_offset=2, n_steps=profile_steps)
    def _commit(st):
        # committed to the engine's declared shardings, like every state
        # the step itself returns: one compile of the step, not two
        if n_proc == 1 and _srecipe is not None:
            return _srecipe().place_state(st)
        return st

    from theanompi_tpu.utils.dispatch import KeyStream, MetricsDispatcher

    # every step's random key, split off the carry one dispatch unit
    # ahead (utils/dispatch.py); keys.carry is what a checkpoint saves
    keys = KeyStream(jax.random.PRNGKey(seed), ahead=fuse)
    state = _commit(engine.init_state(keys.carry))
    start_epoch = 0
    summary_resumed_from = None
    # set when an elastic resume actually resharded: the obs facade is
    # built later, so the reshard record/metrics are emitted then
    pending_reshard = None
    # data batches skipped by anomaly rollbacks in this training
    # timeline (restored from checkpoint meta on resume): every replay
    # position below must count BATCHES CONSUMED = step + skipped, or a
    # later resume would re-feed one already-trained batch per skip and
    # shift every subsequent step's data
    skipped_prior = 0
    layout_meta = None
    if ckpt_dir:
        # validates for EVERY rule (a fresh non-pipeline run must not
        # clobber an interleaved dir either); writes/clears the sidecar
        layout = pipeline_layout_guard(ckpt_dir, pp, pp_interleave, resume)
        layout_meta = {"pipeline_layout": layout}

    def _place_restored(restored):
        # restored leaves are full host arrays; under multi-controller
        # the SPMD step needs global sharded jax Arrays — each process
        # commits only its addressable shards (jnp.asarray would make
        # process-local arrays). Shared by resume and anomaly rollback.
        shardings = getattr(engine, "state_shardings", None)
        if n_proc > 1 and shardings is not None:
            return jax.tree_util.tree_map(
                lambda a, s: jax.make_array_from_callback(
                    np.shape(a), s, lambda idx, a=a: np.asarray(a)[idx]
                ),
                restored, shardings,
            )
        return _commit(jax.tree_util.tree_map(jnp.asarray, restored))

    if resume and ckpt_dir:
        # verify=True: the integrity chain (per-array CRC manifests)
        # walks back past a corrupt/truncated newest checkpoint instead
        # of resuming into a load-time explosion
        path = latest_checkpoint(ckpt_dir, verify=True)
        if n_proc > 1:
            # Every controller must resume from the SAME step or the
            # lockstep SPMD program diverges/deadlocks. ckpt_dir must be
            # shared storage (same contract as the reference's NFS-visible
            # rank-0 save). Allgather every rank's resolved step and have
            # EVERY rank (including 0) compare the full vector, so all
            # processes fail together instead of rank 0 sailing into a
            # collective that will never complete.
            from jax.experimental import multihost_utils

            steps_seen = np.asarray(
                multihost_utils.process_allgather(
                    np.int64(checkpoint_step(path))
                )
            ).reshape(-1)
            if not np.all(steps_seen == steps_seen[0]):
                raise RuntimeError(
                    f"controller processes resolved different checkpoint "
                    f"steps {steps_seen.tolist()} (this is process "
                    f"{jax.process_index()}): ckpt_dir={ckpt_dir!r} is not "
                    "shared storage visible to all controllers (required "
                    "for --resume)"
                )
        if path:
            from theanompi_tpu.utils.checkpoint import read_checkpoint_meta

            ckpt_meta = read_checkpoint_meta(path)
            saved_layout = ckpt_meta.get("pipeline_layout")
            if saved_layout is not None and layout_meta is not None and (
                _layout_mismatch(saved_layout, layout_meta["pipeline_layout"])
            ):
                # defense in depth vs a deleted/absent sidecar: the
                # checkpoint itself knows the stack layout it was saved
                # under (every layout has identical leaf shapes, so a
                # mismatch would otherwise load silently layer-permuted)
                raise ValueError(
                    f"checkpoint {path!r} embeds pipeline stack layout "
                    f"{saved_layout} but this run requests "
                    f"{layout_meta['pipeline_layout']} — rerun with the "
                    "matching --pp/--pp-interleave"
                )
            if elastic:
                # mesh-portable restore: same saved/live topology loads
                # exactly like the plain path (bit-identical resume); a
                # topology mismatch reshards each leaf onto the live
                # mesh under the manifest's elastic policies —
                # returning device-placed global arrays directly (the
                # sharded-set path never assembles a full array here)
                from theanompi_tpu.utils.checkpoint import load_resharded

                _t0 = time.perf_counter()
                restored, saved_rng, rs_info = load_resharded(
                    path, state, getattr(engine, "mesh", mesh)
                )
                if rs_info["resharded"]:
                    state = restored
                    pending_reshard = {
                        "step": engine.get_step(state),
                        "from_world": rs_info["from_world"],
                        "to_world": rs_info["to_world"],
                        "seconds": time.perf_counter() - _t0,
                        "leaves": rs_info["leaves"],
                        "per_replica_batch": batch // n_dev,
                    }
                    print(
                        f"[elastic] resharded {path} onto the live mesh: "
                        f"world {rs_info['from_world']} -> "
                        f"{rs_info['to_world']}, {rs_info['leaves']} "
                        f"leaves, per-replica batch {batch // n_dev}",
                        flush=True,
                    )
                else:
                    state = _place_restored(restored)
            else:
                restored, saved_rng = load_checkpoint(path, state)
                state = _place_restored(restored)
            if saved_rng is not None:
                # already wrapped with the impl that wrote it — a
                # pre-rbg-default threefry checkpoint keeps resuming
                keys.reset(saved_rng)
            # positioning counts BATCHES CONSUMED, not steps: rollback
            # skips consumed batches without training steps, and the
            # checkpoint records how many (see skipped_prior above)
            skipped_prior = int(ckpt_meta.get("skipped_batches", 0))
            start_epoch = (engine.get_step(state) + skipped_prior) // steps_per_epoch
            summary_resumed_from = engine.get_step(state)
            print(f"resumed from {path} at step {engine.get_step(state)}", flush=True)

    if hasattr(engine, "place_batch"):
        # engine-owned placement (ND engines: tokens shard over
        # (batch, seq) axes / microbatch-major — not the leading-dim-
        # only layout put_global_batch assumes)
        def place(b):
            return engine.place_batch(*b)
    else:
        def place(b):
            # global rows inferred per array (local rows x process_count):
            # x and y may carry different row counts (10-crop val ships
            # views x batch image rows against batch label rows)
            x, y = b
            return (put_global_batch(mesh, x), put_global_batch(mesh, y))

    def place_group(group):
        # fused dispatch: stack g host batches -> ONE [g, batch, ...]
        # transfer (dim 0 replicated, dim 1 sharded); ND engines own the
        # stacked layout (token specs / microbatch-major)
        if hasattr(engine, "place_group"):
            return engine.place_group(group)
        from theanompi_tpu.parallel.mesh import put_stacked_batches

        xs = np.stack([b[0] for b in group])
        ys = np.stack([b[1] for b in group])
        return put_stacked_batches(mesh, xs), put_stacked_batches(mesh, ys)

    def grouper(it, k):
        buf = []
        for b in it:
            buf.append(b)
            if len(buf) == k:
                yield buf
                buf = []
        if buf:  # epoch remainder: a smaller fused program (cached)
            yield buf

    summary: dict = {"epochs": [], "rule": rule, "model": model.name,
                     "resumed_from_step": summary_resumed_from}
    # images shipped per dispatch ('step' timing bracket) — fused
    # dispatches carry g x batch, so throughput must be computed from
    # this ledger, not batch / mean_time (which undercounts g-fold)
    dispatch_images: list[int] = []
    # sharded_ckpt: per-host shard files, no cross-host gather / rank-0
    # memory spike; restorable under any process count (SURVEY.md §5.4)
    ckpt_writer = (
        AsyncCheckpointer(sharded=sharded_ckpt)
        if (ckpt_dir and async_checkpoint) else None
    )
    sync_save = save_checkpoint_sharded if sharded_ckpt else save_checkpoint
    step_count = engine.get_step(state)
    # Mid-epoch resume (checkpoint written after a max_steps truncation):
    # fast-forward past the batches the restored timeline already
    # consumed — trained steps PLUS rollback-skipped batches — so data
    # order and epoch accounting stay exact.
    skip_batches = (step_count + skipped_prior) % steps_per_epoch
    if skip_batches and os.environ.get("TMPI_CHAOS_MUTATE") == "refeed":
        # chaos oracle self-test mutation (tools/chaos.py --mutate
        # refeed): deliberately re-feed the last already-consumed batch
        # on resume — a seeded recovery-accounting bug the campaign's
        # invariant oracle MUST catch (and shrink); never set outside
        # the chaos runner's mutation mode
        skip_batches -= 1
    from theanompi_tpu.obs import Observability

    # obs facade: span log + heartbeat per rank, metrics snapshots on
    # rank 0, stall watchdog when requested; inert when obs_dir is None.
    # Created HERE, immediately before the try whose finally closes it:
    # any earlier raise (resume mismatch, layout guard, init OOM) must
    # not leak its threads / open files / the process-global span hook.
    nfreq = max(0, int(numerics_freq))
    if nfreq and obs_dir is None:
        print(
            f"[rank {jax.process_index()}] WARNING: --numerics-freq "
            f"without --obs-dir: sentinels and anomaly detection run "
            f"(on_anomaly={on_anomaly!r} is honored) but no numerics "
            "telemetry or flight dump can be written",
            flush=True,
        )
    obs = Observability(
        obs_dir,
        rank=jax.process_index(),
        stall_timeout=stall_timeout,
        snapshot_freq=metrics_snapshot_freq,
        numerics_freq=nfreq,
        flight_window=flight_window,
        on_anomaly=on_anomaly,
        drift_tolerance=drift_tolerance,
    )
    fleet_exporter = None
    if fleet_exporter_port and obs.enabled and jax.process_index() == 0:
        # chief-only fleet telemetry plane (obs/exporter.py): tail the
        # obs dir every rank writes into, serve the merged FleetView
        # over HTTP. Best-effort — a taken port degrades to
        # no-exporter, never to a failed run. (Supervised runs start
        # the exporter in launch/supervisor.py instead, outside the
        # retry loop, and do not forward the port here.)
        try:
            from theanompi_tpu.obs.exporter import FleetExporter

            fleet_exporter = FleetExporter(
                obs_dir, fleet_exporter_port, topology=topo_meta
            ).start()
            print(f"[rank 0] fleet exporter on {fleet_exporter.url} "
                  "(/metrics /fleet.json /healthz)", flush=True)
        except OSError as e:
            fleet_exporter = None
            print(f"[rank 0] WARNING: fleet exporter failed to bind "
                  f"port {fleet_exporter_port}: {e!r}; continuing "
                  "without it", flush=True)
    if pending_reshard is not None:
        # the reshard ran before the obs facade existed; emit its
        # kind=reshard record + tmpi_reshard_* metrics now
        obs.note_reshard(**pending_reshard)
        summary["resharded_from_world"] = pending_reshard["from_world"]
        summary["resharded_to_world"] = pending_reshard["to_world"]
    if obs.enabled:
        # bracket delegation: timing histograms into the obs registry,
        # wait/step/comm brackets doubling as trace spans
        rec.registry = obs.registry
        rec.spans = obs.spans
        if hasattr(engine, "traffic_model"):
            # each sync rule declares its analytic wire model
            # (obs/comm.py); never let an accounting bug take down
            # training
            try:
                obs.set_traffic_model(engine.traffic_model(state))
            except Exception as e:  # noqa: BLE001
                print(f"[obs] traffic model unavailable for {rule!r}: "
                      f"{e!r}", flush=True)
        if nfreq and hasattr(engine, "numerics_model"):
            # ... and its numerics declaration (obs/numerics.py):
            # which sentinels ride the step, which divergence gauge
            # the rule supports, what extra wire the gauge costs
            try:
                obs.set_numerics_model(engine.numerics_model(state))
            except Exception as e:  # noqa: BLE001
                print(f"[obs] numerics model unavailable for {rule!r}: "
                      f"{e!r}", flush=True)
        if hasattr(engine, "cost_model") and n_proc == 1:
            # ... and the compiled-step cost model (utils/flops.py):
            # FLOPs + HBM bytes of the step executable, feeding the
            # live tmpi_mfu / tmpi_hbm_gbps / tmpi_step_*_frac gauges
            # and the per-snapshot kind=profile attribution record
            # (obs/attribution.py). The lowering compiles (persistent-
            # cache-friendly) but never executes; single-controller
            # only — abstract lowering has no multihost story yet.
            try:
                obs.set_cost_model(engine.cost_model(state, batch))
            except Exception as e:  # noqa: BLE001
                print(f"[obs] cost model unavailable for {rule!r}: "
                      f"{e!r}", flush=True)
        if hasattr(engine, "memory_model"):
            # ... and the declared state residency (utils/flops.py
            # MemoryModel): the predicted per-device HBM high-water the
            # drift watchdog diffs against device.memory_stats()
            try:
                obs.set_memory_model(engine.memory_model(state))
            except Exception as e:  # noqa: BLE001
                print(f"[obs] memory model unavailable for {rule!r}: "
                      f"{e!r}", flush=True)

    def _flight_state_saver(dump_dir):
        # best-effort param-state capture into the triage bundle (the
        # anomalous step's params/opt state, NaNs and all); closure
        # reads the CURRENT state/step — the dump happens at drain
        # time, on the driver thread
        sync_save(dump_dir, state, step_count, rng=keys.carry, keep=1,
                  topology=topo_meta)

    obs.set_flight_state_saver(_flight_state_saver)
    # Async dispatch pipeline (utils/dispatch.py): the ONLY
    # host<->device sync in the train loops below lives in the
    # dispatcher's drain (lint: tools/check_hot_loop.py). depth=2, the
    # default, drains step N-1 with step N already queued; depth=1
    # reproduces the classic per-step sync exactly. on_row feeds each
    # drained row (already host-side) to the flight ring + anomaly
    # detection — numerics telemetry adds no sync of its own. Wired
    # only when something can consume it: sentinels requested, or a
    # stall watchdog whose dump would preserve the ring (plain obs runs
    # keep their drain path lean).
    disp = MetricsDispatcher(
        rec, depth=dispatch_depth, on_step_seconds=obs.note_step_seconds,
        on_row=obs.on_row
        if (nfreq or (obs.enabled and stall_timeout > 0)) else None,
    )
    obs.attach_dispatcher(disp)
    if disp.depth > 1 and not getattr(engine, "donates_state", False):
        print(
            f"[rank {jax.process_index()}] WARNING: engine {rule!r} does "
            f"not donate its state buffers on this mesh; dispatch_depth="
            f"{disp.depth} keeps extra params+opt copies live in HBM",
            flush=True,
        )

    def _dispatch_and_split(step_fn, state, xs, ys, n, stacked, numerics):
        """The ``dispatch`` and ``key_split`` spans of one dispatch, for
        both train loops: the call of the step program on the next ``n``
        keys of the stream (stacked for a fused group), then the splits
        that make the NEXT unit's keys, queued behind the program just
        dispatched; each a recorder bracket under the dispatched group's
        last step number. -> (state, metrics)."""
        disp.note_dispatch()  # before the call: is the device still busy?
        last = step_count + n
        carry = keys.carry
        subs = keys.take(n, stacked)
        rec.start("dispatch")
        try:
            state, metrics = step_fn(state, xs, ys, subs, numerics=numerics)
        except Exception:
            # no step happened: the crash save pairs this state with the
            # carry BEFORE the step's keys, which is where a resume starts
            keys.reset(carry)
            raise
        rec.end("dispatch", step=last)
        rec.start("key_split")
        keys.refill()
        rec.end("key_split", step=last)
        return state, metrics

    train_loop_s = 0.0  # wall time inside the train loops (the
    # denominator of summary['host_blocked_frac'])
    # -- fault-tolerance state (fault-tolerant run supervisor PR) -------
    # injected faults fire at deterministic steps (utils/faults.py);
    # SIGTERM flips a flag the train loops poll, so preemption
    # checkpoints and exits cleanly inside the grace window; the
    # rollback policy restores the last VERIFIED checkpoint on a
    # confirmed anomaly and keeps training within its budget.
    # accept a pre-built injector: the supervisor passes ONE instance
    # through every retry attempt, so its fired flags persist and an
    # injected fault is transient (fires once per supervised run, not
    # once per attempt — refiring every attempt would model a permanent
    # bug no retry policy could absorb)
    faults = (
        inject_faults if isinstance(inject_faults, FaultInjector)
        else (FaultInjector(inject_faults) if inject_faults else None)
    )
    if faults is not None:
        # storage faults (enospc/slow_write) fire INSIDE the checkpoint
        # write — install the injector as the writer shim for this run
        # (cleared in the finally; the hook is process-global because
        # the async writer thread has no per-save plumbing)
        from theanompi_tpu.utils.checkpoint import set_write_fault_hook

        set_write_fault_hook(faults.write_fault)
        # slice-granular topology faults (slice_down) resolve their
        # survivor world from the mesh THIS attempt actually built —
        # re-registered every attempt, so an elastic retry's shrunk
        # shape is what the next whole-slice loss subtracts from
        from theanompi_tpu.parallel.mesh import slice_topology

        faults.set_topology(*slice_topology(mesh))
    # background keep-chain scrubber (chaos PR): periodic re-verify +
    # quarantine of corrupt checkpoint members, reported through the
    # obs facade (kind=scrub + tmpi_scrub_* gauges)
    scrubber = None
    if ckpt_dir and scrub_interval and scrub_interval > 0:
        from theanompi_tpu.utils.checkpoint import CheckpointScrubber

        scrubber = CheckpointScrubber(
            ckpt_dir, interval=float(scrub_interval),
            on_result=obs.note_scrub,
        )
        scrubber.start()
    rollbacks = 0
    rollback_budget_left = (
        max(0, int(rollback_budget)) if on_anomaly == "rollback" else 0
    )
    skip_from_step: Optional[int] = None  # anomalous step whose batch
    # window the post-rollback replay skips (per-step path)
    skip_data_batches = 0
    skipped_steps_total = skipped_prior  # timeline total, persisted in
    # every checkpoint's meta so replay positioning survives resume
    # set the moment an anomaly is detected in the LIVE state (a flush
    # during preemption/unwinding making the first detection): both the
    # preemption save and the finally's crash save honor it, so a
    # poisoned state can never become the newest resumable checkpoint
    _state_poisoned = False

    def _save_meta():
        # checkpoint meta: pipeline layout + (when any) the rollback-
        # skipped batch count — the replay-position correction a later
        # resume needs (batches consumed = step + skipped)
        m = dict(layout_meta or {})
        if skipped_steps_total:
            m["skipped_batches"] = skipped_steps_total
        return m or None
    # step of the newest durable checkpoint: the crash-path save in the
    # finally below must not duplicate a boundary save (-1 = none yet)
    last_ckpt_step = step_count if summary_resumed_from is not None else -1
    _preempt = {"flag": False}
    _prev_sigterm = None
    if sigterm_grace and sigterm_grace > 0:
        if threading.current_thread() is threading.main_thread():

            def _on_sigterm(signum, frame):
                _preempt["flag"] = True
                print(
                    f"[rank {jax.process_index()}] SIGTERM: will "
                    f"checkpoint and exit within the {sigterm_grace}s "
                    "grace window",
                    flush=True,
                )

            _prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)
        else:
            print(
                f"[rank {jax.process_index()}] WARNING: sigterm_grace "
                "needs the main thread (signal handlers cannot be "
                "installed from session-API background threads); "
                "preemption grace is off for this run",
                flush=True,
            )
    # the device trace and the JSONL log must be closed even when a
    # step raises (OOM, loader failure, Ctrl-C) — close() stops a
    # live capture and warns if the window never opened
    try:
        epoch = start_epoch
        while epoch < n_epochs:
          try:
            rec.start_epoch()
            epoch_steps = 0
            t_loop0 = time.perf_counter()
            if fuse > 1:
                # fused dispatch: groups of <= fuse batches, stacked and
                # shipped in one transfer, run by one compiled program
                import itertools

                with PrefetchLoader(
                    grouper(
                        itertools.islice(
                            data.train_epoch(epoch, batch, seed=seed, part=part),
                            skip_batches,
                            None,
                        ),
                        fuse,
                    ),
                    place_group,
                    # depth counts GROUPS here: keep device-resident input
                    # comparable to the per-step path (depth x fuse steps
                    # prefetched would scale input HBM by fuse)
                    depth=max(1, prefetch_depth // fuse),
                ) as loader:
                    skip_batches = 0
                    rec.start("wait")
                    for xs, ys in loader:
                        if _preempt["flag"]:
                            raise Preempted(step_count)
                        # the group's last step, after the trim below
                        last = step_count + int(xs.shape[0])
                        disp.note_wait(rec.end(
                            "wait",
                            step=min(last, max_steps) if max_steps else last))
                        if max_steps and step_count + xs.shape[0] > max_steps:
                            # trim the final group to land exactly on max_steps
                            keep = max_steps - step_count
                            xs, ys = xs[:keep], ys[:keep]
                        rec.profile_tick(step_count)
                        g = int(xs.shape[0])
                        if faults is not None:
                            # fused injection at GROUP granularity: a
                            # fault due anywhere in the group fires
                            # before its dispatch; nan_batch poisons
                            # the whole stacked transfer (the sentinel
                            # machinery reads it identically)
                            faults.check_step(step_count + 1, step_count + g)
                            xs = faults.poison_batch(
                                xs, step_count + 1, step_count + g
                            )
                        # numerics under fusion: the dispatch unit is
                        # the GROUP, so the cadence gates at group
                        # granularity — the numerics variant runs only
                        # for groups that contain a step on the nfreq
                        # grid (then sentinels ride every substep of
                        # that group; per-substep gating would split
                        # the compiled program). GoSGD's param-sized
                        # divergence pmean is therefore still amortized
                        # by raising --numerics-freq.
                        nm_group = bool(nfreq) and (
                            (step_count + g) // nfreq > step_count // nfreq
                        )
                        # the SAME sequential splits the per-step path draws,
                        # shipped stacked — fused training is bit-identical
                        state, metrics = _dispatch_and_split(
                            engine.fused_train_step, state, xs, ys,
                            g, True, nm_group,
                        )
                        step_count += g
                        epoch_steps += g
                        dispatch_images.append(batch * g)
                        # liveness first (watchdog/heartbeat learn of the
                        # dispatch immediately — a hung collective stops
                        # the drain, and with it further dispatches,
                        # within `depth` groups), then rows + step timing
                        # via the dispatcher's drain — the only host sync
                        # in this loop
                        obs.on_step(step_count, substeps=g)
                        disp.push(step_count, metrics,
                                  n_images=batch * g, substeps=g)
                        rec.start("wait")
                        if max_steps and step_count >= max_steps:
                            break
                    # the epoch-tail wait (the loader's StopIteration
                    # fetch) must be credited too, or the flush below
                    # would attribute it to the in-flight steps AND the
                    # wait bracket — double counting that breaks the
                    # span-fraction invariant
                    disp.note_wait(rec.end("wait", step=step_count + 1))
                disp.flush()
                rec.end_epoch(epoch, n_images=epoch_steps * batch)
            else:
                with PrefetchLoader(
                    data.train_epoch(epoch, batch, seed=seed, part=part),
                    place,
                    depth=prefetch_depth,
                ) as loader:
                    rec.start("wait")
                    for xg, yg in loader:
                        if skip_batches:
                            skip_batches -= 1
                            continue
                        if skip_from_step is not None and (
                            step_count + 1 == skip_from_step
                        ):
                            # post-rollback replay reached the anomalous
                            # step again: skip its batch window (consume
                            # the data and its rng splits, train
                            # nothing) so a persistent bad batch cannot
                            # re-poison every rollback attempt
                            skip_from_step = None
                            skip_data_batches = max(0, int(rollback_skip))
                        if skip_data_batches:
                            skip_data_batches -= 1
                            skipped_steps_total += 1
                            keys.take(1)
                            continue
                        if _preempt["flag"]:
                            raise Preempted(step_count)
                        disp.note_wait(rec.end("wait", step=step_count + 1))
                        if faults is not None:
                            faults.check_step(step_count + 1)
                            xg = faults.poison_batch(xg, step_count + 1)
                        rec.profile_tick(step_count)
                        # sentinel cadence: every nfreq-th step runs the
                        # numerics variant of the SAME compiled step
                        # (extra scalar outputs; obs/numerics.py) — the
                        # scalars drain with the loss, no host sync here
                        state, metrics = _dispatch_and_split(
                            engine.train_step, state, xg, yg, 1, False,
                            bool(nfreq) and (step_count + 1) % nfreq == 0,
                        )
                        step_count += 1
                        epoch_steps += 1
                        dispatch_images.append(batch)
                        # liveness first (watchdog/heartbeat track
                        # dispatched progress; a hang stops dispatches
                        # within `depth` steps), then the row + step
                        # timing via the dispatcher's drain (step
                        # N-depth+1 while this step runs) — the per-step
                        # host round trip lives ONLY there
                        obs.on_step(step_count)
                        disp.push(step_count, metrics, n_images=batch)
                        # periodic exchange (EASGD avg_freq; reference: worker
                        # loop calling exchanger.exchange() — recorded as 'comm')
                        if engine.exchange_every and step_count % engine.exchange_every == 0:
                            # exchange boundary: drain in-flight metrics
                            # first so the comm bracket below times the
                            # collective, not K backlogged steps
                            disp.flush()
                            rec.start("comm")
                            state = engine.exchange(state)
                            # sync on a leaf of the exchanged state: without it
                            # the bracket measures only async dispatch and the
                            # collective's real cost bleeds into the next
                            # wait/step brackets
                            cdt = rec.end(
                                "comm", sync=jax.tree_util.tree_leaves(state)[0],
                                step=step_count,
                            )
                            # the comm gauge's denominator includes the
                            # exchange's wall time on the steps that pay
                            # it (amortized bytes / local-only time would
                            # report gbps above the physical link)
                            obs.note_step_seconds(
                                (disp.last_step_seconds or 0.0) + cdt
                            )
                        rec.start("wait")
                        if max_steps and step_count >= max_steps:
                            break
                    # credit the epoch-tail wait (see the fused path)
                    disp.note_wait(rec.end("wait", step=step_count + 1))
                disp.flush()
                rec.end_epoch(epoch, n_images=epoch_steps * batch)

            train_loop_s += time.perf_counter() - t_loop0

            # validation (reference: per-epoch val loop on the worker/server)
            val_accum: Optional[dict] = None
            n_val = 0
            rec.start("eval")
            for vx, vy in data.val_epoch(vbatch, part=vpart):
                vm = engine.eval_step(state, *place((vx, vy)))
                # device-side accumulation: the adds dispatch async and
                # the ONE D2H for the whole val epoch happens below —
                # the old per-batch float(v) was a hidden host round
                # trip per val batch (the same tax the train loop paid).
                # Accumulate in float32 regardless of the metric dtype
                # (the old host sum was float64; low-precision metrics
                # would drift far worse summed in their own dtype)
                vm = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a, jnp.float32), vm
                )
                val_accum = (
                    vm if val_accum is None
                    else jax.tree_util.tree_map(jnp.add, val_accum, vm)
                )
                n_val += 1
            rec.end(
                "eval",
                sync=None if val_accum is None
                else jax.tree_util.tree_leaves(val_accum)[0],
                step=step_count,
            )
            if n_val:
                val_metrics = {k: float(v) / n_val for k, v in val_accum.items()}
                rec.val_metrics(epoch, val_metrics)
                summary["val"] = val_metrics
                # a non-finite val metric is an anomaly even when the
                # sentinel cadence skipped the poisoning train step
                obs.check_val_metrics(epoch, step_count, val_metrics)

            if ckpt_dir and (epoch + 1) % ckpt_every_epochs == 0:
                rec.start("checkpoint")
                if ckpt_writer is not None:
                    # overlapped with the next epoch's steps; ordering +
                    # durability enforced by the writer (drained in the
                    # finally below before the summary returns) — this
                    # bracket times only the enqueue; the real write is
                    # spanned inside utils/checkpoint.py on its thread
                    ckpt_writer.save(ckpt_dir, state, step_count,
                                     rng=keys.carry, extra_meta=_save_meta(),
                                     topology=topo_meta)
                else:
                    sync_save(ckpt_dir, state, step_count, rng=keys.carry,
                              extra_meta=_save_meta(), topology=topo_meta)
                rec.end("checkpoint", step=step_count)
                last_ckpt_step = step_count
                if faults is not None:
                    # post-save storage mutations (ckpt_truncate /
                    # bitrot / partial_set): mangle the newest COMMITTED
                    # checkpoint the way torn writes / at-rest bit-rot /
                    # a lost shard file would (the async save must be
                    # durable first, or the PREVIOUS file would be the
                    # one mutated) — latest_checkpoint(verify=True) and
                    # the scrubber must absorb them
                    due = faults.storage_mutations_due(step_count)
                    if due:
                        if ckpt_writer is not None:
                            ckpt_writer.wait()
                        for spec in due:
                            faults.apply_storage_mutation(spec, ckpt_dir)
            rec.save()
            obs.snapshot(step=step_count)  # epoch-boundary metrics snapshot
            summary["epochs"].append(epoch)
            if max_steps and step_count >= max_steps:
                break
            epoch += 1
          except RollbackRequested as rb:
            # --on-anomaly rollback: restore the newest VERIFIED
            # checkpoint and keep training. The dispatcher's in-flight
            # entries belong to steps the restore is about to erase —
            # discard them, never drain (draining would re-run anomaly
            # detection on the very rows that fired). With the budget
            # exhausted, no ckpt_dir, or nothing verified on disk, the
            # raise stands and rollback degrades to halt semantics.
            disp.discard()
            if rollback_budget_left <= 0 or not ckpt_dir:
                raise
            if ckpt_writer is not None:
                try:
                    ckpt_writer.wait()  # the pre-anomaly boundary save
                except Exception as e:  # noqa: BLE001
                    print(f"checkpoint writer failed before rollback "
                          f"(suppressed): {e!r}", flush=True)
            path = latest_checkpoint(ckpt_dir, verify=True)
            if n_proc > 1:
                # same agreement guard as the resume path: every
                # controller must restore the SAME step (an NFS
                # attribute cache or a short sharded set can make one
                # rank resolve an older checkpoint) or the lockstep
                # SPMD replay diverges/deadlocks silently
                from jax.experimental import multihost_utils

                steps_seen = np.asarray(
                    multihost_utils.process_allgather(
                        np.int64(checkpoint_step(path))
                    )
                ).reshape(-1)
                if not np.all(steps_seen == steps_seen[0]):
                    raise RuntimeError(
                        f"controller processes resolved different "
                        f"rollback checkpoints {steps_seen.tolist()} "
                        f"(this is process {jax.process_index()}): "
                        f"ckpt_dir={ckpt_dir!r} views disagree"
                    ) from rb
            if path is None:
                raise
            rollback_budget_left -= 1
            rollbacks += 1
            restored, saved_rng = load_checkpoint(path, state)
            state = _place_restored(restored)
            if saved_rng is not None:
                keys.reset(saved_rng)
            step_count = engine.get_step(state)
            last_ckpt_step = step_count
            # replay from the restored boundary; the per-step path
            # skips the anomalous step's batch window when it gets
            # there (fused dispatch replays without skipping: transient
            # faults clear on replay, persistent ones exhaust the
            # budget)
            skip_from_step = (
                rb.step if (rollback_skip and fuse == 1) else None
            )
            skip_data_batches = 0
            # position by BATCHES CONSUMED in the restored timeline:
            # the checkpoint's meta records the batches earlier
            # rollbacks skipped before it was written — skips after it
            # are erased with the state they fed
            from theanompi_tpu.utils.checkpoint import read_checkpoint_meta

            skipped_steps_total = int(
                read_checkpoint_meta(path).get("skipped_batches", 0)
            )
            consumed = step_count + skipped_steps_total
            epoch = consumed // steps_per_epoch
            skip_batches = consumed % steps_per_epoch
            obs.note_rollback(rb.step, step_count, rollback_budget_left,
                              skipped=int(rollback_skip) if fuse == 1 else 0)
            print(
                f"[rank {jax.process_index()}] anomaly rollback: restored "
                f"{path} at step {step_count} (anomaly at step {rb.step}; "
                f"budget left {rollback_budget_left})",
                flush=True,
            )
          except Preempted:
            # SIGTERM grace: persist what we have — drain the in-flight
            # rows, make any async save durable, write a final
            # checkpoint at the current step, and mark the run
            # resumable so the supervisor's next invocation picks it
            # up. The re-raise unwinds through the finally below
            # (recorder/obs close) and reaches the CLI/supervisor as a
            # clean, resumable exit.
            try:
                disp.flush()
            except NumericsAnomaly as e:
                # the drained tail held the FIRST detection of an
                # anomaly: the live state is poisoned — it must NOT
                # become the newest resumable checkpoint (quarantine
                # invariant; the flag also disarms the finally's crash
                # save); the marker still lands, so the next invocation
                # resumes from the last GOOD checkpoint
                _state_poisoned = True
                print(f"numerics anomaly surfaced during preemption "
                      f"flush; skipping the final checkpoint: {e!r}",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"dispatch flush failed during preemption "
                      f"(suppressed): {e!r}", flush=True)
            if ckpt_dir:
                if ckpt_writer is not None:
                    # suppressed like the rollback path: a failed
                    # BACKGROUND write must not replace the clean
                    # Preempted exit (the sync save below still runs)
                    try:
                        ckpt_writer.wait()
                    except Exception as e:  # noqa: BLE001
                        print(f"checkpoint writer failed during "
                              f"preemption (suppressed): {e!r}",
                              flush=True)
                if step_count != last_ckpt_step and not _state_poisoned:
                    # best-effort like the crash-save path: a failed
                    # final save (quota, transient NFS) must not
                    # replace the clean Preempted exit — the last
                    # boundary checkpoint is still a valid resume
                    # point, and the marker below records it
                    try:
                        sync_save(ckpt_dir, state, step_count, rng=keys.carry,
                                  extra_meta=_save_meta(),
                                  topology=topo_meta)
                        last_ckpt_step = step_count
                    except Exception as e:  # noqa: BLE001
                        print(f"final preemption checkpoint failed "
                              f"(suppressed; marker will point at step "
                              f"{last_ckpt_step}): {e!r}", flush=True)
                if jax.process_index() == 0:
                    write_resumable_marker(ckpt_dir, last_ckpt_step,
                                           "sigterm")
            raise

    finally:
        # best-effort drain of in-flight step metrics BEFORE the
        # recorder closes: an exception mid-epoch with dispatch_depth>1
        # leaves up to depth-1 executed steps buffered — their rows are
        # exactly the pre-crash tail a post-mortem reads, and sync mode
        # would have persisted them (clean exits reach here with the
        # buffer already empty: the boundary flushes ran). Suppressed on
        # failure: a poisoned device value must not mask the training
        # exception already propagating. SKIPPED when unwinding a
        # BaseException (KeyboardInterrupt/SystemExit): Ctrl-C on a
        # wedged collective is the canonical escape hatch, and the
        # flush's block_until_ready would never return — the recorder
        # and obs must still close so the process can exit.
        # ... and wrapped so a KeyboardInterrupt arriving DURING the
        # flush's device sync still reaches rec.close()/obs.close()
        # in the inner finally below.
        try:
            _exc = sys.exc_info()[0]
            if _exc is None or issubclass(_exc, Exception):
                try:
                    disp.flush()
                except NumericsAnomaly as e:
                    # first detection arrived in the unwinding flush:
                    # the state is poisoned — record that so the crash
                    # save below cannot quarantine-break (the anomaly
                    # itself stays suppressed; the original exception
                    # keeps propagating)
                    _state_poisoned = True
                    print(f"numerics anomaly surfaced during error-"
                          f"unwinding flush (suppressed): {e!r}",
                          flush=True)
                except Exception as e:  # noqa: BLE001
                    print(f"dispatch flush failed during error unwinding "
                          f"(suppressed): {e!r}", flush=True)
            if (
                _exc is not None
                and issubclass(_exc, Exception)
                and not issubclass(_exc, NumericsAnomaly)
                and not _state_poisoned
                and ckpt_dir
                and step_count > last_ckpt_step
            ):
                # crash-path durability: an exception with an async save
                # still in flight must not lose the newest state — wait()
                # the pending write, then attempt ONE final synchronous
                # checkpoint at the crash step (the disp.flush() pattern
                # above, applied to state). Best-effort: a poisoned
                # device value can fail the gather, and that failure
                # must not mask the training exception propagating.
                # Skipped for NumericsAnomaly unwinds (halt / rollback
                # budget exhausted): that state IS the anomalous one —
                # making it the newest resumable checkpoint would poison
                # every future resume; the flight dump's state/ capture
                # already preserves it for triage, quarantined from the
                # resume chain.
                try:
                    if ckpt_writer is not None:
                        ckpt_writer.wait()
                    sync_save(ckpt_dir, state, step_count, rng=keys.carry,
                              extra_meta=_save_meta(), topology=topo_meta)
                    last_ckpt_step = step_count
                    print(
                        f"[rank {jax.process_index()}] crash checkpoint "
                        f"saved at step {step_count}",
                        flush=True,
                    )
                except Exception as e:  # noqa: BLE001
                    print(f"crash checkpoint failed during error "
                          f"unwinding (suppressed): {e!r}", flush=True)
        finally:
            try:
                if ckpt_writer is not None:
                    # may re-raise a failed background write — but never
                    # let that replace a training exception already
                    # propagating (the original would survive only as
                    # __context__)
                    if sys.exc_info()[0] is not None:
                        try:
                            ckpt_writer.close()
                        except Exception as e:  # noqa: BLE001
                            print(
                                f"checkpoint writer failed during error "
                                f"unwinding (suppressed): {e!r}",
                                flush=True,
                            )
                    else:
                        ckpt_writer.close()
            finally:
                try:
                    rec.close()  # trace + JSONL must close even then
                finally:
                    # final snapshot + span summary + health-thread
                    # shutdown; after rec.close() so the recorder's last
                    # emissions land
                    try:
                        obs.close()
                    finally:
                        try:
                            if fleet_exporter is not None:
                                # server down + tailer joined; the last
                                # merged view stays in fleet.jsonl for
                                # post-mortem `tmpi top --once`
                                try:
                                    fleet_exporter.stop()
                                except Exception as e:  # noqa: BLE001
                                    print(f"fleet exporter stop failed "
                                          f"(suppressed): {e!r}",
                                          flush=True)
                            if faults is not None:
                                # uninstall the process-global writer
                                # shim (installed where faults armed) —
                                # AFTER the crash/preempt saves above,
                                # so a due write fault can still hit
                                # them like any other save
                                from theanompi_tpu.utils.checkpoint import (
                                    set_write_fault_hook as _clear_wfh,
                                )

                                _clear_wfh(None)
                            if scrubber is not None:
                                scrubber.stop()
                        finally:
                            if _prev_sigterm is not None:
                                # restore the caller's SIGTERM disposition
                                # (tests and stacked runs share the process)
                                signal.signal(signal.SIGTERM, _prev_sigterm)
    # reached only on success: a completed run consumed any resumable
    # marker a preempted predecessor left — otherwise a later SUPERVISED
    # run reusing this ckpt_dir would silently flip into resume mode
    # off the stale marker (the supervisor clears its own, but plain
    # --resume completions must too)
    if ckpt_dir and jax.process_index() == 0:
        clear_resumable_marker(ckpt_dir)
    summary["steps"] = step_count
    # device-truth step counter (host-fetched AFTER training): the host
    # loop counts dispatches, the counter inside the compiled step
    # counts executions — a dispatch that never ran on the device shows
    # up as a mismatch here (chip_smoke.py and benchmark/ check it)
    summary["device_steps"] = engine.get_step(state)
    # the devices this run's mesh actually held (not jax.devices(): an
    # explicit device list or a capped world may differ)
    _dev0 = mesh.devices.reshape(-1)[0]
    summary["device"] = {"platform": _dev0.platform,
                         "kind": _dev0.device_kind, "count": int(n_dev)}
    # dispatch-pipeline accounting: how much of the train loop the host
    # spent BLOCKED on device syncs (the per-step tax dispatch_depth>1
    # removes)
    summary["dispatch_depth"] = disp.depth
    # the key stream's engagement: keys that were ready when taken over
    # keys taken (1 less the first unit; falling = refill not ahead)
    summary["keys_ready_share"] = keys.ready_share
    # the pipeline's engagement: dispatches made while the step before
    # was still running on the device, over dispatches (depth 2: 1 less
    # the first after each flush; about 0 at depth 1; falling = the host
    # has become the pace)
    summary["dispatch_ahead_share"] = disp.ahead_share
    # numerics flight recorder: anomalies seen at drain time (0 when
    # numerics is off) — a nonzero count with policy 'record'/'dump' is
    # the "check the triage bundle" signal for sweep drivers
    summary["anomalies"] = obs.anomaly_count
    # anomaly-rollback accounting (--on-anomaly rollback): restores of
    # the last verified checkpoint, and the data batches the replay
    # skipped at the anomalous steps
    summary["rollbacks"] = rollbacks
    summary["skipped_steps"] = skipped_steps_total
    if ckpt_writer is not None:
        # boundary saves the ENOSPC-safe async writer absorbed (torn
        # attempt, chain intact — utils/checkpoint.AsyncCheckpointer):
        # nonzero means the checkpoint cadence silently degraded, which
        # a success summary must not hide
        summary["ckpt_storage_failures"] = ckpt_writer.storage_failures
    summary["host_blocked_s"] = round(disp.host_blocked_s, 6)
    summary["train_loop_s"] = round(train_loop_s, 6)
    summary["host_blocked_frac"] = (
        round(min(1.0, disp.host_blocked_s / train_loop_s), 6)
        if train_loop_s > 0 else None
    )
    k_recent = min(50, len(dispatch_images))
    t_recent = rec.mean_time("step", k_recent)
    summary["images_per_sec"] = (
        (sum(dispatch_images[-k_recent:]) / k_recent) / t_recent
        if (k_recent and t_recent) else 0.0
    )
    if obs.cost is not None and summary["images_per_sec"]:
        # achieved utilization from the SHARED cost model (the same
        # numbers the live gauges carry): per-step seconds recovered from the
        # throughput ledger so fused dispatches amortize correctly
        _sps = batch / summary["images_per_sec"]
        _mfu = obs.cost.mfu(_sps)
        summary["mfu"] = round(_mfu, 4) if _mfu is not None else None
        summary["tflops_per_sec"] = round(obs.cost.flops / _sps / 1e12, 6)
    if return_recorder:
        summary["recorder"] = rec
    return summary
