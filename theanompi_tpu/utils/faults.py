"""Deterministic fault injection: every recovery path gets exercised.

The reference framework had no failure story at all — a dead worker or
a NaN burst killed the whole mpirun (SURVEY.md §5.4) — and a recovery
path that is never executed is a recovery path that does not work. This
module is the registry behind ``--inject-fault KIND@STEP`` (repeatable):
a :class:`FaultInjector` armed with one or more :class:`FaultSpec`\\ s
fires each of them exactly once, at a deterministic global step, so the
supervisor's retry loop, the checkpoint integrity chain, the anomaly
rollback policy, and the SIGTERM grace path are all proven by tier-1
tests instead of trusted on faith.

Fault kinds (``KIND@STEP`` or ``KIND@STEP:ARG``):

- ``crash``        raise :class:`InjectedCrash` before dispatching STEP
                   (an in-process worker-loop exception: OOM, loader
                   bug, poisoned collective — the supervisor retries it)
- ``sigterm``      ``os.kill(self, SIGTERM)`` before STEP (preemption;
                   with ``--sigterm-grace`` the driver checkpoints and
                   exits cleanly, marking the run resumable)
- ``sigkill``      ``os.kill(self, SIGKILL)`` before STEP (hard host
                   death: no finally, no grace — resume must come from
                   the last durable checkpoint)
- ``ckpt_truncate`` truncate the newest checkpoint file after the first
                   save at/after STEP (torn write / died mid-replace:
                   ``latest_checkpoint(verify=True)`` must walk back)
- ``nan_batch``    poison the data batch feeding STEP with NaN (a bad
                   shard / corrupted record: the numerics sentinels and
                   the rollback policy must absorb it)
- ``loader_stall`` sleep ARG seconds (default 2.0) before STEP (a hung
                   data source: the stall watchdog's territory)
- ``shrink``       raise :class:`TopologyChanged` before STEP with the
                   world shrunk to ARG devices (a slice died; the
                   elastic supervisor must reshard-and-resume onto the
                   smaller mesh — ``shrink@3:2``)
- ``grow``         like ``shrink`` but ARG grows the world (capacity
                   returned; ``grow@3:4``)
- ``slice_down``   whole-slice loss: :class:`TopologyChanged` before
                   STEP with ARG slices (default 1) removed from the
                   CURRENT mesh topology (``slice_down@3`` /
                   ``slice_down@3:2``). Unlike ``shrink`` the arg is
                   slice-granular — the surviving world is computed
                   from the topology the run registered via
                   :meth:`FaultInjector.set_topology`, so the same spec
                   exercises reshard-to-survivors on any ``--slices N``
                   shape (a DCN partition isolating a whole ICI domain,
                   the failure unit real pods lose)

Storage-level kinds (chaos PR) — the fault matrix used to stop at the
process boundary; these reach into the checkpoint write path itself:

- ``enospc``       the first checkpoint save at/after STEP raises
                   ``OSError(ENOSPC)`` MID-WRITE via the injectable
                   writer shim in ``utils/checkpoint.py`` (disk full /
                   quota: the torn attempt must read as absent and the
                   keep-chain must stay restorable)
- ``slow_write``   the first save at/after STEP stalls ARG seconds
                   (default 2.0) inside the writer (a degraded NFS
                   mount: the async checkpointer's NEXT save blocks the
                   driver — the stall watchdog's territory)
- ``bitrot``       flip bytes in the newest COMMITTED keep-chain member
                   after the first save at/after STEP (at-rest
                   bit-corruption: the CRC32 chain must catch it and
                   the scrubber must quarantine it)
- ``partial_set``  delete one member of the newest sharded checkpoint
                   set after the first save at/after STEP (a host's
                   file lost: completeness-by-counting must read the
                   torn set as absent)

Injection points live in ``launch/worker.py``'s train loops; all hooks
are host-side and sync-free (``tools/check_hot_loop.py`` stays green).

Cross-process once-only semantics: the in-process supervisor threads
ONE injector through every retry, so fired flags persist. A run that is
relaunched as a NEW process (SIGKILL under an outer chaos campaign,
rc-75 preemption) would re-fire every fault — unless the injector is
armed with a ``ledger`` file: every fired spec is appended (and fsynced
BEFORE the fault's side effect, so even a SIGKILL cannot lose the
entry) and specs already in the ledger arm as fired. ``--fault-ledger``
on the CLI wires it; ``tools/chaos.py`` relies on it to relaunch killed
runs without replaying their faults.
"""

from __future__ import annotations

import os
import signal
import time
from dataclasses import dataclass
from typing import Optional, Sequence, Union


class InjectedFault(RuntimeError):
    """Base class for failures raised by the fault injector."""


class InjectedCrash(InjectedFault):
    """The ``crash`` fault: an ordinary worker-loop exception, exactly
    what the run supervisor's bounded-retry loop exists to absorb."""


class TopologyChanged(InjectedFault):
    """The ``shrink``/``grow``/``slice_down`` faults: the visible device
    world changed mid-run (a slice died, or capacity came back). The
    attempt dies like
    any infrastructure fault; under ``supervise_training(elastic=True)``
    the retry re-probes the world (honoring :meth:`FaultInjector.
    world_override` in tests), rebuilds the mesh at ``new_world``
    devices, and reshards the checkpoint onto it
    (utils/checkpoint.load_resharded)."""

    def __init__(self, kind: str, step: int, new_world: int):
        self.kind = str(kind)
        self.step = int(step)
        self.new_world = int(new_world)
        super().__init__(
            f"injected {kind} before step {step}: world is now "
            f"{new_world} device(s)"
        )


class Preempted(RuntimeError):
    """Graceful SIGTERM exit: the driver checkpointed inside the grace
    window and marked the run resumable (``launch/worker.py``). The
    supervisor records it as a resumable attempt and exits — the next
    invocation auto-resumes from the marker."""

    def __init__(self, step: int):
        self.step = int(step)
        super().__init__(
            f"preempted (SIGTERM) at step {step}: checkpointed and "
            "marked resumable"
        )


FAULT_KINDS = (
    "crash", "sigterm", "sigkill", "ckpt_truncate", "nan_batch",
    "loader_stall", "shrink", "grow", "slice_down",
    # storage-level kinds (chaos PR): enospc/slow_write fire INSIDE the
    # write via the checkpoint writer shim; bitrot/partial_set mutate a
    # COMMITTED file after the save lands (like ckpt_truncate)
    "enospc", "slow_write", "bitrot", "partial_set",
)

# post-save mutators: applied to a durable checkpoint after the first
# save at/after the spec's step (the ckpt_truncate family)
STORAGE_MUTATION_KINDS = ("ckpt_truncate", "bitrot", "partial_set")

# during-write faults: consulted by the checkpoint writer shim
# (utils/checkpoint.set_write_fault_hook) at each save's step
WRITE_FAULT_KINDS = ("enospc", "slow_write")


@dataclass
class FaultSpec:
    """One armed fault: ``kind`` fires once at global step ``step``.
    ``fired_seq`` stamps the ORDER the injector fired specs in (-1 =
    not fired) — what "the LAST fired topology fault" means cannot
    depend on the order specs were listed on the command line."""

    kind: str
    step: int
    arg: Optional[float] = None
    fired: bool = False
    fired_seq: int = -1
    # slice_down resolves its survivor world from the registered
    # topology AT FIRE TIME (the spec's arg is slices lost, not a world
    # size) — recorded here so world_override can replay the answer
    resolved_world: Optional[int] = None


def parse_fault_spec(spec: Union[str, FaultSpec]) -> FaultSpec:
    """``KIND@STEP`` / ``KIND@STEP:ARG`` -> :class:`FaultSpec`."""
    if isinstance(spec, FaultSpec):
        return spec
    kind, sep, rest = str(spec).partition("@")
    if not sep:
        raise ValueError(
            f"fault spec {spec!r} must be KIND@STEP (e.g. crash@5); "
            f"kinds: {FAULT_KINDS}"
        )
    if kind not in FAULT_KINDS:
        raise ValueError(f"unknown fault kind {kind!r}; kinds: {FAULT_KINDS}")
    step_s, sep2, arg_s = rest.partition(":")
    try:
        step = int(step_s)
    except ValueError:
        raise ValueError(f"fault spec {spec!r}: step {step_s!r} is not an int")
    if step < 1:
        raise ValueError(f"fault spec {spec!r}: steps are 1-based")
    arg = None
    if sep2:
        try:
            arg = float(arg_s)
        except ValueError:
            raise ValueError(f"fault spec {spec!r}: arg {arg_s!r} is not a number")
    if kind in ("shrink", "grow"):
        # the arg IS the post-fault world size — elastic recovery is
        # only testable against a deterministic target topology
        if arg is None or int(arg) != arg or arg < 1:
            raise ValueError(
                f"fault spec {spec!r}: {kind} needs an integer target "
                f"world size >= 1 (e.g. {kind}@{step}:2)"
            )
    if kind == "slice_down" and arg is not None and (
            int(arg) != arg or arg < 1):
        raise ValueError(
            f"fault spec {spec!r}: slice_down's arg is the number of "
            f"slices lost, an integer >= 1 (e.g. slice_down@{step}:1)"
        )
    return FaultSpec(kind=kind, step=step, arg=arg)


class FaultInjector:
    """Fires each armed :class:`FaultSpec` exactly once at its step.

    The driver calls :meth:`check_step` with the 1-based step it is
    ABOUT to dispatch, :meth:`poison_batch` on the batch feeding it,
    :meth:`storage_mutations_due`/:meth:`apply_storage_mutation` around
    checkpoint saves, and installs :meth:`write_fault` as the
    checkpoint writer shim for the during-write kinds. Deterministic by
    construction: same specs + same step sequence = same failures.

    ``ledger``: optional path of a fired-fault ledger (module
    docstring) — specs already recorded there arm as fired, and every
    fire appends+fsyncs its line BEFORE the fault's side effect, so a
    relaunched process armed with the same ledger never replays a
    fault that already happened.
    """

    def __init__(self, specs: Sequence[Union[str, FaultSpec]],
                 ledger: Optional[str] = None):
        self.specs = [parse_fault_spec(s) for s in (specs or [])]
        self._fire_seq = 0
        self._topology: Optional[tuple] = None  # (n_slices, per_slice)
        self._ledger = ledger
        if ledger and os.path.exists(ledger):
            # arm-as-fired anything a previous incarnation already did.
            # Duplicate specs (crash@3 twice) consume ledger entries
            # positionally: two recorded fires mark two specs fired.
            with open(ledger) as f:
                seen = [ln.strip() for ln in f if ln.strip()]
            for entry in seen:
                for s in self.specs:
                    if not s.fired and f"{s.kind}@{s.step}" == entry:
                        s.fired = True
                        s.fired_seq = self._fire_seq
                        self._fire_seq += 1
                        break

    def set_topology(self, n_slices: int, per_slice: int) -> None:
        """Register the CURRENT mesh shape (``parallel.mesh.
        slice_topology``) so slice-granular faults can resolve survivor
        worlds. The driver calls this each attempt, after building its
        mesh — an elastic retry re-registers the shrunk shape, so a
        second ``slice_down`` removes a slice of the world that
        actually survived the first."""
        self._topology = (int(n_slices), int(per_slice))

    def _record_fire(self, s: FaultSpec) -> None:
        """Durably note a fired spec BEFORE its side effect (a SIGKILL
        one line later must not lose the entry)."""
        if not self._ledger:
            return
        with open(self._ledger, "a") as f:
            f.write(f"{s.kind}@{s.step}\n")
            f.flush()
            os.fsync(f.fileno())

    def _take(self, kind: str, step: int) -> Optional[FaultSpec]:
        """The unfired spec of ``kind`` due at ``step`` (marked fired),
        or None."""
        for s in self.specs:
            if s.kind == kind and not s.fired and s.step == step:
                s.fired = True
                s.fired_seq = self._fire_seq
                self._fire_seq += 1
                self._record_fire(s)
                return s
        return None

    def check_step(self, step: int) -> None:
        """Fire crash/sigterm/sigkill/loader_stall faults due before
        dispatching ``step``."""
        s = self._take("loader_stall", step)
        if s is not None:
            time.sleep(2.0 if s.arg is None else float(s.arg))
        s = self._take("crash", step)
        if s is not None:
            raise InjectedCrash(f"injected crash before step {s.step}")
        for kind in ("shrink", "grow"):
            s = self._take(kind, step)
            if s is not None:
                raise TopologyChanged(kind, s.step, int(s.arg))
        s = self._take("slice_down", step)
        if s is not None:
            lost = 1 if s.arg is None else int(s.arg)
            if self._topology is None or self._topology[0] <= 1:
                raise ValueError(
                    f"slice_down@{s.step}: no multislice topology "
                    "registered — the run must build a --slices N mesh "
                    "(N > 1) and call set_topology() for whole-slice "
                    "loss to have a surviving world"
                )
            n_slices, per_slice = self._topology
            survivors = (n_slices - lost) * per_slice
            if survivors < 1:
                raise ValueError(
                    f"slice_down@{s.step}:{lost}: losing {lost} of "
                    f"{n_slices} slice(s) leaves no survivors — elastic "
                    "recovery needs at least one live slice"
                )
            s.resolved_world = survivors
            raise TopologyChanged("slice_down", s.step, survivors)
        s = self._take("sigterm", step)
        if s is not None:
            os.kill(os.getpid(), signal.SIGTERM)
        s = self._take("sigkill", step)
        if s is not None:
            os.kill(os.getpid(), signal.SIGKILL)

    def poison_batch(self, x, step: int):
        """``nan_batch``: return ``x`` poisoned with NaN when a spec is
        due at ``step``, else ``x`` unchanged. Device-side op
        (adds NaN to the already-placed batch) — no host sync, and the
        result keeps ``x``'s sharding. Float batches only (token
        batches raise: an int stream cannot carry NaN)."""
        import jax.numpy as jnp

        s = self._take("nan_batch", step)
        if s is None:
            return x
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise ValueError(
                f"nan_batch@{s.step}: batch dtype {x.dtype} cannot carry "
                "NaN (token/int batches); inject on a float-input model"
            )
        return x + jnp.asarray(float("nan"), x.dtype)

    def world_override(self) -> Optional[int]:
        """The world size the MOST RECENTLY FIRED shrink/grow/
        slice_down fault left behind (by firing order, not command-line
        spec order), or None when no topology fault has fired. Sticky
        by design: the supervisor reuses ONE injector across attempts,
        so a shrunk world stays shrunk for every subsequent elastic
        retry — the CPU-simulation stand-in for re-probing real device
        liveness."""
        fired = [s for s in self.specs
                 if s.kind in ("shrink", "grow", "slice_down") and s.fired]
        if not fired:
            return None
        last = max(fired, key=lambda s: s.fired_seq)
        if last.kind == "slice_down":
            # resolved at fire time from the then-registered topology;
            # a ledger-rearmed spec never fired in THIS process and
            # carries no resolution — fall back to the next-most-recent
            # resolved fault (the world it left is the one that ran)
            resolved = [s for s in fired if s.kind != "slice_down"
                        or s.resolved_world is not None]
            if not resolved:
                return None
            last = max(resolved, key=lambda s: s.fired_seq)
            if last.kind == "slice_down":
                return int(last.resolved_world)
        return int(last.arg)

    def _take_at_or_after(self, kind: str, step: int) -> Optional[FaultSpec]:
        """The unfired spec of ``kind`` due at/after ``step`` (marked
        fired + ledgered) — the save-boundary firing rule: a save can
        land later than the spec's step (epoch cadence), and the fault
        applies to the first save that reaches it."""
        for s in self.specs:
            if s.kind == kind and not s.fired and step >= s.step:
                s.fired = True
                s.fired_seq = self._fire_seq
                self._fire_seq += 1
                self._record_fire(s)
                return s
        return None

    def truncate_due(self, step: int) -> bool:
        """True once when a ``ckpt_truncate`` spec is due at/after
        ``step`` (the driver checks after each checkpoint save)."""
        return self._take_at_or_after("ckpt_truncate", step) is not None

    def storage_mutations_due(self, step: int) -> list:
        """Every post-save storage mutation (``ckpt_truncate`` /
        ``bitrot`` / ``partial_set``) due at/after ``step``, each fired
        once — the driver applies them with
        :meth:`apply_storage_mutation` after the save is DURABLE (an
        async save must be waited first, or the previous file would be
        the one mutated)."""
        out = []
        for kind in STORAGE_MUTATION_KINDS:
            s = self._take_at_or_after(kind, step)
            if s is not None:
                out.append(s)
        return out

    @staticmethod
    def apply_storage_mutation(spec: FaultSpec, ckpt_dir: str) -> Optional[str]:
        """Apply one fired post-save mutation to ``ckpt_dir``; returns
        the mangled/removed path (None when nothing qualified)."""
        if spec.kind == "ckpt_truncate":
            return FaultInjector.truncate_newest(ckpt_dir)
        if spec.kind == "bitrot":
            return FaultInjector.bitrot_newest(ckpt_dir)
        if spec.kind == "partial_set":
            return FaultInjector.drop_sharded_member(ckpt_dir)
        raise ValueError(f"{spec.kind!r} is not a storage mutation")

    def write_fault(self, step: int) -> Optional[tuple]:
        """The checkpoint writer shim hook
        (``utils/checkpoint.set_write_fault_hook``): called by the save
        path with the step being saved; returns ``(kind, arg)`` for a
        due ``enospc``/``slow_write`` spec (fired once), else None. May
        run on the async writer thread — the injector's firing state is
        only ever advanced from one save at a time (the writer
        serializes saves)."""
        for kind in WRITE_FAULT_KINDS:
            s = self._take_at_or_after(kind, step)
            if s is not None:
                return (kind, s.arg)
        return None

    @staticmethod
    def truncate_newest(ckpt_dir: str) -> Optional[str]:
        """Truncate the newest checkpoint file to half its size (a torn
        write: the file exists, the zip central directory is gone).
        Returns the mangled path."""
        from theanompi_tpu.utils.checkpoint import latest_checkpoint

        path = latest_checkpoint(ckpt_dir)  # unverified: the raw newest
        if path is None:
            return None
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(1, size // 2))
        return path

    @staticmethod
    def bitrot_newest(ckpt_dir: str) -> Optional[str]:
        """Flip bytes in the middle of the newest COMMITTED checkpoint
        file (at-rest bit-rot: size and name intact, content corrupt —
        only the CRC32 integrity chain can tell). Returns the path."""
        from theanompi_tpu.utils.checkpoint import latest_checkpoint

        path = latest_checkpoint(ckpt_dir)
        if path is None:
            return None
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.seek(size // 2)
            chunk = f.read(8)
            f.seek(size // 2)
            f.write(bytes(b ^ 0xFF for b in chunk))
        return path

    @staticmethod
    def drop_sharded_member(ckpt_dir: str) -> Optional[str]:
        """Delete one member of the newest COMPLETE sharded checkpoint
        set (a host's file lost after the save landed): the set must
        then read as ABSENT via completeness-by-counting. Returns the
        removed path (None when no sharded set exists)."""
        from theanompi_tpu.utils.checkpoint import _sharded_sets

        sets = _sharded_sets(ckpt_dir)
        if not sets:
            return None
        files = sets[max(sets)]
        victim = files[-1]  # the highest-proc member: deterministic
        os.unlink(victim)
        return victim
