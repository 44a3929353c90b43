"""Fault-injection registry tests (utils/faults.py): spec parsing and
once-only firing semantics — the deterministic substrate every recovery
path's acceptance test stands on."""

import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.utils.faults import (
    FaultInjector,
    FaultSpec,
    InjectedCrash,
    parse_fault_spec,
)


def test_parse_fault_spec():
    s = parse_fault_spec("crash@5")
    assert (s.kind, s.step, s.arg) == ("crash", 5, None)
    s = parse_fault_spec("loader_stall@3:0.25")
    assert (s.kind, s.step, s.arg) == ("loader_stall", 3, 0.25)
    # already-parsed specs pass through
    assert parse_fault_spec(s) is s


@pytest.mark.parametrize("bad", [
    "crash",          # no @STEP
    "explode@5",      # unknown kind
    "crash@x",        # non-int step
    "crash@0",        # steps are 1-based
    "loader_stall@3:fast",  # non-numeric arg
])
def test_parse_fault_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_fault_spec(bad)


def test_crash_fires_exactly_once():
    inj = FaultInjector(["crash@3"])
    inj.check_step(1)
    inj.check_step(2)
    with pytest.raises(InjectedCrash, match="before step 3"):
        inj.check_step(3)
    inj.check_step(3)  # fired: replaying the same step is clean
    inj.check_step(4)


def test_nan_batch_poisons_float_once():
    inj = FaultInjector(["nan_batch@2"])
    x = jnp.ones((4, 3))
    assert np.isfinite(np.asarray(inj.poison_batch(x, 1))).all()
    poisoned = inj.poison_batch(x, 2)
    assert np.isnan(np.asarray(poisoned)).all()
    # fired: the replayed batch comes back clean (transient fault)
    assert np.isfinite(np.asarray(inj.poison_batch(x, 2))).all()


def test_nan_batch_rejects_int_batches():
    inj = FaultInjector(["nan_batch@1"])
    with pytest.raises(ValueError, match="cannot carry"):
        inj.poison_batch(jnp.ones((4,), jnp.int32), 1)


def test_loader_stall_sleeps():
    import time

    inj = FaultInjector(["loader_stall@1:0.15"])
    t0 = time.perf_counter()
    inj.check_step(1)
    assert time.perf_counter() - t0 >= 0.15
    t0 = time.perf_counter()
    inj.check_step(1)  # fired: no second stall
    assert time.perf_counter() - t0 < 0.1


def test_truncate_due_and_truncate_newest(tmp_path):
    from theanompi_tpu.utils.checkpoint import (
        save_checkpoint,
        verify_checkpoint,
    )

    inj = FaultInjector(["ckpt_truncate@4"])
    assert not inj.truncate_due(3)  # not yet
    assert inj.truncate_due(5)      # due at/after step 4
    assert not inj.truncate_due(6)  # fired

    p = save_checkpoint(str(tmp_path), {"w": jnp.arange(64.0)}, 7)
    assert verify_checkpoint(p)
    assert FaultInjector.truncate_newest(str(tmp_path)) == p
    assert not verify_checkpoint(p)


def test_injector_accepts_prebuilt_specs():
    inj = FaultInjector([FaultSpec(kind="crash", step=1)])
    with pytest.raises(InjectedCrash):
        inj.check_step(1)


def test_parse_topology_fault_specs():
    s = parse_fault_spec("shrink@3:2")
    assert (s.kind, s.step, s.arg) == ("shrink", 3, 2.0)
    s = parse_fault_spec("grow@5:4")
    assert (s.kind, s.step, s.arg) == ("grow", 5, 4.0)


@pytest.mark.parametrize("bad", [
    "shrink@3",      # the target world is the whole point
    "grow@3",
    "shrink@3:0",    # worlds are >= 1
    "shrink@3:1.5",  # integral device counts only
])
def test_parse_topology_fault_specs_reject(bad):
    with pytest.raises(ValueError, match="world size"):
        parse_fault_spec(bad)


def test_shrink_fires_once_and_override_is_sticky():
    from theanompi_tpu.utils.faults import TopologyChanged

    inj = FaultInjector(["shrink@3:2"])
    assert inj.world_override() is None  # nothing fired yet
    inj.check_step(1)
    inj.check_step(2)
    with pytest.raises(TopologyChanged) as ei:
        inj.check_step(3)
    assert ei.value.new_world == 2 and ei.value.kind == "shrink"
    # fired once: the replayed step is clean, but the world STAYS
    # shrunk for every later probe (the supervisor reuses one injector
    # across attempts — a dead slice does not resurrect on retry)
    inj.check_step(3)
    inj.check_step(4)
    assert inj.world_override() == 2


def test_grow_after_shrink_latest_fired_wins():
    from theanompi_tpu.utils.faults import TopologyChanged

    inj = FaultInjector(["shrink@2:2", "grow@4:6"])
    with pytest.raises(TopologyChanged):
        inj.check_step(2)
    assert inj.world_override() == 2
    with pytest.raises(TopologyChanged) as ei:
        inj.check_step(4)
    assert ei.value.new_world == 6 and ei.value.kind == "grow"
    assert inj.world_override() == 6


def test_world_override_follows_firing_order_not_spec_order():
    """The sticky world is the LAST FIRED topology fault's — even when
    the specs were listed out of step order on the command line (the
    naive last-in-list answer would be wrong here)."""
    from theanompi_tpu.utils.faults import TopologyChanged

    inj = FaultInjector(["grow@5:4", "shrink@2:2"])
    with pytest.raises(TopologyChanged):
        inj.check_step(2)          # shrink fires first despite being listed second
    assert inj.world_override() == 2
    with pytest.raises(TopologyChanged):
        inj.check_step(5)          # grow fires last -> its world wins
    assert inj.world_override() == 4


# -- storage-level kinds + the fired-fault ledger (chaos PR) ---------------


def test_parse_storage_kinds():
    assert parse_fault_spec("enospc@3").kind == "enospc"
    assert parse_fault_spec("slow_write@2:0.5").arg == 0.5
    assert parse_fault_spec("bitrot@4").kind == "bitrot"
    assert parse_fault_spec("partial_set@2").kind == "partial_set"


def test_write_fault_fires_once_at_or_after_step():
    inj = FaultInjector(["enospc@3"])
    assert inj.write_fault(2) is None           # save before the step
    assert inj.write_fault(4) == ("enospc", None)  # first save at/after
    assert inj.write_fault(5) is None           # fired: once only


def test_storage_mutations_due_fires_each_once():
    inj = FaultInjector(["bitrot@2", "ckpt_truncate@2", "partial_set@5"])
    due = inj.storage_mutations_due(3)
    assert sorted(s.kind for s in due) == ["bitrot", "ckpt_truncate"]
    assert inj.storage_mutations_due(3) == []   # both fired
    assert [s.kind for s in inj.storage_mutations_due(6)] == ["partial_set"]


def test_bitrot_and_partial_set_mutators(tmp_path):
    """bitrot flips bytes the CRC chain must catch; partial_set makes
    the sharded set read as absent (completeness-by-counting)."""
    import jax
    from theanompi_tpu.utils.checkpoint import (
        latest_checkpoint,
        save_checkpoint,
        save_checkpoint_sharded,
        verify_checkpoint,
    )

    state = {"w": jnp.arange(64, dtype=jnp.float32)}
    single = tmp_path / "single"
    save_checkpoint(str(single), state, 3, rng=jax.random.PRNGKey(0))
    assert verify_checkpoint(latest_checkpoint(str(single)))
    mangled = FaultInjector.bitrot_newest(str(single))
    assert mangled.endswith("ckpt_3.npz")
    assert not verify_checkpoint(mangled)       # size intact, CRC not
    import os as _os

    assert _os.path.getsize(mangled) > 0

    sharded = tmp_path / "sharded"
    save_checkpoint_sharded(str(sharded), state, 3,
                            rng=jax.random.PRNGKey(0))
    assert latest_checkpoint(str(sharded)) is not None
    removed = FaultInjector.drop_sharded_member(str(sharded))
    assert removed is not None
    assert latest_checkpoint(str(sharded)) is None  # incomplete = absent


def test_fault_ledger_survives_process_boundary(tmp_path):
    """The cross-process once-only contract: fired specs land in the
    ledger BEFORE their side effect, and a fresh injector armed with
    the same specs + ledger treats them as already fired — duplicates
    consume ledger entries positionally."""
    ledger = str(tmp_path / "ledger.txt")
    inj = FaultInjector(["crash@3", "crash@5", "enospc@2"], ledger=ledger)
    with pytest.raises(InjectedCrash):
        inj.check_step(3)
    assert inj.write_fault(2) == ("enospc", None)
    assert open(ledger).read().splitlines() == ["crash@3", "enospc@2"]

    # the "relaunched process": same specs, same ledger
    inj2 = FaultInjector(["crash@3", "crash@5", "enospc@2"], ledger=ledger)
    inj2.check_step(3)                      # already fired: no raise
    assert inj2.write_fault(4) is None      # enospc consumed too
    with pytest.raises(InjectedCrash):
        inj2.check_step(5)                  # the unfired spec still fires
    assert open(ledger).read().splitlines() == [
        "crash@3", "enospc@2", "crash@5"]


# -- whole-slice loss (hierarchical-collectives PR) ------------------------


def test_parse_slice_down_specs():
    s = parse_fault_spec("slice_down@3")
    assert (s.kind, s.step, s.arg) == ("slice_down", 3, None)  # 1 slice
    s = parse_fault_spec("slice_down@3:2")
    assert (s.kind, s.step, s.arg) == ("slice_down", 3, 2.0)
    for bad in ("slice_down@3:0", "slice_down@3:1.5"):
        with pytest.raises(ValueError, match="slices lost"):
            parse_fault_spec(bad)


def test_slice_down_resolves_survivors_from_topology():
    from theanompi_tpu.utils.faults import TopologyChanged

    inj = FaultInjector(["slice_down@3"])
    inj.set_topology(2, 4)  # 2 slices x 4 chips
    inj.check_step(1)
    with pytest.raises(TopologyChanged) as ei:
        inj.check_step(3)
    assert ei.value.kind == "slice_down" and ei.value.new_world == 4
    # sticky like shrink: the dead slice stays dead across retries
    inj.check_step(3)
    assert inj.world_override() == 4


def test_slice_down_needs_multislice_topology():
    inj = FaultInjector(["slice_down@2"])
    with pytest.raises(ValueError, match="multislice topology"):
        inj.check_step(2)  # never registered
    inj2 = FaultInjector(["slice_down@2"])
    inj2.set_topology(1, 8)  # flat mesh: no slice to lose
    with pytest.raises(ValueError, match="multislice topology"):
        inj2.check_step(2)


def test_slice_down_refuses_to_kill_the_last_slice():
    inj = FaultInjector(["slice_down@2:2"])
    inj.set_topology(2, 4)  # losing both slices leaves nobody
    with pytest.raises(ValueError, match="no survivors"):
        inj.check_step(2)


def test_slice_down_retopology_between_attempts():
    """An elastic retry re-registers the SHRUNK shape: the second
    whole-slice loss subtracts from the world that actually survived."""
    from theanompi_tpu.utils.faults import TopologyChanged

    inj = FaultInjector(["slice_down@2", "slice_down@5"])
    inj.set_topology(4, 2)  # 4 slices x 2 chips
    with pytest.raises(TopologyChanged) as ei:
        inj.check_step(2)
    assert ei.value.new_world == 6
    inj.set_topology(3, 2)  # the retry rebuilt a 3-slice mesh
    with pytest.raises(TopologyChanged) as ei:
        inj.check_step(5)
    assert ei.value.new_world == 4
    assert inj.world_override() == 4
