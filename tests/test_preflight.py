"""Memory pre-flight (tools/analyze/memory.py + tools/preflight.py,
ISSUE 12): static peak-HBM budgeting, per-leaf residency attribution,
the donation-bytes-realized audit, and the `tmpi preflight` CLI.

Mutation self-tests in the test_analyze.py style: one seeded defect
per rule — a scratch BSP step with its donate flag dropped (MEM002 +
predicted-peak growth >= the param bytes), a shrunk budget (MEM001
naming the offending leaves), a synthetic temp blowup (MEM003) — plus
the clean-matrix zero-findings gate, the committed golden inventory
for every engine x codec x fused config, and the perf-gate trajectory
hook."""

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from theanompi_tpu.tools.analyze import harness
from theanompi_tpu.tools.analyze.golden import (
    diff_payload,
    load_preflight_golden,
    preflight_golden_path,
)
from theanompi_tpu.tools.analyze.memory import (
    MemoryReport,
    XlaMemory,
    analyze_memory,
    analyze_step_memory,
    config_report,
    lowered_memory,
    memory_findings,
    memory_payload,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return sorted(f.rule for f in findings)


# --------------------------------------------------------------------------
# engine memory_model() hooks: per-leaf residency declarations
# --------------------------------------------------------------------------


def test_bsp_memory_model_replicated(devices):
    pre = harness.preflight_trace("bsp", "none", False)
    assert pre.error is None, pre.error
    mm = pre.memory
    assert mm.rule == "bsp" and mm.n_devices == 2
    # replicated: per-device == global on every leaf
    assert all(l.shard_factor == 1 for l in mm.leaves)
    assert mm.state_bytes_per_device == mm.state_bytes_global


def test_bsp_ef_residuals_are_per_device(devices):
    mm = harness.preflight_trace("bsp", "int8:ef", False).memory
    ef = [l for l in mm.leaves if l.category == "ef"]
    assert ef and all(l.shard_factor == 2 for l in ef)
    rest = [l for l in mm.leaves if l.category != "ef"]
    assert all(l.shard_factor == 1 for l in rest)


def test_zero1_opt_state_sharded(devices):
    """The ZeRO-1 memory claim IS the model: optimizer accumulators
    divide by n, params do not."""
    mm = harness.preflight_trace("zero1", "none", False).memory
    opt = [l for l in mm.leaves if l.category == "opt_state"]
    par = [l for l in mm.leaves if l.category == "params"]
    assert opt and all(l.shard_factor == 2 for l in opt)
    assert par and all(l.shard_factor == 1 for l in par)
    assert all(l.per_device_bytes * 2 >= l.global_bytes for l in opt)


def test_worker_stacked_engines_shard_the_stack(devices):
    for name in ("easgd", "gosgd"):
        mm = harness.preflight_trace(name, "none", False).memory
        workers = [l for l in mm.leaves if l.category == "workers"]
        assert workers and all(l.shard_factor == 2 for l in workers)
    # EASGD's center stays replicated on every device
    mm = harness.preflight_trace("easgd", "none", False).memory
    center = [l for l in mm.leaves if l.category.startswith("center")]
    assert center and all(l.shard_factor == 1 for l in center)


def test_nd_memory_model_follows_specs(devices):
    """ND shard factors come from each leaf's own PartitionSpec — on
    the harness dp-only mesh everything is replicated (factor 1), and
    the declared model matches the engine's spec table by path."""
    pre = harness.preflight_trace("nd", "none", False)
    assert pre.error is None, pre.error
    factors = {l.path: l.shard_factor for l in pre.memory.leaves}
    sizes = dict(zip(pre.eng.mesh.axis_names, pre.eng.mesh.devices.shape))
    from jax.sharding import PartitionSpec as P

    for path, spec in jax.tree_util.tree_flatten_with_path(
            pre.eng._state_specs, is_leaf=lambda x: isinstance(x, P))[0]:
        want = 1
        for dim in tuple(spec):
            for ax in (dim if isinstance(dim, tuple) else (dim,)):
                if ax is not None:
                    want *= sizes.get(ax, 1)
        key = jax.tree_util.keystr(path)
        if key in factors:
            assert factors[key] == want, key


# --------------------------------------------------------------------------
# XLA reconciliation + the donation audit (MEM002)
# --------------------------------------------------------------------------


def test_clean_matrix_realizes_every_donation(devices):
    """All five engines x both codecs x both fused flags: the declared
    donation is fully realized (alias == state bytes, shortfall 0) and
    no MEM finding fires — the acceptance gate for the clean tree."""
    findings = analyze_memory()
    assert findings == [], [f.as_json() for f in findings]
    for name in harness.PREFLIGHT_ENGINES:
        rep, err = config_report(name, "none", False)
        assert err is None, (name, err)
        assert rep.donation_shortfall == 0
        assert rep.xla.alias_bytes == rep.donated_expected_bytes


def test_dropped_donate_flag_trips_mem002_and_grows_peak(devices):
    """THE acceptance mutation: a scratch BSP engine copy with its
    donate flag dropped (still DECLARING donates_state) trips MEM002
    and its predicted peak grows by >= the param bytes."""
    from theanompi_tpu.parallel.bsp import make_bsp_train_step
    from theanompi_tpu.tools.analyze.harness import _mesh2, _tiny_model

    pre = harness.preflight_trace("bsp", "none", False)
    good, _ = config_report("bsp", "none", False)
    assert memory_findings(good) == []

    model = _tiny_model()
    mesh = _mesh2()
    scratch = make_bsp_train_step(model, mesh, donate=False)  # the mutation
    bad = analyze_step_memory(
        scratch, pre.step_args, pre.memory, declared_donates=True,
        engine="bsp_nodonate",
    )
    rules = _rules(memory_findings(bad))
    assert "MEM002" in rules
    param_bytes = pre.memory.params_bytes_per_device()
    growth = bad.peak_bytes - good.peak_bytes
    assert growth >= param_bytes, (growth, param_bytes)
    # and the realized alias collapsed to nothing
    assert bad.xla.alias_bytes == 0
    assert bad.donation_shortfall >= good.donated_expected_bytes


def test_budget_refusal_names_top_buffers(devices):
    """MEM001 under a shrunk budget names the largest live buffers in
    per-device bytes order."""
    rep, err = config_report("bsp", "none", False,
                             budget_bytes=1024.0,
                             budget_source="--budget-gb")
    assert err is None
    assert rep.fit is False
    findings = memory_findings(rep)
    assert "MEM001" in _rules(findings)
    msg = next(f.message for f in findings if f.rule == "MEM001")
    # the biggest state leaf is named in the refusal
    biggest = max(rep.model.leaves, key=lambda l: l.per_device_bytes)
    assert biggest.path in msg
    # and the table itself is sorted descending
    table = rep.top_buffers(10)
    assert all(table[i]["bytes"] >= table[i + 1]["bytes"]
               for i in range(len(table) - 1))


def test_zero_budget_is_a_budget_not_absence(devices):
    """--budget-gb 0 is an explicit budget (nothing fits in it), not
    'no budget' — presence is None-ness, never value truthiness (the
    same distinction the perf-gate zero-baseline satellite fixes)."""
    rep, err = config_report("bsp", "none", False, budget_bytes=0.0,
                             budget_source="--budget-gb")
    assert err is None
    assert rep.fit is False
    assert "MEM001" in _rules(memory_findings(rep))
    unbudgeted, _ = config_report("bsp", "none", False)
    assert unbudgeted.fit is None


def test_mem003_rematerialization_smell():
    """Synthetic report with a temp pool far beyond state trips
    MEM003; at the threshold boundary it does not."""
    from theanompi_tpu.utils.flops import MemoryLeaf, MemoryModel

    model = MemoryModel(rule="x", n_devices=1, leaves=[
        MemoryLeaf(path=".params['w']", dtype="float32", shape=(256,),
                   global_bytes=1024, shard_factor=1),
    ])

    def rep(temp):
        return MemoryReport(
            engine="x", codec="none", fused=False,
            xla=XlaMemory(argument_bytes=2048, output_bytes=1024,
                          temp_bytes=temp, alias_bytes=1024,
                          generated_code_bytes=0),
            model=model, declared_donates=True,
        )

    assert _rules(memory_findings(rep(temp=17 * 1024))) == ["MEM003"]
    assert memory_findings(rep(temp=15 * 1024)) == []


def test_lowered_memory_reads_alias_of_donated_jit(devices):
    f = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = lowered_memory(f, sds, sds)
    assert x.argument_bytes == 2 * 128 * 128 * 4
    assert x.alias_bytes == 128 * 128 * 4


# --------------------------------------------------------------------------
# goldens: committed inventory + drift detection (MEM101)
# --------------------------------------------------------------------------


def test_preflight_goldens_exist_for_full_matrix():
    """Acceptance: all five engines x {none, int8:ef} x {fused,
    unfused} have committed goldens carrying BOTH family blocks."""
    for name in harness.PREFLIGHT_ENGINES:
        for codec in harness.CODEC_SPECS:
            for fused in harness.FUSED_FLAGS:
                gold = load_preflight_golden(name, codec, fused)
                path = preflight_golden_path(name, codec, fused)
                assert gold is not None, f"missing golden {path}"
                assert "memory" in gold and "precision" in gold, path


def test_memory_golden_drift_is_caught(devices):
    """A drifted residency row (leaf grew, e.g. an optimizer gained a
    second accumulator) is reported with its path."""
    rep, err = config_report("bsp", "none", False)
    assert err is None
    gold = load_preflight_golden("bsp", "none", False)["memory"]
    current = memory_payload(rep)
    assert diff_payload(gold, current) == []
    tampered = json.loads(json.dumps(gold))
    tampered["leaves"][0]["per_device_bytes"] += 4096
    errs = diff_payload(tampered, current)
    assert errs and any("per_device_bytes" in e for e in errs)


# --------------------------------------------------------------------------
# the `tmpi preflight` CLI (acceptance paths) + obs/perf-gate hooks
# --------------------------------------------------------------------------


def _run_cli(args, timeout=240):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # preflight sets up its own platform
    return subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "preflight", *args],
        capture_output=True, text=True, timeout=timeout, cwd=_REPO,
        env=env,
    )


@pytest.mark.slow
def test_cli_fit_verdict_and_leaf_table(tmp_path):
    """`tmpi preflight --model mlp --engine bsp --budget-gb 16` exits 0
    with a fit verdict and the per-leaf byte table."""
    r = _run_cli(["--model", "mlp", "--engine", "bsp",
                  "--budget-gb", "16",
                  "--obs-dir", str(tmp_path / "obs")])
    assert r.returncode == 0, r.stderr
    assert "FITS" in r.stdout and "per-leaf residency" in r.stdout
    assert ".params['01_fc1']['w']" in r.stdout
    assert "tmpi preflight: OK" in r.stdout
    # obs side: schema-valid preflight record + gauges
    from theanompi_tpu.tools.check_obs_schema import check_file

    mpath = tmp_path / "obs" / "metrics.jsonl"
    assert check_file(str(mpath)) == []
    recs = [json.loads(l) for l in mpath.read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert kinds == ["preflight", "metrics"]
    assert recs[0]["fit"] is True and recs[0]["peak_bytes"] > 0
    m = recs[1]["metrics"]
    assert m["tmpi_preflight_fit"] == 1.0
    assert m["tmpi_preflight_peak_bytes"] == recs[0]["peak_bytes"]


@pytest.mark.slow
def test_cli_over_budget_refuses_naming_buffers(tmp_path):
    """`--budget-gb 0.001` exits 1 naming the top live buffers."""
    r = _run_cli(["--model", "mlp", "--engine", "bsp",
                  "--budget-gb", "0.001",
                  "--obs-dir", str(tmp_path / "obs")])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "DOES NOT FIT" in r.stdout
    assert "MEM001" in r.stdout and "largest live buffers" in r.stdout
    assert ".params['01_fc1']['w']" in r.stdout
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "metrics.jsonl").read_text().splitlines()]
    assert recs[0]["fit"] is False
    assert recs[1]["metrics"]["tmpi_preflight_fit"] == 0.0

