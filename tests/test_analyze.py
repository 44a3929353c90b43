"""SPMD safety analyzer (tools/analyze/, ISSUE 7): mutation
self-tests — one seeded defect per rule family, each caught by its
rule ID — plus the clean-tree zero-findings gate and the golden
signature inventory.

The defects seeded here are the exact classes the analyzer exists for:
a collective that only one side of a rank-divergent branch posts (the
deadlock class), a traffic model that drifts from the traced program,
an engine claiming donation it doesn't perform, and host code deciding
resume agreement from an unsorted directory listing (the PR 4 rollback
bug class).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from theanompi_tpu.tools.analyze import harness
from theanompi_tpu.tools.analyze.astlint import (
    donation_findings,
    rank_divergence_findings,
)
from theanompi_tpu.tools.analyze.golden import (
    compare_golden,
    golden_path,
    load_golden,
    signature_payload,
)
from theanompi_tpu.tools.analyze.rules import (
    analyze_engines,
    axis_findings,
    donation_findings_for,
    traffic_findings,
)
from theanompi_tpu.tools.analyze.signature import (
    donated_flags,
    extract_signature,
)


@pytest.fixture(scope="module")
def mesh2(devices):
    return Mesh(np.array(devices[:2]), ("data",))


@pytest.fixture(scope="module")
def mesh22(devices):
    return Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "aux"))


# --------------------------------------------------------------------------
# rule family 1: collective safety (SPMD001 / SPMD002)
# --------------------------------------------------------------------------


def test_mismatched_psum_axis_in_cond_branch_caught(mesh22):
    """Seeded defect: a cond whose predicate is derived from SHARDED
    data (each rank can see a different value) with a psum on one
    branch only — and over a different axis than the other branch's
    collective. The uniformity analysis must flag it as SPMD002's
    cond-mismatch (the deadlock class)."""

    def inner(flag, x):
        return lax.cond(
            flag[0] > 0,
            lambda: lax.psum(x, "data"),
            lambda: lax.psum(x, "aux") * 0.5,
        )

    def f(flag, x):
        return jax.shard_map(
            inner, mesh=mesh22, in_specs=(P("data"), P()), out_specs=P(),
            check_vma=False,
        )(flag, x)

    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.float32),
    )
    sig, _ = extract_signature(jaxpr)
    kinds = [i.kind for i in sig.issues]
    assert "cond-mismatch" in kinds, kinds


def test_uniform_predicate_cond_is_not_flagged(mesh22):
    """Control: the same asymmetric cond under a REPLICATED predicate
    is safe (every rank takes the same branch) and must not fire."""

    def inner(flag, x):
        return lax.cond(
            flag[0] > 0,
            lambda: lax.psum(x, "data"),
            lambda: x,
        )

    def f(flag, x):
        return jax.shard_map(
            inner, mesh=mesh22, in_specs=(P(), P()), out_specs=P(),
            check_vma=False,
        )(flag, x)

    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((8,), jnp.float32),
    )
    sig, _ = extract_signature(jaxpr)
    assert sig.issues == []
    assert [c.prim for c in sig.collectives] == ["psum"]


def test_varying_trip_count_while_with_collective_caught(mesh2):
    """A while-loop whose trip count each rank decides from its own
    shard, with a psum in the body: ranks disagree on iteration count
    and deadlock mid-loop (SPMD002 while-collective)."""

    def inner(x):
        def cond(c):
            i, acc = c
            return i < jnp.sum(x).astype(jnp.int32)

        def body(c):
            i, acc = c
            return i + 1, acc + lax.psum(acc, "data")

        return lax.while_loop(cond, body, (0, x))[1]

    def f(x):
        return jax.shard_map(inner, mesh=mesh2, in_specs=(P("data"),),
                             out_specs=P("data"), check_vma=False)(x)

    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8,), jnp.float32))
    sig, _ = extract_signature(jaxpr)
    assert any(i.kind == "while-collective" for i in sig.issues)


def test_unbound_axis_becomes_spmd001():
    """A collective naming an axis no mesh binds fails at trace time;
    the harness converts that into an SPMD001 finding instead of
    crashing the lint."""
    trace = harness.EngineTrace(engine="bsp", codec="none",
                                error="NameError: unbound axis 'ghost'",
                                module_file="parallel/bsp.py")
    found = axis_findings(trace)
    assert [f.rule for f in found] == ["SPMD001"]
    assert "ghost" in found[0].message


# --------------------------------------------------------------------------
# rule family 2: traffic-model cross-check (SPMD101)
# --------------------------------------------------------------------------


def test_traffic_model_byte_drift_caught():
    """Seeded defect: an engine whose declared traffic_model() reports
    2x the wire the traced program moves — the gauge-drift class."""
    import dataclasses

    trace = harness.trace_engine("bsp", "none")
    assert trace.error is None
    drifted = dataclasses.replace(
        trace.traffic,
        raw_bytes_per_step=trace.traffic.raw_bytes_per_step * 2.0,
    )
    found = traffic_findings(trace, declared=drifted)
    assert [f.rule for f in found] == ["SPMD101"]
    # ... and the honest model passes
    assert traffic_findings(trace) == []


# --------------------------------------------------------------------------
# rule family 3: donation audit (SPMD201)
# --------------------------------------------------------------------------


def test_missing_donation_caught(mesh2):
    """Seeded defect: a BSP step built with donate=False behind an
    engine that still declares donates_state=True."""
    from theanompi_tpu.parallel.bsp import make_bsp_train_step

    model = harness._tiny_model()
    step = make_bsp_train_step(model, mesh2, donate=False)
    from theanompi_tpu.train import init_train_state

    rng = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda k: init_train_state(model, k), rng)
    n_state = len(jax.tree_util.tree_leaves(state))
    jaxpr = jax.make_jaxpr(step)(
        state, jax.ShapeDtypeStruct((16, 8, 8, 3), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.int32), rng,
    )
    sig, axis_sizes = extract_signature(jaxpr)
    part = harness.TracePart(
        name="step", signature=sig, axis_sizes=axis_sizes,
        donated=donated_flags(jaxpr, n_state),
    )
    bad = harness.EngineTrace(engine="bsp", codec="none", parts=[part],
                              declared_donates=True,
                              module_file="parallel/bsp.py")
    found = donation_findings_for(bad)
    assert [f.rule for f in found] == ["SPMD201"]


def test_real_engines_do_donate():
    for name in harness.ENGINE_NAMES:
        trace = harness.trace_engine(name, "none")
        assert trace.error is None, trace.error
        assert donation_findings_for(trace) == [], name


# --------------------------------------------------------------------------
# rule family 4: rank-divergence lint (SPMD301/302) + donation alias
# (SPMD202)
# --------------------------------------------------------------------------

_RESUME_AGREEMENT_BAD = '''
import os
def resolve_resume(d, engine, state):
    names = os.listdir(d)          # unsorted: NFS order differs per host
    newest = names[-1]
    if newest:
        steps = multihost_utils.process_allgather(parse_step(newest))
    return steps
'''


def test_unsorted_listdir_feeding_resume_agreement_caught():
    found = rank_divergence_findings("snippet.py", _RESUME_AGREEMENT_BAD)
    rules = {f.rule for f in found}
    assert "SPMD302" in rules  # the unsorted listing itself
    assert "SPMD301" in rules  # its value gating the agreement collective
    spmd301 = [f for f in found if f.rule == "SPMD301"][0]
    assert "process_allgather" in spmd301.message


def test_sorted_listing_and_uniform_gate_pass():
    clean = '''
import os
def resolve_resume(d, state):
    names = sorted(os.listdir(d))
    if state.step > 0:
        steps = multihost_utils.process_allgather(state.step)
    return names
'''
    assert rank_divergence_findings("snippet.py", clean) == []


def test_unsorted_device_probe_gating_reshard_caught():
    """Elastic PR mutation: jax.devices() enumeration order (and, mid-
    failure, membership) is rank-divergent; deriving the reshard gate
    from the raw probe means controllers can compute DIFFERENT transfer
    plans around the gang-scheduled load — SPMD301, same class as a
    gated collective."""
    bad = '''
import jax
def elastic_resume(path, template, saved_world):
    devs = jax.devices()
    if len(devs) != saved_world:
        state = load_resharded(path, template, devs)
    return state
'''
    found = rank_divergence_findings("snippet.py", bad)
    assert [f.rule for f in found] == ["SPMD301"]
    assert "load_resharded" in found[0].message
    assert "jax.devices()" in found[0].message


def test_sorted_device_probe_passes():
    """The clean form — enumeration pinned by sorted(...) BEFORE the
    plan derives from it (supervisor._probe_world's shape)."""
    clean = '''
import jax
def elastic_resume(path, template, saved_world):
    devs = sorted(jax.devices(), key=lambda d: d.id)
    if len(devs) != saved_world:
        state = load_resharded(path, template, devs)
    return state
'''
    assert rank_divergence_findings("snippet.py", clean) == []


def test_sorted_clock_read_still_tainted():
    """sorted(...) launders ORDER, not VALUE: the escape applies only
    to listing/device-enumeration sources. A clock read is just as
    rank-divergent after a sort, so wrapping it must NOT silence
    SPMD301 on the gated reshard."""
    bad = '''
import time
def elastic_resume(path, template, mesh, deadline):
    t = sorted([time.time()])[0]
    if t < deadline:
        state = load_resharded(path, template, mesh)
    return state
'''
    found = rank_divergence_findings("snippet.py", bad)
    assert [f.rule for f in found] == ["SPMD301"]
    assert "time.time()" in found[0].message


def test_use_after_donation_alias_caught():
    bad = '''
import numpy as np
def loop(engine, state, batch, rng):
    snap = np.asarray(state.params)   # zero-copy view of donated buffers
    state, metrics = engine.train_step(state, batch, batch, rng)
    return snap
'''
    found = donation_findings("snippet.py", bad)
    assert [f.rule for f in found] == ["SPMD202"]
    # np.array (a copy) is the sanctioned snapshot and must pass
    ok = bad.replace("np.asarray", "np.array")
    assert donation_findings("snippet.py", ok) == []


def test_scanned_tree_sources_are_clean():
    from theanompi_tpu.tools.analyze.astlint import run_ast_lints

    assert run_ast_lints() == []


# --------------------------------------------------------------------------
# goldens + suppressions + the clean-tree gate
# --------------------------------------------------------------------------


def test_golden_signatures_exist_for_every_engine_and_codec():
    import os

    for name in harness.ENGINE_NAMES:
        for codec in harness.CODEC_SPECS:
            assert os.path.exists(golden_path(name, codec)), (name, codec)


def test_golden_drift_is_caught():
    trace = harness.trace_engine("gosgd", "none")
    gold = load_golden("gosgd", "none")
    assert compare_golden(trace, gold) == []
    # tamper: drop the gossip ppermute from the snapshot
    tampered = signature_payload(trace)
    tampered["parts"]["step"] = [
        c for c in tampered["parts"]["step"] if c["prim"] != "ppermute"
    ]
    assert compare_golden(trace, tampered) != []


def test_bucketed_golden_pins_per_bucket_psums():
    """The bsp_bucketed config (--allreduce-buckets) traces one psum
    PER BUCKET instead of the single gradient pmean — its own golden
    pins that schedule (ISSUE 11)."""
    trace = harness.trace_engine("bsp_bucketed", "none")
    assert trace.error is None, trace.error
    gold = load_golden("bsp_bucketed", "none")
    assert compare_golden(trace, gold) == []
    step = signature_payload(trace)["parts"]["step"]
    grad_psums = [c for c in step if c["prim"] == "psum" and c["shape"]]
    plain = signature_payload(harness.trace_engine("bsp", "none"))
    plain_grad = [c for c in plain["parts"]["step"]
                  if c["prim"] == "psum" and c["shape"]]
    # same per-leaf collectives, DIFFERENT order: the bucketed trace
    # posts them in gradient-PRODUCTION order (output-layer leaves
    # first — the overlap schedule), where plain bsp's single
    # post-backward pmean posts them in tree order. That ordering is
    # exactly what the golden pins.
    key = lambda c: (c["prim"], tuple(c["shape"]))  # noqa: E731
    assert sorted(map(key, grad_psums)) == sorted(map(key, plain_grad))
    assert [key(c) for c in grad_psums] != [key(c) for c in plain_grad]
    # the output layer's 10-class leaves lead the bucketed schedule
    assert {key(c) for c in grad_psums[:2]} == {("psum", (10,)),
                                                ("psum", (32, 10))}
    # every bucket reduces over the SAME axis — the invariant the
    # mutation below violates
    assert {tuple(c["axes"]) for c in grad_psums} == {("data",)}


def test_bucketed_fused_combined_config_has_its_own_goldens():
    """ISSUE 12 satellite: the two PR-11 knobs COMBINED
    (--allreduce-buckets + --fused-update) are pinned together as the
    `bsp_bucketed_fused` config — per-bucket psum schedule preserved
    under the fused epilogue, with its own committed goldens, not only
    the knobs-in-isolation ones."""
    import os

    for codec in harness.CODEC_SPECS:
        assert os.path.exists(golden_path("bsp_bucketed_fused", codec))
    trace = harness.trace_engine("bsp_bucketed_fused", "none")
    assert trace.error is None, trace.error
    gold = load_golden("bsp_bucketed_fused", "none")
    assert compare_golden(trace, gold) == []
    # the fused epilogue must NOT change the bucketed wire schedule:
    # same ordered collective keys as bsp_bucketed
    fused_step = signature_payload(trace)["parts"]["step"]
    plain_step = signature_payload(
        harness.trace_engine("bsp_bucketed", "none"))["parts"]["step"]
    key = lambda c: (c["prim"], tuple(c["shape"]), tuple(c["axes"]))  # noqa: E731
    assert [key(c) for c in fused_step] == [key(c) for c in plain_step]
    # and the combined config rides the default lint matrix
    assert "bsp_bucketed_fused" in harness.ENGINE_NAMES


def test_bucket_psum_axis_drift_caught(mesh22):
    """Mutation self-test (ISSUE 11 satellite): a bucketed sync where
    ONE bucket's psum axis drifts from its siblings. The traced
    schedule shows the drift, and the golden comparison (rule SPMD003)
    reports it — a reviewer cannot merge a bucket that reduces over the
    wrong mesh axis without regenerating (and re-reviewing) the
    snapshot."""
    from theanompi_tpu.parallel.strategies import assign_buckets

    params = {
        "a": jax.ShapeDtypeStruct((64,), jnp.float32),
        "b": jax.ShapeDtypeStruct((8,), jnp.float32),
        "c": jax.ShapeDtypeStruct((4,), jnp.float32),
    }

    def make_mapped(drift: bool):
        def wrap(p):
            leaves, treedef = jax.tree_util.tree_flatten(p)
            out = list(leaves)
            # ~64 B buckets: every leaf its own bucket
            for k, idx in enumerate(assign_buckets(leaves, 64)):
                axis = "aux" if (drift and k == 0) else "data"

                @jax.custom_vjp
                def tag(*ls):
                    return ls

                def fwd(*ls):
                    return ls, None

                def bwd(_, cts, axis=axis):
                    return tuple(lax.pmean(c, axis) for c in cts)

                tag.defvjp(fwd, bwd)
                tagged = tag(*[leaves[i] for i in idx])
                for j, i in enumerate(idx):
                    out[i] = tagged[j]
            return jax.tree_util.tree_unflatten(treedef, out)

        def step(p, x):
            def loss(p):
                wp = wrap(p)
                return sum(jnp.sum(l) for l in
                           jax.tree_util.tree_leaves(wp)) * jnp.sum(x)

            return jax.grad(loss)(p)

        return jax.shard_map(
            step, mesh=mesh22, in_specs=(P(), P("data")), out_specs=P(),
            check_vma=False,
        )

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    sig_ok, _ = extract_signature(jax.make_jaxpr(make_mapped(False))(params, x))
    sig_bad, _ = extract_signature(jax.make_jaxpr(make_mapped(True))(params, x))
    assert {tuple(c.axes) for c in sig_ok.collectives} == {("data",)}
    assert ("aux",) in {tuple(c.axes) for c in sig_bad.collectives}

    # the drifted schedule against the reviewed snapshot -> SPMD003
    ok_trace = harness.EngineTrace(engine="bsp_bucketed", codec="none")
    ok_trace.parts.append(harness.TracePart(
        name="step", signature=sig_ok, axis_sizes={"data": 2, "aux": 2}))
    bad_trace = harness.EngineTrace(engine="bsp_bucketed", codec="none")
    bad_trace.parts.append(harness.TracePart(
        name="step", signature=sig_bad, axis_sizes={"data": 2, "aux": 2}))
    golden = signature_payload(ok_trace)
    assert compare_golden(ok_trace, golden) == []
    errs = compare_golden(bad_trace, golden)
    assert errs and any("aux" in e for e in errs)


def test_spmd_exempt_needs_a_reason(tmp_path):
    from theanompi_tpu.tools.lint import _exemption_reason

    f = tmp_path / "x.py"
    f.write_text(
        "a = 1  # spmd_exempt: ordering provably irrelevant here\n"
        "b = 2  # spmd_exempt:\n"
        "c = 3\n"
    )
    assert _exemption_reason(str(f), 1) == "ordering provably irrelevant here"
    assert _exemption_reason(str(f), 2) is None  # bare marker: no waiver
    assert _exemption_reason(str(f), 3) is None


def test_clean_tree_has_zero_findings():
    """The acceptance gate: the committed tree analyzes clean — every
    engine's signature matches its golden, traffic models agree with
    the traces, donation claims hold, and the host sources carry no
    unexempted divergence."""
    assert analyze_engines(update_golden=False) == []
