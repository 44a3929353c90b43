"""The three per-layer metrics that read the program's spans and kernel
names (PR 26), on the recorded fixture and on made-up rings and reductions."""

import os

import numpy as np
import pytest

from harness import manifest, spans

MS = 1e6  # ns
ORIGIN = 1_790_000_000_000_000_000  # a time.time_ns() reading: no float64 holds it exactly


class Ring:
    """What ``Recorder.span_rings[name]`` answers to."""

    def __init__(self, steps, t0_ns, dur_ns):
        self.rows = (np.asarray(steps, np.int64), np.asarray(t0_ns, np.int64), np.asarray(dur_ns, np.int64))

    def held(self):
        return self.rows


class Rec:
    def __init__(self, rings, timings=None):
        self.span_rings, self.timings = rings, timings or {}


@pytest.fixture(scope="module")
def fixture_planes(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(manifest.BENCH_DIR, "fixtures", "alexnet_3steps.xspace.txt")) as f:
        text = f.read()
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    ops, modules, origin = spans.read_planes(str(path))
    assert origin is None  # the fixture holds the device plane alone
    return ops, modules


def test_the_fixtures_step_gap(fixture_planes):
    starts, intervals = spans.step_intervals(*fixture_planes)
    assert len(starts) == 4 and len(intervals) == 3
    idle = [iv["idle_ns"] / MS for iv in intervals]
    # ISSUE 26's table: 6.70, 6.04 and 6.48 ms between the three pairs of runs
    assert idle == pytest.approx([6.70, 6.04, 6.48], abs=0.01)
    assert sum(idle) / 3 == pytest.approx(6.405, abs=0.01)
    # each gap is cut by the five small programs of the key split
    assert all(len(iv["segments"]) >= 6 for iv in intervals)


def _made_up_spans(starts, intervals, shift=0):
    """Per interval: the drain's last ms, 0.5 ms of emit, 0.1 of wait, 3 ms
    of key split and the 0.6 ms of dispatch before the program starts; the
    rest of the gap under no span. Times on the spans' clock (``shift`` on
    top of the trace's)."""
    rows = {name: [] for name in spans.DRIVER_SPANS}
    rows["dispatch"].append((1, starts[0] - 0.6 * MS, 2 * MS))
    for i, iv in enumerate(intervals):
        a, step = iv["segments"][0][0], i + 1
        rows["drain"].append((step, a - 40 * MS, 41 * MS))
        rows["emit"].append((step, a + 1 * MS, 0.5 * MS))
        rows["wait"].append((step + 1, a + 1.5 * MS, 0.1 * MS))
        rows["key_split"].append((step + 1, a + 1.7 * MS, 3 * MS))
        rows["dispatch"].append((step + 1, starts[i + 1] - 0.6 * MS, 2 * MS))
    return Rec({name: Ring([r[0] for r in v], [int(r[1]) + shift for r in v], [int(r[2]) for r in v])
                for name, v in rows.items()})


def test_made_up_spans_come_back_under_their_names(fixture_planes):
    starts, intervals = spans.step_intervals(*fixture_planes)
    rec = _made_up_spans(starts, intervals, shift=ORIGIN)
    got = spans.laid_over(starts, intervals, rec, ORIGIN)
    assert got["how"] == "the trace's own origin" and got["offset_ms"] == 0
    assert got["scatter_ms"] == pytest.approx(0, abs=1e-6)
    under = got["under_ms"]
    gap = sum(iv["idle_ns"] for iv in intervals) / 3 / MS
    # the programs start some us before their first operation, and the key
    # split's own five programs run inside its span: hence the 0.02
    assert under["drain"] == pytest.approx(1.0, abs=1e-6)
    assert under["emit"] == pytest.approx(0.5, abs=1e-6)
    assert under["wait"] == pytest.approx(0.1, abs=1e-6)
    assert under["key_split"] == pytest.approx(3.0, abs=0.02)
    assert under["dispatch"] == pytest.approx(0.6, abs=0.02)
    assert under["no span"] == pytest.approx(gap - 5.2, abs=0.03)
    assert sum(under.values()) == pytest.approx(gap, abs=1e-9)


def test_no_spans_no_attribution(fixture_planes):
    starts, intervals = spans.step_intervals(*fixture_planes)
    assert spans.laid_over(starts, intervals, Rec({}), ORIGIN) is None
    assert spans.laid_over(starts, intervals, object(), None) is None  # a program before the rings


def _dispatches(starts, lags_ms, constant):
    """Twelve dispatch openings: the traced programs' own (``starts`` less
    each lag) in the middle of four earlier and four later steps, at uneven
    periods, all moved by ``constant``."""
    own = [s - lag * MS for s, lag in zip(starts, lags_ms)]
    before = [own[0] - p * MS for p in (240.3, 181.1, 119.6, 59.2)]
    after = [own[-1] + p * MS for p in (58.7, 118.9, 177.2, 236.4)]
    return np.array(before + own + after) + constant


def test_a_constant_clock_offset_is_recovered(fixture_planes):
    starts, _ = spans.step_intervals(*fixture_planes)
    lags = [0.61, 0.60, 0.64, 0.62]
    # one clock: no offset, the lags as they are
    offset, scatter, how = spans.clock_offset(starts, _dispatches(starts, lags, 0), True)
    assert offset == 0 and how == "the trace's own origin" and scatter == pytest.approx(0.0325 * MS, abs=1)
    # the spans' clock 5 s ahead: estimated, the smallest lag taken as nothing
    for same_origin in (True, False):
        offset, scatter, how = spans.clock_offset(starts, _dispatches(starts, lags, 5000 * MS), same_origin)
        assert how.startswith("estimated") and offset == pytest.approx((5000 - 0.60) * MS, abs=1)
        assert scatter < 0.05 * MS


def test_a_program_that_seems_to_start_before_its_dispatch_moves_the_trace(fixture_planes):
    """The profiler sets the device's clock against the host's anew in every
    session: on the chip one trace had its programs start up to 0.07 ms, another
    0.66 ms, before their dispatch spans opened (PERF.md section 6, PR 26)."""
    starts, _ = spans.step_intervals(*fixture_planes)
    lags = [0.15, -0.07, 0.21, 0.04]
    offset, scatter, how = spans.clock_offset(starts, _dispatches(starts, lags, 0), True)
    assert offset == pytest.approx(0.07 * MS, abs=1) and how.startswith("the trace's own origin, moved")
    assert scatter == pytest.approx(0.2375 * MS, abs=1)
    # by more than the slack: not this origin's clock, so estimated like any other
    lags = [0.15, -1.5, 0.21, 0.04]
    assert spans.clock_offset(starts, _dispatches(starts, lags, 0), True) is None  # and it scatters


def test_a_scattered_clock_gives_nothing(fixture_planes):
    starts, intervals = spans.step_intervals(*fixture_planes)
    lags = [0.1, 3.0, 0.5, 2.0]
    assert spans.clock_offset(starts, _dispatches(starts, lags, 5000 * MS), False) is None
    assert spans.clock_offset(starts, _dispatches(starts, lags, 0), True) is None
    assert spans.clock_offset(starts, _dispatches(starts, lags, 0)[:3], True) is None  # fewer spans than programs


def _metric(name):
    return manifest.load_module("metrics", name)


def test_flash_attn_roofline_on_a_made_up_reduction():
    flops = manifest.load_module("flops", "lm136m")
    config = manifest.load_json("configs", "lm136m.json")
    needed = flops.attention_flops(config)
    assert needed == pytest.approx(0.464e12, rel=0.01)  # PERF.md: 0.46 TF a step
    ctx = {"trace": {"steps": 29, "op_s": {
        "%jvp_flash_fwd_.1 custom-call": 29 * 0.0059, "%transpose_jvp_flash_bwd_dq__.1 custom-call": 29 * 0.0060,
        "%transpose_jvp_flash_bwd_dkv__.1 custom-call": 29 * 0.0079, "%fusion.7 fusion": 29 * 0.0222}},
        "peaks": {"bf16_flops": 197e12}, "flops": flops, "config": config}
    read = _metric("flash_attn_roofline.train").read
    assert read(ctx) == pytest.approx(100 * needed / 0.0198 / 197e12, rel=1e-9)
    assert 11 < read(ctx) < 13
    # the parent's names: nothing, never 0
    ctx["trace"]["op_s"] = {"%jvp__.3 custom-call": 0.17, "%transpose_jvp___.4 custom-call": 0.4}
    assert read(ctx) is None
    assert read({**ctx, "trace": None}) is None
    assert read({**ctx, "flops": manifest.load_module("flops", "alexnet")}) is None


def test_driver_self_ms_on_a_made_up_ring():
    read = _metric("driver_self_ms.train").read
    steps = np.arange(1, 41)
    waits = [5e-5 + 1e-6 * i for i in range(40)]
    rings = {"wait": Ring(steps, ORIGIN + steps * 60 * MS, [round(w * 1e9) for w in waits]),
             "drain": Ring(steps, ORIGIN + steps * 60 * MS + 8 * MS, np.full(40, 52 * MS))}
    # the window: positions 10..29 of timings[...], so steps 11..30
    ctx = {"recorder": Rec(rings, {"wait": waits}), "first_step": 10, "last_step": 29,
           "steps": 20, "seconds": 20 * 0.0592}
    assert read(ctx) == pytest.approx(59.2 - 52.0, abs=1e-9)
    # a run that resumed: the ring's waits are not the window's
    assert read({**ctx, "recorder": Rec(rings, {"wait": waits[1:] + [1.0]})}) is None
    # the ring has overwritten part of the window
    short = {"wait": rings["wait"], "drain": Ring(steps[15:], steps[15:], np.full(25, 52 * MS))}
    assert read({**ctx, "recorder": Rec(short, {"wait": waits})}) is None
    # a program without spans
    assert read({**ctx, "recorder": Rec({}, {"wait": waits})}) is None
    assert read({**ctx, "recorder": object()}) is None


def test_step_gap_reads_nothing_without_a_trace(capsys):
    read = _metric("step_gap_ms.train").read
    assert read({"trace": None, "cell": {"name": "no-such-cell"}, "recorder": Rec({})}) is None
    assert capsys.readouterr().out == ""
