import os

import pytest

from harness import manifest, trace

SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 4 offset_ps: 9100000 duration_ps: 100000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 9000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_single_step(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%jvp__.3 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(s32[1,1]{1,0:T(1,128)} %c)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_reshape(9)" } }
}
"""


def _reduce_text(text, tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce(str(path))


def test_known_busy_idle_and_op_times(tmp_path):
    r = _reduce_text(SYNTHETIC, tmp_path)
    # whole steps only: from the first run of the step program to the last one's start
    assert r["step_module"] == "jit_single_step(123)" and r["steps"] == 2
    assert r["window_s"] == pytest.approx(20e-6)
    # step 1: ops overlap on [0, 8) us; step 2: [10, 14) and [15, 19) us
    assert r["busy_s"] == pytest.approx(16e-6)
    assert r["op_s"] == {"%fusion.1 fusion": pytest.approx(8e-6), "%jvp__.3 custom-call": pytest.approx(9e-6)}
    gaps = sorted(r["gaps"])
    assert [g[0] for g in gaps].count("inside the step program") == 1
    assert sum(d for _, d in gaps) == pytest.approx(4e-6)
    b = trace.breakdown(r)
    assert b["device_ops"][0] == ["%jvp__.3 custom-call", pytest.approx(9e-6)]
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_a_trace_without_a_repeating_step_reduces_to_nothing(tmp_path):
    assert _reduce_text('planes { id: 1 name: "/host:CPU" }', tmp_path) is None


def test_recorded_alexnet_steps(tmp_path):
    """Three steps cut from a traced run of ``alexnet-bsp1-synthetic`` on the v5e
    (PR 25) with ``fixtures/cut_trace.py``; the expected numbers were worked
    out from the fixture's events by a sweep over a sorted list, apart from
    the reduction."""
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", "alexnet_3steps.xspace.txt")) as f:
        text = f.read()
    r = _reduce_text(text, tmp_path)
    assert r["steps"] == 3 and r["step_module"].startswith("jit_single_step(")
    assert r["window_s"] == pytest.approx(EXPECTED["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    top = max(r["op_s"], key=r["op_s"].get)
    assert top == EXPECTED["top_op"] and r["op_s"][top] == pytest.approx(EXPECTED["top_op_s"], rel=1e-9)


EXPECTED = {"window_s": 0.176246354, "busy_s": 0.157033012,
            "top_op": "%fusion.278 fusion", "top_op_s": 0.018568856}
