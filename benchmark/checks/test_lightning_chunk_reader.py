"""``metrics/lightning_chunk_roofline.decode.py`` on synthetic inputs: its
least time by hand at the cell's widths, nothing (and no error) without a
matching operation, a trace, a traced window, a prefill in it or a flops file
that counts a recurrence, and a share under 100 % with a match."""

import json
import os

import pytest

from harness import manifest, peaks

CONFIG = "minicpm-sala-decode"
V5E = peaks.PEAKS["TPU v5e"]
FLOPS = manifest.load_module("flops", CONFIG)
READER = manifest.load_module("metrics", "lightning_chunk_roofline.decode")


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def _ctx(op_s, first=10, lengths=(15000,)):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context,
    # new_lens, prefills); iteration 12 admits the prompts
    rows = [(n, 0, 0, 0, 0, 0, 16, 16 * 14600, tuple(lengths) if n == 12 else (), len(lengths) * (n == 12))
            for n in range(1, 20)]
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.04},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 1.0}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


NAMED = {"%fusion.1 fusion": 0.5, "%sparse_prefill.2 custom-call": 0.05, "%lightning_chunk.6 custom-call": 0.012,
         "%lightning_chunk.7 custom-call": 0.012, "%lightning_step.9 custom-call": 0.003}


def test_the_least_time_of_a_prompt_by_hand():
    c = _config()
    n = 16383
    # six lightning layers; a layer reads q, k, v in bfloat16 and writes o in float32: 40,960 B a position,
    # and one state of 32 x 128 x 128 float32
    by_bytes = 6 * (n * 32 * 128 * (3 * 2 + 4) + 32 * 128 * 128 * 4) / 819e9
    by_flops = 6 * 4 * 32 * 128 * 128 * n / 197e12
    assert READER.least_seconds(FLOPS, c, V5E, n) == max(by_bytes, by_flops) == by_bytes
    assert round(by_bytes * 1e3, 2) == 4.93 and round(by_flops * 1e3, 2) == 1.05
    assert FLOPS.n_lightning(c) * FLOPS.recurrence_flops_per_position(c) * n == 6 * 4 * 32 * 128 * 128 * n


def test_nothing_without_a_match():
    other = {k: v for k, v in NAMED.items() if "lightning_chunk" not in k}
    assert READER.read(_ctx(other)) is None
    assert READER.read({**_ctx(NAMED), "trace": None}) is None
    assert READER.read({**_ctx(NAMED), "traced_first": None}) is None
    assert READER.read({**_ctx(NAMED), "peaks": None}) is None
    assert READER.read(_ctx(NAMED, lengths=())) is None  # no prefill in the traced window
    assert READER.read({**_ctx(NAMED), "flops": manifest.load_module("flops", "mistral-small-4-decode")}) is None


@pytest.mark.parametrize("lengths", [(15000,), (12289, 16383)])
def test_a_share_under_100_with_a_match(lengths):
    want = 100 * sum(READER.least_seconds(FLOPS, _config(), V5E, n) for n in lengths) / 0.024
    got = READER.read(_ctx(NAMED, lengths=lengths))
    assert abs(got - want) < 1e-9 and 0 < got < 100
