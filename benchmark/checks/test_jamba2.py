"""The ``jamba2-3b-decode`` configuration's arithmetic and reader: the
configuration file against the published numbers, the flops file's parameter
count against the model's own leaves, a prefill's operations and a decode
step's bytes against a hand count, the new reader on synthetic inputs
(nothing without a match or without the count, a share under 100 % with
them), the manifest's entries found by name, and the new cell's ``--tiny``
rehearsal through ``drivers/decode.py`` on the CPU: sound, the control not,
the faults not."""

import json
import os
import time

import numpy as np
import pytest

from harness import manifest, peaks

CELL, CONFIG = "jamba2-3b-decode-doc32k", "jamba2-3b-decode"
PUBLISHED = {  # the catalog row's config; the reduced key left out
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
}
V5E = peaks.PEAKS["TPU v5e"]
# the tiny model's own readings on the CPU (the rehearsal below): the program's logit_gap 0.0022 and
# token_gap 0; the int8 control fails logit_gap. The cell's limits were read on the chip at its own size (PERF.md
# section 2).
TINY_LIMITS = {"token_gap": 0.2, "logit_gap": 0.006, "init_gap": 0}


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


FLOPS = manifest.load_module("flops", CONFIG)


def test_the_file_holds_every_published_number_and_states_the_cut():
    c = _config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers"] and set(c["reduced_why"]) == set(c["reduced"])
    assert c["num_hidden_layers"] == 14 and c["published"] == {"num_hidden_layers": 28}
    assert c["n_layers"] == c["num_hidden_layers"] and c["vocab"] == c["vocab_size"] and c["d_model"] == 2560
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["param_dtype"] == c["compute_dtype"] == c["kv_dtype"] == "bfloat16" and c["control_precision"] == "int8"
    assert "two stages of 14 layers" in c["deployment"] and len(c["assumed"]) >= 8
    for said in ("i % 14 == 7", "num_experts is 1", "no positional encoding", "dt_layernorm",
                 "log(1 .. 16)", "normal(0, 0.02)", "last 3 PRE-activation rows", "float32"):
        assert any(said in a for a in c["assumed"]), said
    e = c["engine"]
    assert (e["max_seqs"], e["max_new_tokens"], e["prefill_buckets"], e["page_size"]) == (16, 512, [32768], 256)
    # pages for 16 worst-case sequences of 32,768 + 1 + 512 positions
    assert c["seq_len"] == 32768 + 1 + 512 and e["kv_pages"] == 16 * -(-c["seq_len"] // 256) == 16 * 131
    entry = {x["name"]: x for x in manifest.load_manifest()["configs"]}[CONFIG]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    t = c["tiny"]
    assert t["seq_len"] == max(t["engine"]["prefill_buckets"]) + 1 + t["engine"]["max_new_tokens"]


def test_the_flops_files_parameter_count_is_the_models_own_leaves():
    import jax

    from theanompi_tpu.models.jamba import Jamba2_3B_Stage2

    c = _config()
    model = Jamba2_3B_Stage2()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    assert sum(int(a.size) for a in leaves) == FLOPS.model_params(c) == 1_598_556_096  # 3.20 GB in bfloat16
    assert FLOPS.kinds(c) == list(model.kinds) and FLOPS.n_mamba(c) == 13 and FLOPS.n_attention(c) == 1
    # a Mamba layer: in_proj, conv taps and bias, x_proj, dt_proj and its bias, A_log, D, out_proj, 3 inner gains
    mixer = 2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560 + 192
    assert FLOPS.mamba_mixer_params(c) == mixer == 41_241_792
    assert FLOPS.layer_params(c, "mamba") == 104_161_472 and FLOPS.layer_params(c, "attention") == 76_682_240
    assert FLOPS.head_params(c) == 167_772_160  # the tied embedding, counted once
    # the pools of the configuration's engine: 512 B a position, 358,400 B a slot and Mamba layer
    spec = model.cache_spec(c["engine"]["page_size"])
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * np.dtype(spec["dtype"]).itemsize
    assert a_page // 256 == FLOPS.kv_bytes_per_position(c) == 512
    held = sum(a["layers"] * np.prod(a["row"]) * np.dtype(a["dtype"]).itemsize for a in spec["slots"].values())
    assert held == FLOPS.state_bytes_per_slot(c) == 13 * 358_400


def test_a_prefill_and_a_decode_step_by_hand():
    c = _config()
    matmuls = 13 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) + 2 * 2560 * 2560 + 2 * 2560 * 128
    matmuls += 14 * 3 * 2560 * 8192
    assert FLOPS.matmul_params_per_token(c) == matmuls == 1_429_176_320
    # the last layer (Mamba) keeps its window and state: its SwiGLU, out_proj and the z half of in_proj are dead
    assert FLOPS.prefill_matmul_params(c) == matmuls - 3 * 2560 * 8192 - 2 * 2560 * 5120
    n = 29000
    assert FLOPS.prefill_flops(c, [n]) == 2 * FLOPS.prefill_matmul_params(c) * n + 20 * 4 * 128 * n * (n + 1) // 2
    assert round(FLOPS.prefill_flops(c, [32767]) / 1e12, 1) == 93.3
    assert FLOPS.prefill_flops(c, [n, n]) == 2 * FLOPS.prefill_flops(c, [n])
    # 16 slots at 29,000 positions: 3.19 GB of weights, 0.24 GB of K and V, 0.15 GB of state read and written
    ctx = 16 * 29001
    assert FLOPS.decode_bytes(c, 16, ctx) == 2 * (matmuls + 167_772_160) + 512 * ctx + 2 * 16 * 13 * 358_400
    seconds, bound = FLOPS.decode_least_seconds(c, V5E, 16, ctx)
    assert bound == "bytes" and round(seconds * 1e3, 2) == 4.37
    # the scan: x, z, dt and y of 5,120 and B and C of 16, in bfloat16, once a row and layer
    assert FLOPS.mamba_scan_bytes_per_row(c) == 41_024
    assert FLOPS.mamba_scan_least_seconds(c, V5E, n) == 13 * n * 41_024 / 819e9
    assert FLOPS.mamba_scan_elementwise(c, n) == (13 * n * 16 * 5120, 3 * 13 * n * 16 * 5120)
    assert FLOPS.step_flops(c) == FLOPS.decode_flops(c, 16, 16 * (33281 - 256))


def _rctx(op_s, first=10):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context,
    # new_lens, prefills); the engine's iteration k is the probe's row k
    rows = [(n, 0, 0, 0, 0, 0, 16, 16 * 29000, (28999,) if n == 12 else (), int(n == 12)) for n in range(1, 20)]
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.024},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 0.9}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


READER = "mamba_scan_roofline.decode"


def test_the_new_reader_reads_nothing_without_a_match_or_the_count():
    reader = manifest.load_module("metrics", READER)
    other = {"%fusion.1 fusion": 0.5, "%flash_fwd.1 custom-call": 0.05, "%sparse_decode.3 custom-call": 0.01}
    named = {**other, "%mamba_scan.2 custom-call": 0.08}
    assert reader.read({**_rctx(named), "trace": None}) is None
    assert reader.read({**_rctx(named), "traced_first": None}) is None
    assert reader.read({**_rctx(named), "peaks": None}) is None
    assert reader.read(_rctx(other)) is None  # the parent's program, or another cell's
    assert reader.read({**_rctx(named), "flops": manifest.load_module("flops", "evabyte-decode")}) is None


def test_the_new_reader_reads_a_share_under_100_with_a_match():
    named = {"%fusion.1 fusion": 0.5, "%mamba_scan.2 custom-call": 0.04, "%mamba_scan.7 custom-call": 0.04}
    got = manifest.load_module("metrics", READER).read(_rctx(named))
    want = 100 * FLOPS.mamba_scan_least_seconds(_config(), V5E, 28999) / 0.08  # the one prefill in the window
    assert abs(got - want) < 1e-9 and 0 < want < 100


def test_the_prefills_share_of_the_peak_by_hand():
    reader = manifest.load_module("metrics", "prefill_mfu.decode")
    ctx = {**_rctx({"%fusion.1 fusion": 0.5}), "chips": 1}
    # one prefill of 28,999 cached positions in the traced window, 0.9 s of the prefill program
    want = 100 * FLOPS.prefill_flops(_config(), [28999]) / 0.9 / 197e12
    assert abs(reader.read(ctx) - want) < 1e-9 and 40 < want < 60


def test_the_new_cell_reports_what_30_s_can_hold_steady_and_the_new_reader_lists_it_alone():
    man = manifest.load_manifest()
    assert {m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)} == {"ttft_p50_ms", "setup_s"}
    assert {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)} == {
        "compile_s", "prefill_share.decode", "prefill_mfu.decode", READER}
    # found by name, not by place: a later PR appends after these entries
    new = {m["name"]: m for m in man["per_layer"]}[READER]
    assert new["workloads"] == [CELL] and new["moves"] == "ttft_p50_ms"
    assert new["layer"] == "kernels" and new["source"] == "device_trace" and new["unit"] == "%"
    names = [m["name"] for m in man["per_layer"]]
    assert names.index(READER) > names.index("eva_prefill_roofline.decode")  # appended
    assert CELL in {w["name"] for w in man["workloads"]} and CONFIG in {c["name"] for c in man["configs"]}
    _, cell, workload, _ = manifest.resolve(CELL)
    data = workload["data"]
    assert (data["clients"], data["prompt_len"], data["new_tokens"]) == (
        16, {"law": "uniform", "lo": 24577, "hi": 32768}, {"law": "uniform", "lo": 256, "hi": 512})
    assert cell["chips"] == 1 and workload["driver"] == "decode" and data["kind"] == "closed_loop_prompts"
    assert len(cell["why"]) <= 200 and cell["why"] == workload["why"]


_clock = {}


def _measure(seed, fault=None):
    man, entry, workload, config = manifest.resolve(CELL)
    driver = manifest.load_module("drivers", "decode")
    ctx = {"manifest": man, "cell": entry, "workload": {**workload, "limits": TINY_LIMITS}, "config": config,
           "seed": seed, "seconds": 0.4, "trace": False, "tiny": True, "fault": fault,
           "t_process_start": time.perf_counter(), "clock": _clock.get("clock")}
    m = driver.measure(ctx)
    _clock["clock"] = ctx["clock"]
    return driver, m


def test_tiny_rehearsal_of_the_new_cell_is_sound_and_its_control_is_not():
    driver, m = _measure(3_000_000_019)
    checks = driver.checks_of(m)
    assert driver.is_correct(checks), checks
    assert m["dtypes"] == {"compute_dtype": "bfloat16", "kv_dtype": "bfloat16", "param_dtype": "bfloat16"}
    assert m["compile_count"] == 3 + 1 and m["sent"] == len(m["finished"]) and len(m["counted"]) > 0
    control = driver.stand_in_numbers(m, m["config"]["control_precision"])
    stood = {**checks, **{k: (v, TINY_LIMITS[k]) for k, v in control.items()}}
    assert not driver.is_correct(stood), f"the lower-precision control passed every limit: {control}"
    assert control["logit_gap"] > TINY_LIMITS["logit_gap"] > checks["logit_gap"][0]


def test_altered_tokens_under_the_timed_path_read_not_correct():
    driver, m = _measure(12, fault="token_altered")
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks) and checks["token_gap"][0] > TINY_LIMITS["token_gap"], checks
    assert m["bad"] == 0


def test_a_step_that_hands_back_the_pools_it_was_given_stops_the_run():
    # the programs take the pools donated: the pools the fault hands back are gone, the next
    # step fails on them, the engine fails its requests and the driver gives no result
    with pytest.raises(SystemExit, match="the engine failed under the window"):
        _measure(12, fault="state_unchanged")
