"""The ``evabyte-decode`` configuration's arithmetic and readers: the
configuration file against the published numbers, the flops file's parameter
count against the model's own leaves and a decode step's bytes against a hand
count, the three new readers on synthetic inputs (nothing without a match or
without the program's counts, a share under 100 % with them), and the new
cell's ``--tiny`` rehearsal through ``drivers/decode.py`` on the CPU: sound,
the control not, the faults not."""

import json
import os
import runpy
import time

import numpy as np
import pytest

from harness import manifest, peaks

CELL, CONFIG = "evabyte-decode-bytes24k", "evabyte-decode"
PUBLISHED = {  # the catalog row's config; the reduced key left out
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
    "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None, "init_fn": "v2",
    "init_std": 0.01275, "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
    "num_attention_heads": 32, "num_chunks": None, "num_key_value_heads": 32, "num_pred_heads": 8,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
    "vocab_size": 320, "window_size": 2048,
}
V5E = peaks.PEAKS["TPU v5e"]
# the tiny model's own readings on the CPU (4 seeds, PR 39, checks/readings_decode.py --tiny): the program's
# logit_gap 0.0021-0.0026 and token_gap 0 over 25-39 served tokens; the bfloat16 witness 0.0022-0.0026; the int8
# control 0.0187-0.0235 and 0-0.037 (it fails logit_gap on every seed, never token_gap: 320 bytes, few near-ties).
# The cell's limits were read on the chip at its own size (PERF.md section 2).
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
    assert c["num_hidden_layers"] == 4 and c["published"] == {"num_hidden_layers": 32}
    assert c["n_layers"] == c["num_hidden_layers"] and c["vocab"] == c["vocab_size"] and c["d_model"] == 4096
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["param_dtype"] == c["compute_dtype"] == c["kv_dtype"] == "bfloat16" and c["control_precision"] == "int8"
    assert "eight stages of 4 layers" in c["deployment"] and len(c["assumed"]) >= 10
    for said in ("softmax_j(mu_h . x_j)", "d^-1/4", "rotated keys are compressed", "never used compressed",
                 "no log C", "one stacked matrix", "(j, j + 64)", "embedding unscaled", "normal(0, 0.01275)",
                 "ONE byte from head 0"):
        assert any(said in a for a in c["assumed"]), said
    e = c["engine"]
    assert (e["max_seqs"], e["max_new_tokens"], e["prefill_buckets"], e["page_size"]) == (24, 2048, [24576], 2048)
    # a page is one window block; pages for 24 worst-case sequences of 24,576 + 1 + 2,048 positions
    assert e["page_size"] == c["window_size"] and e["kv_pages"] == 24 * 14 and c["seq_len"] == 24576 + 1 + 2048
    assert 14 * 2048 >= c["seq_len"] > 13 * 2048
    entry = {x["name"]: x for x in manifest.load_manifest()["configs"]}[CONFIG]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    t = c["tiny"]
    assert (t["window_size"], t["chunk_size"]) == (32, 4) and t["engine"]["page_size"] == 32
    assert t["seq_len"] == max(t["engine"]["prefill_buckets"]) + 1 + t["engine"]["max_new_tokens"]


def test_the_flops_files_parameter_count_is_the_models_own_leaves():
    import jax

    from theanompi_tpu.models.evabyte import EvaByte_Stage4

    c = _config()
    model = EvaByte_Stage4()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    assert all(a.dtype == "bfloat16" for a in leaves)
    assert sum(int(a.size) for a in leaves) == FLOPS.model_params(c) == 821_366_784  # 1.64 GB in bfloat16
    assert FLOPS.layer_params(c) == 202_391_552 == 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert FLOPS.head_params(c) * 9 == 1_310_720 + 10_485_760  # the embedding and 8 heads
    r = model.recipe
    assert (r.d_model, r.d_ff, r.n_heads, r.head_dim, r.n_layers, r.num_classes, r.n_pred_heads) == (
        4096, 11008, 32, 128, 4, 320, 8)
    assert (r.window, r.chunk, r.rope_theta, r.rms_eps, r.init_std) == (2048, 16, 100000, 1e-5, 0.01275)
    # the pools of the configuration's engine: 128 compressed rows of K~ and V~ a page a layer, a ring a slot
    spec = model.cache_spec(c["engine"]["page_size"])
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * np.dtype(spec["dtype"]).itemsize
    assert 4 * a_page // 2048 == FLOPS.cache_bytes_per_position(c) == 4096
    ring = spec["slots"]["ring"]
    assert ring["layers"] * np.prod(ring["row"]) * 2 == FLOPS.ring_bytes_per_slot(c) == 4 * 2048 * 16384


def test_what_a_query_reads_by_hand():
    c = _config()
    assert list(FLOPS.window_positions(c, [1, 2048, 2049, 23300])) == [1, 2048, 1, 23299 - 11 * 2048 + 1]
    assert list(FLOPS.compressed_rows(c, [1, 2048, 2049, 23300, 26624])) == [0, 0, 128, 11 * 128, 12 * 128]
    # a prompt of 4,096 cached positions: the first block sees itself causally, the second its causal half and
    # the first block's 128 compressed rows
    assert FLOPS.prefill_pairs(c, 4096) == 2 * (2048 * 2049 // 2) + 2048 * 128


def test_one_decode_step_of_24_sequences_at_23300_positions_by_hand():
    c = _config()
    n, ctx = 24, 24 * 23300
    weights = 4 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
    assert FLOPS.matmul_params_per_token(c) == weights == 809_500_672
    # t = 23,299 = block 11, offset 771: 771 cached ring rows and 11 x 128 compressed rows a layer
    ring, comp = 771, 11 * 128
    row = 2 * 4096 * 2
    assert FLOPS.decode_bytes(c, n, ctx) == (
        2 * (weights + 4096 * 320) + 4 * row * (n * (ring + comp) + n * (1 + 1 / 16)))
    assert FLOPS.decode_flops(c, n, ctx) == (
        2 * (weights + 4096 * 320) * n + 4 * (32 * 512 * n * (ring + 1 + comp) + 32 * 8 * 128 * n))
    seconds, bound = FLOPS.decode_least_seconds(c, V5E, n, ctx)
    assert bound == "bytes" and round(seconds * 1e3, 2) == 6.17  # 1.62 GB of weights, 3.43 of rows read
    # the kernel's own: from the program's counts (window positions with the own row, compressed rows)
    least = FLOPS.eva_decode_least_seconds(c, V5E, n, n * (ring + 1), n * comp)
    assert least == 4 * row * n * (ring + comp) / 819e9 and FLOPS.attention_flops_per_row(c) / row == 1.0
    # a prompt of 22,528 bytes prefills 22,527 positions: three whole layers and the fourth's keys and values
    assert FLOPS.prefill_matmul_params(c) == 3 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 2 * 4096 ** 2
    assert round(FLOPS.prefill_flops(c, [22527]) / 1e12, 1) == 30.7
    assert FLOPS.prefill_flops(c, [22527, 22527]) == 2 * FLOPS.prefill_flops(c, [22527])
    assert round(FLOPS.eva_prefill_least_seconds(c, V5E, 22527) * 1e3, 2) == 9.35  # three layers' flash passes
    assert FLOPS.step_flops(c) == FLOPS.decode_flops(c, 24, 24 * (26625 - 1024))


def _rctx(op_s, first=10, store=True):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context,
    # new_lens, prefills); the engine's iteration k is the probe's row k
    rows = [(n, 0, 0, 0, 0, 0, 24, 24 * 23300, (22527,) if n == 12 else (), int(n == 12)) for n in range(1, 20)]
    if store:
        from theanompi_tpu.utils.recorder import SpanStore

        spans = SpanStore("decode")  # the newest store of the name: what the readers find
        for k in range(len(rows)):
            spans.count("eva_window_positions", k, 24 * 772)
            spans.count("eva_compressed_rows", k, 24 * 1408)
    else:
        from theanompi_tpu.utils.recorder import SpanStore

        SpanStore("decode")  # a program without the counts
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.04},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 0.3}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


READERS = ("eva_decode_roofline.decode", "eva_prefill_roofline.decode", "eva_seen_share.decode")


@pytest.mark.parametrize("name", READERS)
def test_a_new_reader_reads_nothing_without_a_match_or_the_programs_counts(name):
    reader = manifest.load_module("metrics", name)
    other = {"%fusion.1 fusion": 0.5, "%eva_cache_write.1 custom-call": 0.2, "%mla_decode.3 custom-call": 0.1}
    named = {**other, "%eva_decode.3 custom-call": 0.01, "%eva_prefill.2 custom-call": 0.1}
    assert reader.read({**_rctx(named), "trace": None}) is None
    assert reader.read({**_rctx(named), "traced_first": None}) is None
    if name == "eva_seen_share.decode":
        assert reader.read(_rctx(named, store=False)) is None  # a program that keeps no counts (the parent)
        return
    assert reader.read(_rctx(other)) is None
    assert reader.read({**_rctx(named), "flops": manifest.load_module("flops", "mistral-small-4-decode")}) is None
    if name == "eva_decode_roofline.decode":
        assert reader.read(_rctx(named, store=False)) is None


def test_the_new_readers_read_a_share_under_100_with_a_match():
    c = _config()
    named = {"%fusion.1 fusion": 0.5, "%eva_decode.3 custom-call": 0.012, "%eva_decode.4 custom-call": 0.012,
             "%eva_prefill.2 custom-call": 0.05, "%eva_cache_write.1 custom-call": 0.5}
    read = {name: manifest.load_module("metrics", name).read(_rctx(named)) for name in READERS}
    # 4 traced steps of 24 sequences reading 771 ring rows and 1,408 compressed rows each against 24 ms of the kernel
    want = 100 * 4 * FLOPS.eva_decode_least_seconds(c, V5E, 24, 24 * 772, 24 * 1408) / 0.024
    assert abs(read[READERS[0]] - want) < 1e-9 and 0 < want < 100
    want = 100 * FLOPS.eva_prefill_least_seconds(c, V5E, 22527) / 0.05  # the one prefill in the traced window
    assert abs(read[READERS[1]] - want) < 1e-9 and 0 < want < 100
    assert abs(read[READERS[2]] - 100 * (772 + 1408) / 23300) < 1e-9  # about a tenth of the context


def test_the_prefills_share_of_the_peak_by_hand():
    reader = manifest.load_module("metrics", "prefill_mfu.decode")
    ctx = {**_rctx({"%fusion.1 fusion": 0.5}), "chips": 1}
    # one prefill of 22,527 cached positions in the traced window, 0.3 s of the prefill program
    want = 100 * FLOPS.prefill_flops(_config(), [22527]) / 0.3 / 197e12
    assert abs(reader.read(ctx) - want) < 1e-9 and 40 < want < 60


def test_the_new_cell_reports_what_30_s_can_hold_steady_and_the_new_readers_list_it_alone():
    # ttft_p50_ms alone beside setup_s (PERF.md section 2: tpot_p50_ms and decode_tokens_per_s spread past half
    # their bounds over the builder's runs); the readers of the decode step stay as files (EVA.md)
    man = manifest.load_manifest()
    assert {m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)} == {"ttft_p50_ms", "setup_s"}
    assert {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)} == {
        "compile_s", "prefill_share.decode", "prefill_mfu.decode", "eva_prefill_roofline.decode"}
    # found by name, not by place: a later PR appends after these entries (PERF.md section 7 says why)
    new = {m["name"]: m for m in man["per_layer"]}["eva_prefill_roofline.decode"]
    assert new["workloads"] == [CELL] and new["moves"] == "ttft_p50_ms"
    assert new["layer"] == "kernels" and new["source"] == "device_trace"
    names = [m["name"] for m in man["per_layer"]]
    assert names.index("eva_prefill_roofline.decode") > names.index("sparse_prefill_roofline.decode")  # appended
    assert CELL in {w["name"] for w in man["workloads"]} and CONFIG in {c["name"] for c in man["configs"]}
    _, cell, workload, _ = manifest.resolve(CELL)
    data = workload["data"]
    assert (data["clients"], data["prompt_len"], data["new_tokens"]) == (
        24, {"law": "uniform", "lo": 20481, "hi": 24576}, {"law": "uniform", "lo": 1024, "hi": 2048})
    assert cell["chips"] == 1 and workload["driver"] == "decode" and data["kind"] == "closed_loop_prompts"
    assert len(cell["why"]) <= 200 and cell["why"] == workload["why"]


_clock = {}


def _measure(seed, fault=None, plant=None):
    man, entry, workload, config = manifest.resolve(CELL)
    driver = manifest.load_module("drivers", "decode")
    if plant is not None:
        driver.plant_fault = plant
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
    # contexts on both sides of the first window boundary (32), and past the third
    lengths = [len(h) for h, _ in m["samples"]]
    assert min(lengths) >= 60 and max(lengths) > 96
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


@pytest.mark.parametrize("fault", ["state_unchanged", "prefill_unchanged"])
def test_a_program_that_leaves_ring_and_rows_as_it_found_them_reads_not_correct(fault):
    # experiments/mla_fault_probe.py plants the fault with a copy of every pool (compressed pages and ring)
    # taken before the call, so that it survives donation and reaches the run's own comparison
    probe = runpy.run_path(os.path.join(manifest.ROOT, "experiments", "mla_fault_probe.py"))
    driver, m = _measure(12, fault=fault, plant=probe["plant"])
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks) and checks["logit_gap"][0] > TINY_LIMITS["logit_gap"], checks
    assert m["bad"] == 0 and m["pages_lost"] == 0 and m["compiles_in_window"] == 0
