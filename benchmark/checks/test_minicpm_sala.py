"""The ``minicpm-sala-decode`` configuration's arithmetic and readers: the
configuration file against the published numbers, the flops file's parameter
count against the model's own leaves and a decode step's bytes against a hand
count, the three kernel readers on synthetic inputs (nothing without a match,
a share under 100 % with one), and the new cell's ``--tiny`` rehearsal through
``drivers/decode.py`` on the CPU: sound, the control not, the faults not."""

import json
import os
import runpy
import time

import numpy as np
import pytest

from harness import manifest, peaks

CELL, CONFIG = "minicpm-sala-decode-doc16k", "minicpm-sala-decode"
PERIOD = ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"]
PUBLISHED = {  # the catalog row's config; the reduced keys left out
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True, "max_position_embeddings": 524288,
    "model_type": "minicpm_sala", "num_attention_heads": 32, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
}
PUBLISHED_SPARSE_AT = [0, 9, 16, 17, 22, 29, 30, 31]
V5E = peaks.PEAKS["TPU v5e"]
# the tiny model's own readings on the CPU (8 seeds, PR 35, checks/readings_decode.py --tiny): the program's logit_gap
# 0.0018-0.0023 and token_gap 0-0.0038 over 20-42 served tokens; the bfloat16 witness 0.0018-0.0024; the int8 control
# 0.0117-0.0144 and 0-0.026 (it fails logit_gap on every seed and never token_gap: a tiny vocabulary of 512 has few
# near-ties). The cell's limits were read on the chip at its own size (PERF.md section 2).
TINY_LIMITS = {"token_gap": 0.2, "logit_gap": 0.005, "init_gap": 0}


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


FLOPS = manifest.load_module("flops", CONFIG)


def test_the_file_holds_every_published_number_and_states_the_cut():
    c = _config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "mixer_types"] and set(c["reduced_why"]) == set(c["reduced"])
    assert c["num_hidden_layers"] == 8 and c["mixer_types"] == PERIOD * 2
    pub = c["published"]
    assert pub["num_hidden_layers"] == 32 and len(pub["mixer_types"]) == 32
    assert [i for i, k in enumerate(pub["mixer_types"]) if k == "minicpm4"] == PUBLISHED_SPARSE_AT
    # the cut keeps the published ratio of kinds (8 : 24) and a sparse first layer
    assert c["mixer_types"].count("minicpm4") * 32 == 8 * len(c["mixer_types"]) and c["mixer_types"][0] == "minicpm4"
    assert c["n_layers"] == c["num_hidden_layers"] and c["vocab"] == c["vocab_size"] and c["d_model"] == 4096
    assert c["param_dtype"] == c["compute_dtype"] == c["kv_dtype"] == "bfloat16" and c["control_precision"] == "int8"
    assert c["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
                                  "init_blocks": 1, "window_size": 2048}
    assert "four stages of 8 layers" in c["deployment"] and len(c["assumed"]) >= 10
    for said in ("dense_len", "BY QUERY POSITION", "EXACT softmax", "mup_denominator", "(j, j + 64)"):
        assert any(said in a for a in c["assumed"]), said
    e = c["engine"]
    assert (e["max_seqs"], e["max_new_tokens"], e["prefill_buckets"], e["page_size"]) == (16, 512, [16384], 64)
    # pages for 16 worst-case sequences of 16,384 + 1 + 512 positions, and the longest context the reference pads to
    assert e["kv_pages"] == 16 * 265 and 265 * 64 >= c["seq_len"] == 16384 + 1 + 512 > 264 * 64
    assert e["page_size"] == c["sparse_config"]["block_size"]  # a chosen block is a page
    entry = {x["name"]: x for x in manifest.load_manifest()["configs"]}[CONFIG]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    t = c["tiny"]
    assert t["sparse_config"] == {"kernel_size": 4, "kernel_stride": 2, "block_size": 8, "topk": 2,
                                  "init_blocks": 1, "window_size": 16}
    assert t["engine"]["page_size"] == 8 and t["seq_len"] == max(t["engine"]["prefill_buckets"]) + 1 + 16


def test_the_flops_files_parameter_count_is_the_models_own_leaves():
    import jax

    from theanompi_tpu.models.minicpm_sala import MiniCPM_SALA_Stage8

    c = _config()
    model = MiniCPM_SALA_Stage8()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    assert all(a.dtype == "bfloat16" for a in leaves)
    assert sum(int(a.size) for a in leaves) == FLOPS.model_params(c) == 2_820_569_088  # 5.64 GB in bfloat16
    assert FLOPS.layer_params(c, "minicpm4") == 253_763_840
    assert FLOPS.layer_params(c, "lightning-attn") == 285_225_216
    assert 2 * FLOPS.head_params(c) == 601_686_016
    # the recipe is the file: widths, kinds and the sparse sizes
    r, sp = model.recipe, c["sparse_config"]
    assert (r.d_model, r.d_ff, r.n_heads, r.head_dim, r.n_kv_heads, r.num_classes) == (4096, 16384, 32, 128, 2, 73448)
    assert list(r.mixer_types) == c["mixer_types"] and r.depth_published == c["published"]["num_hidden_layers"]
    assert (r.sparse_kernel, r.sparse_stride, r.sparse_block, r.sparse_topk, r.sparse_init_blocks,
            r.sparse_window) == (sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"],
                                 sp["init_blocks"], sp["window_size"])
    assert (r.scale_emb, r.scale_depth, r.dim_model_base, r.rope_theta) == (12, 1.4, 256, 10000)
    # the pools of the configuration's engine: 2 x 512 B of K and V and 32 B of compressed key a position a sparse layer
    spec = model.cache_spec(c["engine"]["page_size"])
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * np.dtype(spec["dtype"]).itemsize
    assert spec["paged_layers"] * a_page // 64 == 2 * FLOPS.kv_bytes_per_position(c) == 2048
    assert FLOPS.cache_bytes_per_position(c) == 2048 + 2 * 32 and FLOPS.state_bytes_per_slot(c) == 6 * 2 * 1024 ** 2


def test_what_a_query_sees_by_hand():
    c = _config()
    assert list(FLOPS.visible_positions(c, [1, 64, 65, 6208])) == [1, 64, 65, 6208]  # everything
    # a context of 6,209: position 6,208 = block 97, offset 0; the window starts in block 65; blocks 1 .. 64 are all chosen
    assert FLOPS.visible_positions(c, 6209) == 6209
    # 6,272 positions: t = 6,271 in block 97; the window starts at 4,224 = block 66: one block of 65 is left out
    assert FLOPS.visible_positions(c, 6272) == 6272 - 64
    # 16,384 positions: block 0, blocks 224 .. 255 (t - 2047 = 14,336 = the start of block 224) and 64 chosen
    assert FLOPS.visible_positions(c, 16384) == 64 + 32 * 64 + 64 * 64 == 6208
    assert FLOPS.visible_positions(c, 16385) == 64 + 32 * 64 + 1 + 64 * 64  # one more block, one position of it
    assert list(FLOPS.usable_compressed(c, [31, 32, 47, 48, 16384])) == [0, 1, 1, 2, 1023]


def test_one_decode_step_of_16_sequences_at_14600_positions_by_hand():
    c = _config()
    n, ctx = 16, 16 * 14600
    weights = 2 * (3 * 4096 * 4096 + 2 * 4096 * 256) + 6 * 5 * 4096 * 4096 + 8 * 3 * 4096 * 16384
    assert FLOPS.matmul_params_per_token(c) == weights == 2_218_786_816
    # t = 14,599 = block 228, offset 7; the window starts at 12,552 in block 196: block 0, blocks 196 .. 227 whole,
    # 8 positions of block 228, 64 chosen
    seen = 64 + 32 * 64 + 8 + 64 * 64
    assert FLOPS.visible_positions(c, 14600) == seen == 6216
    usable = (14600 - 32) // 16 + 1
    assert FLOPS.decode_bytes(c, n, ctx) == (
        2 * (weights + 4096 * 73448) + 2 * n * seen * 1024 + 2 * n * usable * 512 + 2 * n * 6 * 32 * 128 * 128 * 4)
    assert FLOPS.decode_flops(c, n, ctx) == (
        2 * (weights + 4096 * 73448) * n + 2 * n * (32 * 512 * seen + 32 * 256 * usable) + 6 * n * 32 * 4 * 128 * 128)
    seconds, bound = FLOPS.decode_least_seconds(c, V5E, n, ctx)
    assert bound == "bytes" and round(seconds * 1e3, 2) == 6.91  # 5.04 GB of weights, 0.20 of pages, 0.40 of state
    assert round(FLOPS.sparse_decode_least_seconds(c, V5E, n, ctx) * 1e3, 3) == round(2 * n * seen * 1024 / 819e9 * 1e3, 3)
    assert round(FLOPS.lightning_step_least_seconds(c, V5E, n) * 1e3, 3) == 0.492
    assert FLOPS.attention_flops_per_seen(c) / 1024 == 16.0  # operations a seen byte: under the v5e's 240
    # a prompt of 16,384 tokens prefills 16,383 positions: 4.44 GF a position of weights, 0.19 of mixing
    assert round(FLOPS.prefill_flops(c, [16383]) / 1e12, 1) == 75.8
    assert FLOPS.prefill_flops(c, [16383, 16383]) == 2 * FLOPS.prefill_flops(c, [16383])
    # the flash pass of such a prompt, both layers: 2.7 TF over the seen pairs, where all causal pairs would be 4.4
    assert round(FLOPS.sparse_prefill_least_seconds(c, V5E, 16383) * 1e3, 1) == 13.8
    assert FLOPS.step_flops(c) == FLOPS.decode_flops(c, 16, 16 * (16897 - 256))


def _rctx(op_s, first=10):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context, new_lens, prefills)
    rows = [(n, 0, 0, 0, 0, 0, 16, 16 * 14600, (15000,) if n == 12 else (), int(n == 12)) for n in range(1, 20)]
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.04},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 1.0}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


READERS = ("sparse_decode_roofline.decode", "lightning_step_roofline.decode", "sparse_prefill_roofline.decode")


@pytest.mark.parametrize("name", READERS)
def test_a_kernel_reader_reads_nothing_without_a_match(name):
    reader = manifest.load_module("metrics", name)
    other = {"%fusion.1 fusion": 0.5, "%sparse_cache_write.1 custom-call": 0.2, "%mla_decode.3 custom-call": 0.1}
    assert reader.read(_rctx(other)) is None
    assert reader.read({**_rctx(other), "trace": None}) is None
    named = {**other, "%sparse_decode.3 custom-call": 0.01, "%lightning_step.9 custom-call": 0.01,
             "%sparse_prefill.2 custom-call": 0.1}
    assert reader.read({**_rctx(named), "flops": manifest.load_module("flops", "mistral-small-4-decode")}) is None
    assert reader.read({**_rctx(named), "traced_first": None}) is None


def test_the_kernel_readers_read_a_share_under_100_with_a_match():
    c = _config()
    named = {"%fusion.1 fusion": 0.5, "%sparse_decode.3 custom-call": 0.004, "%sparse_decode.4 custom-call": 0.004,
             "%lightning_step.9 custom-call": 0.003, "%sparse_prefill.2 custom-call": 0.05,
             "%sparse_cache_write.1 custom-call": 0.5}
    read = {name: manifest.load_module("metrics", name).read(_rctx(named)) for name in READERS}
    # 4 traced steps of 16 sequences at 14,600 positions against 8 ms of the kernel
    want = 100 * 4 * FLOPS.sparse_decode_least_seconds(c, V5E, 16, 16 * 14600) / 0.008
    assert abs(read[READERS[0]] - want) < 1e-9 and 0 < want < 100
    want = 100 * 4 * FLOPS.lightning_step_least_seconds(c, V5E, 16) / 0.003
    assert abs(read[READERS[1]] - want) < 1e-9 and 0 < want < 100
    want = 100 * FLOPS.sparse_prefill_least_seconds(c, V5E, 15000) / 0.05  # the one prefill in the window
    assert abs(read[READERS[2]] - want) < 1e-9 and 0 < want < 100


def test_the_prefills_share_of_the_peak_by_hand():
    reader = manifest.load_module("metrics", "prefill_mfu.decode")
    ctx = {**_rctx({"%fusion.1 fusion": 0.5}), "chips": 1}
    # one prefill of 15,000 cached positions in the traced window, 1.0 s of the prefill program
    want = 100 * FLOPS.prefill_flops(_config(), [15000]) / 1.0 / 197e12
    assert abs(reader.read(ctx) - want) < 1e-9 and 30 < want < 40
    assert reader.read({**ctx, "trace": None}) is None and reader.read({**ctx, "traced_first": None}) is None
    two = {**ctx["trace"], "programs": {**ctx["trace"]["programs"], "jit__counted_prefill(2)": {"runs": 2, "seconds": 2.0}}}
    assert reader.read({**ctx, "trace": two}) is None  # the probe's rows and the trace's runs are not the same prefills


def test_the_new_cell_reports_what_30_s_can_hold_steady_and_the_new_readers_list_it_alone():
    # ttft_p50_ms alone beside setup_s: over six seeds tpot_p50_ms spreads 3.8 % and decode_tokens_per_s 5.2 % (my
    # chip runs, PR 35; the traffic's own swing: 27-30 requests of 256-512 tokens a window, each prefill 2.2 % of it),
    # against the 1.25 % and 1.5 % that half their bounds allow. The readers of the decode step's two kernels stay
    # as files (tested above) for the benchmark PR that lets this cell report the metric they move (SALA.md).
    man = manifest.load_manifest()
    assert {m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)} == {"ttft_p50_ms", "setup_s"}
    assert {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)} == {
        "compile_s", "prefill_share.decode", "prefill_mfu.decode", "sparse_prefill_roofline.decode"}
    new = {m["name"]: m for m in man["per_layer"][-2:]}
    assert list(new) == ["prefill_mfu.decode", "sparse_prefill_roofline.decode"]  # appended, nothing moved
    assert all(m["workloads"] == [CELL] and m["moves"] == "ttft_p50_ms" for m in new.values())
    assert new["prefill_mfu.decode"]["layer"] == "model step" and new["sparse_prefill_roofline.decode"]["layer"] == "kernels"
    assert man["workloads"][-1]["name"] == CELL and man["configs"][-1]["name"] == CONFIG
    _, cell, workload, _ = manifest.resolve(CELL)
    data = workload["data"]
    assert (data["clients"], data["prompt_len"], data["new_tokens"]) == (
        16, {"law": "uniform", "lo": 12289, "hi": 16384}, {"law": "uniform", "lo": 256, "hi": 512})
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
    # contexts on both sides of the position where the choice of blocks starts, and past it while decoding
    lengths = [len(h) for h, _ in m["samples"]]
    assert min(lengths) >= 60 and max(lengths) > 100
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
def test_a_program_that_leaves_pages_keys_and_state_as_it_found_them_reads_not_correct(fault):
    # experiments/mla_fault_probe.py plants the fault with a copy of every pool (pages, compressed keys, state)
    # taken before the call, so that it survives donation and reaches the run's own comparison
    probe = runpy.run_path(os.path.join(manifest.ROOT, "experiments", "mla_fault_probe.py"))
    driver, m = _measure(12, fault=fault, plant=probe["plant"])
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks) and checks["logit_gap"][0] > TINY_LIMITS["logit_gap"], checks
    assert m["bad"] == 0 and m["pages_lost"] == 0 and m["compiles_in_window"] == 0
