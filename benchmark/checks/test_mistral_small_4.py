"""The ``mistral-small-4-decode`` configuration's arithmetic and readers: the
configuration file against the published numbers, the flops file's parameter
count against the model's own leaves and its step against a hand count, the
two kernel readers on synthetic inputs (nothing without a match, a share under
100 % with one), and the new cell's ``--tiny`` rehearsal through
``drivers/decode.py`` on the CPU: sound, the control not, the faults not."""

import json
import os
import time

import numpy as np
import pytest

from harness import manifest, peaks

CELL, CONFIG = "mistral-small-4-decode-doc8k", "mistral-small-4-decode"
PUBLISHED = {  # the catalog row's config; the reduced keys left out
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 12288, "kv_lora_rank": 256,
    "max_position_embeddings": 1048576, "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128, "qk_nope_head_dim": 64,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "routed_scaling_factor": 1, "sliding_window": None, "tie_word_embeddings": False,
    "topk_group": 1, "v_head_dim": 128,
    "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 128, "llama_4_scaling_beta": 0.1,
                        "mscale": 1, "mscale_all_dim": 1, "original_max_position_embeddings": 8192,
                        "rope_theta": 10000, "rope_type": "yarn", "type": "yarn"},
}
V5E = peaks.PEAKS["TPU v5e"]
# the tiny model's own readings on the CPU (8 seeds, PR 33): the program's logit_gap 0.0024-0.0031 and token_gap
# 0-0.0027 over 31-43 served tokens; the bfloat16 witness 0.0023-0.0036; the int8 control 0.038-0.070 and 0.022-0.114
# (it fails logit_gap on every seed). With a bfloat16 residual stream the program read 0.0040-0.0050 on six seeds and
# 0.0225-0.0247 on two: a row whose fourth expert differed. The cell's limits were read on the chip at its own size
# (PERF.md section 2).
TINY_LIMITS = {"token_gap": 0.2, "logit_gap": 0.011, "init_gap": 0}


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


FLOPS = manifest.load_module("flops", CONFIG)


def test_the_file_holds_every_published_number_and_states_the_cut():
    c = _config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts_held"], c["vocab_size"]) == (6, 16, 131072 // 8)
    assert c["published"] == {"num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}
    assert c["n_layers"] == c["num_hidden_layers"] and c["vocab"] == c["vocab_size"] and c["d_model"] == 4096
    assert c["param_dtype"] == c["compute_dtype"] == c["kv_dtype"] == "bfloat16"
    assert "8 chips share each layer" in c["deployment"] and len(c["assumed"]) >= 4
    e = c["engine"]
    assert (e["max_seqs"], e["max_new_tokens"], e["prefill_buckets"]) == (32, 512, [8192])
    # pages for 32 worst-case sequences of 8,192 + 512 positions, and the longest context the reference pads to
    assert e["kv_pages"] * e["page_size"] == 32 * 8704 and c["seq_len"] == 8192 + 1 + 512
    entry = {x["name"]: x for x in manifest.load_manifest()["configs"]}[CONFIG]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]


def test_the_flops_files_parameter_count_is_the_models_own_leaves():
    import jax

    from theanompi_tpu.models.mistral4 import MistralSmall4_EP8

    c = _config()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda k: MistralSmall4_EP8().init(k)[0], jax.random.PRNGKey(0)))
    assert all(a.dtype == "bfloat16" for a in leaves)
    counted = sum(int(a.size) for a in leaves)
    assert counted == FLOPS.model_params(c) == 2_872_634_880  # 5.75 GB in bfloat16
    assert FLOPS.attention_params(c) + 1024 + 256 == 28_050_688
    assert FLOPS.expert_params(c) == 25_165_824 and FLOPS.router_params(c) == 524_288
    assert FLOPS.layer_params(c) == 456_402_176
    # the pools of the configuration's engine: 640 B a position a layer
    page = c["engine"]["page_size"]
    spec = MistralSmall4_EP8().cache_spec(page)
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * np.dtype(spec["dtype"]).itemsize
    assert c["n_layers"] * a_page // page == FLOPS.cache_bytes_per_position(c) == 3840


def test_one_decode_step_of_32_sequences_at_8500_positions_by_hand():
    c = _config()
    n, ctx = 32, 32 * 8500
    assert FLOPS.experts_per_token_here(c) == 0.5
    assert round(FLOPS.experts_hit(c, n), 2) == 10.21  # of 16: (1 - 1/32)^32 = 0.362 are missed
    per_token = 2 * (6 * (28_049_408 + 524_288 + 25_165_824 + 0.5 * 25_165_824) + 4096 * 16384)
    assert FLOPS.decode_flops(c, n, ctx) == per_token * n + 6 * 36_864 * ctx
    dense = 6 * (28_049_408 + 524_288 + 25_165_824) + 4096 * 16384
    assert FLOPS.decode_bytes(c, n, ctx) == 2 * (dense + 6 * FLOPS.experts_hit(c, n) * 25_165_824) + 3840 * ctx
    seconds, bound = FLOPS.decode_least_seconds(c, V5E, n, ctx)
    assert bound == "bytes" and round(seconds * 1e3, 2) == 5.99
    assert FLOPS.absorbed_flops_per_position(c) / 640 == 57.6  # operations a cached byte: under the v5e's 240
    assert round(FLOPS.mla_decode_least_seconds(c, V5E, ctx) * 1e3, 3) == 1.275
    # a prompt of 8,192 tokens prefills 8,191 positions: 0.8 GF a position of weights, 0.4 of attention
    assert round(FLOPS.prefill_flops(c, [8191]) / 1e12, 2) == 9.82
    assert FLOPS.prefill_flops(c, [8191, 8191]) == 2 * FLOPS.prefill_flops(c, [8191])
    # the routed products: a decode step's are bound by the weights of the experts hit (3.08 GB: 3.77 ms
    # against 0.02 of operations); a prefill's 256 rows an expert lie at the ridge (7.0 ms of bytes, 6.3 of operations)
    assert round(FLOPS.gmm_least_seconds(c, V5E, 32) * 1e3, 2) == 3.77
    assert round(FLOPS.gmm_flops(c, 32) / V5E["bf16_flops"] * 1e3, 2) == 0.02
    assert round(FLOPS.gmm_bytes(c, 8191) / V5E["hbm_bytes_per_s"] * 1e3, 1) == 7.0
    assert round(FLOPS.gmm_flops(c, 8191) / V5E["bf16_flops"] * 1e3, 1) == 6.3


def _rctx(op_s, first=10):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context, new_lens, prefills)
    rows = [(n, 0, 0, 0, 0, 0, 32, 32 * 8500, (8000,) if n == 12 else (), int(n == 12)) for n in range(1, 20)]
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.08},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 0.1}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


def test_the_kernel_readers_read_nothing_without_a_match_and_a_share_with_one():
    mla = manifest.load_module("metrics", "mla_decode_roofline.decode")
    gmm = manifest.load_module("metrics", "routed_moe_gmm_roofline.decode")
    other = {"%fusion.1 fusion": 0.5, "%divide_add_fusion fusion": 0.2}
    for reader in (mla, gmm):
        assert reader.read(_rctx(other)) is None
        assert reader.read({**_rctx(other), "trace": None}) is None
        assert reader.read({**_rctx(other), "flops": manifest.load_module("flops", "lm136m-decode")}) is None
    names = {**other, "%mla_decode.3 custom-call": 0.02, "%moe_routed_moe_gmm.7 custom-call": 0.04,
             "%mla_cache_write.1 custom-call": 0.5}
    c = _config()
    share = mla.read(_rctx(names))  # 4 traced steps of 272,000 positions against 20 ms of the kernel
    assert abs(share - 100 * 4 * FLOPS.mla_decode_least_seconds(c, V5E, 32 * 8500) / 0.02) < 1e-9 and 0 < share < 100
    share = gmm.read(_rctx(names))  # 4 decode steps and the one prefill of 8,000 positions in the window
    least = 4 * FLOPS.gmm_least_seconds(c, V5E, 32) + FLOPS.gmm_least_seconds(c, V5E, 8000)
    assert abs(share - 100 * least / 0.04) < 1e-9 and 0 < share < 100


def test_the_new_cell_is_in_the_serving_lists_and_the_new_readers_list_it_alone():
    man = manifest.load_manifest()
    assert {m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)} == {
        "ttft_p50_ms", "tpot_p50_ms", "decode_tokens_per_s", "setup_s"}
    assert {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)} == {
        "compile_s", "mfu.decode", "decode_step_roofline.decode", "device_idle.decode", "prefill_share.decode",
        "host_loop_ms.decode", "batch_occupancy.decode", "mla_decode_roofline.decode", "routed_moe_gmm_roofline.decode"}
    for m in man["per_layer"]:
        if m["name"] in ("mla_decode_roofline.decode", "routed_moe_gmm_roofline.decode"):
            assert m["workloads"] == [CELL] and m["moves"] == "decode_tokens_per_s"
    _, cell, workload, _ = manifest.resolve(CELL)
    data = workload["data"]
    assert (data["clients"], data["prompt_len"], data["new_tokens"]) == (
        32, {"law": "uniform", "lo": 7169, "hi": 8192}, {"law": "uniform", "lo": 256, "hi": 512})
    assert cell["chips"] == 1 and workload["driver"] == "decode" and data["kind"] == "closed_loop_prompts"


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
    witness = driver.stand_in_numbers(m, "bfloat16")
    assert witness["logit_gap"] < checks["logit_gap"][0]  # rounding the matmuls' operands alone moves less


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
