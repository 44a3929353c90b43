"""The ``gigachat35-decode`` configuration's arithmetic and readers: the
configuration file against the published numbers, the flops file's parameter
count against the model's own leaves, a prefill's operations and a decode
step's bytes against a hand count, the two new readers on synthetic inputs
(nothing without a match or without the count, a share under 100 % with
them), the manifest's entries found by name, and the new cell's ``--tiny``
rehearsal through ``drivers/decode.py`` on the CPU: sound, the control not,
altered tokens not, a decode step that hands back the delta rule's state
unwritten not."""

import json
import os
import runpy
import time

import numpy as np

from harness import manifest, peaks

CELL, CONFIG = "gigachat35-decode-reason2k", "gigachat35-decode"
PUBLISHED = {  # the catalog row's config; the reduced keys left out
    "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "nextn_is_sparse": False, "num_attention_heads": 64, "n_shared_experts": 1,
    "n_routed_experts": 256, "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192, "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 8, "first_k_dense_replace": 3, "norm_topk_prob": True,
    "rope_interleave": True, "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000, "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                                           "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
                                           "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
    "layernorm_gating_weight": 2, "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39], "linear_key_head_dim": 128,
    "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10,
    "tie_word_embeddings": False, "model_type": "gigachat3_5", "tf_legacy_loss": False,
}
V5E = peaks.PEAKS["TPU v5e"]
# the tiny model's own readings on the CPU (6 seeds, checks/readings_decode.py --tiny): the program's logit_gap
# 0.026-0.046 and token_gap 0-0.055; the bfloat16 witness 0.016-0.051 and 0-0.025; the int8 control 0.176-0.357 and
# 0.173-0.511 (it fails both on every seed). The tiny block routes every token to all 8 of its experts: with top 4
# of 32 at width 64 the k-th and (k+1)-th scores often lie closer than bfloat16 rounding moves them, and the
# post-norms carry a moved choice into the whole row (program and witness read up to 0.40 and 1.04 there, the
# control from 0.31). The cell's limits were read on the chip at its own size (PERF.md section 2).
TINY_LIMITS = {"token_gap": 0.12, "logit_gap": 0.1, "init_gap": 0}


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


FLOPS = manifest.load_module("flops", CONFIG)


def test_the_file_holds_every_published_number_and_states_the_cut():
    c = _config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "num_experts_held", "vocab_size", "num_nextn_predict_layers"]
    assert set(c["reduced_why"]) == set(c["reduced"])
    assert (c["num_hidden_layers"], c["num_experts_held"], c["vocab_size"], c["num_nextn_predict_layers"]) == (
        5, 8, 16032, 0)
    assert c["published"] == {"num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 128256,
                              "num_nextn_predict_layers": 2}
    assert c["layer_ids"] == [0, 3, 4, 5, 6] and c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["n_layers"] == c["num_hidden_layers"] and c["vocab"] == c["vocab_size"] and c["d_model"] == 7168
    assert c["param_dtype"] == c["compute_dtype"] == c["kv_dtype"] == "bfloat16" and c["control_precision"] == "int8"
    assert "32 chips share each layer" in c["deployment"] and len(c["assumed"]) >= 10
    for said in ("2 sigma(w)", "(1 + w) 2 sigma(z)", "h // 2", "log((h + 1) / 4)", "clamped at 10",
                 "noaux_tc", "sigma(h W_g)", "192^-0.5", "[64, 128, 128] float32", "normal(0, 0.02)",
                 "multi-token prediction"):
        assert any(said in a for a in c["assumed"]), said
    e = c["engine"]
    assert (e["max_seqs"], e["max_new_tokens"], e["prefill_buckets"], e["page_size"]) == (128, 2048, [1024], 128)
    # pages for 128 worst-case sequences of 1,024 + 1 + 2,048 positions
    assert c["seq_len"] == 1024 + 1 + 2048 and e["kv_pages"] == 128 * -(-c["seq_len"] // 128) == 128 * 25
    entry = {x["name"]: x for x in manifest.load_manifest()["configs"]}[CONFIG]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    t = c["tiny"]
    assert t["seq_len"] == max(t["engine"]["prefill_buckets"]) + 1 + t["engine"]["max_new_tokens"]


def test_the_flops_files_parameter_count_is_the_models_own_leaves():
    import jax

    from theanompi_tpu.models.gigachat35 import GigaChat35_EP32

    c = _config()
    model = GigaChat35_EP32()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    assert sum(int(a.size) for a in leaves) == FLOPS.model_params(c) == 3_322_436_608  # 6.64 GB in bfloat16
    assert FLOPS.n_full(c) == 1 and FLOPS.n_linear(c) == 4 and FLOPS.routed_layers(c) == 4
    # a delta-rule mixer: q, k (4,096 each), v, z (8,192 each), b, a (64 each), out; the taps, A_log, dt_bias, gain
    mixer = 7168 * (2 * 4096 + 2 * 8192 + 128) + 8192 * 7168 + 4 * 16384 + 128 + 128
    assert FLOPS.gdn_mixer_params(c) == mixer == 235_864_320
    # the gated latent attention: W_dq, W_uq, W_dkv, W_ukv, W_o, W_g, two gains
    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 2 * 64 * 128 * 7168 + 1536 + 512
    assert FLOPS.mla_mixer_params(c) == mla == 159_844_352
    assert FLOPS.layer_params(c, False, True) == 632_254_720  # layer 0: the delta rule beside SwiGLU 18,432
    assert FLOPS.layer_params(c, True, False) == 558_070_016 and FLOPS.layer_params(c, False, False) == 634_089_984
    # the pools of the configuration's engine: 1,152 B a position, 17.2 MB a slot
    spec = model.cache_spec(c["engine"]["page_size"])
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * np.dtype(spec["dtype"]).itemsize
    assert a_page // 128 == FLOPS.latent_bytes_per_position(c) == 1152
    held = sum(a["layers"] * np.prod(a["row"]) * np.dtype(a["dtype"]).itemsize for a in spec["slots"].values())
    assert held == FLOPS.state_bytes_per_slot(c) == 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2) == 17_170_432


def test_a_prefill_and_a_decode_step_by_hand():
    c = _config()
    gdn = 7168 * (2 * 4096 + 2 * 8192 + 128) + 8192 * 7168
    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 2 * 64 * 128 * 7168
    expert, router = 3 * 7168 * 2048, 7168 * 256
    routed_mlp = router + expert + 0.25 * expert  # the shared expert and a quarter of a held expert a token
    per_token = 4 * gdn + mla + 3 * 7168 * 18432 + 4 * routed_mlp
    assert FLOPS.matmul_flops_per_token(c) == 2 * (per_token + 7168 * 16032)
    # the last layer (the delta rule) keeps its window and state: its MLP, W_z and W_out are dead
    assert FLOPS.prefill_matmul_params(c) == per_token - routed_mlp - 2 * 7168 * 8192
    n = 1000
    chunks = 2 * 4 * 64 * 16 * (5 * 64 * 64 * 128 + 3 * 64 * 128 * 128 + 64 ** 3 / 3)
    assert FLOPS.gdn_chunk_flops(c, n) == chunks
    assert FLOPS.prefill_flops(c, [n]) == 2 * FLOPS.prefill_matmul_params(c) * n + 64 * 2 * 320 * n * (n + 1) // 2 + chunks
    assert FLOPS.prefill_flops(c, [n, n]) == 2 * FLOPS.prefill_flops(c, [n])
    # 128 slots at 1,900 positions: the weights outside the experts, 7.7 held experts hit in each routed layer, the
    # latent rows, and 2 x 17.2 MB of state and windows a slot
    ctx = 128 * 1901
    hit = 8 * (1 - (1 - 8 / 256) ** 128)
    weights = 4 * gdn + mla + 3 * 7168 * 18432 + 4 * (router + expert) + 7168 * 16032 + 4 * hit * expert
    assert abs(FLOPS.decode_bytes(c, 128, ctx) - (2 * weights + 1152 * ctx + 2 * 128 * 17_170_432)) < 1
    seconds, bound = FLOPS.decode_least_seconds(c, V5E, 128, ctx)
    assert bound == "bytes" and round(seconds * 1e3, 1) == 13.5
    # the step's kernel: 4 layers x 128 slots x (4.2 MB of state in and out, q k v beta g in, o out)
    assert FLOPS.gdn_step_bytes(c, 128) == 4 * 128 * (2 * 64 * 128 * 128 * 4 + 128 * 128 * 2 + 2 * 64 * 4 + 64 * 128 * 4)
    assert round(FLOPS.gdn_step_least_seconds(c, V5E, 128) * 1e3, 2) == 5.29
    assert FLOPS.gdn_chunk_bytes(c, n) == 4 * (n * (128 * 128 * 2 + 2 * 64 * 4 + 64 * 128 * 4) + 64 * 128 * 128 * 4)
    assert FLOPS.step_flops(c) == FLOPS.decode_flops(c, 128, 128 * (3073 - 1024))


def _rctx(op_s, first=10):
    # iteration rows as the probe keeps them: (n, t_in, t_out, t_dec, t_harvest, t_done, running, sum_context,
    # new_lens, prefills); the engine's iteration k is the probe's row k
    rows = [(n, 0, 0, 0, 0, 0, 128, 128 * 1900, (899,) if n == 12 else (), int(n == 12)) for n in range(1, 20)]
    return {"trace": {"op_s": op_s, "programs": {"jit__counted_decode(1)": {"runs": 4, "seconds": 0.08},
                                                 "jit__counted_prefill(2)": {"runs": 1, "seconds": 0.05}}},
            "peaks": V5E, "flops": FLOPS, "config": _config(), "all_iterations": rows, "traced_first": first,
            "cell": {"programs": {"decode": "_counted_decode", "prefill": "_counted_prefill"}}}


READERS = ("gdn_step_roofline.decode", "gdn_chunk_roofline.decode")


def test_the_new_readers_read_nothing_without_a_match_or_the_count():
    other = {"%fusion.1 fusion": 0.5, "%flash_fwd.1 custom-call": 0.05, "%mla_decode.3 custom-call": 0.01}
    named = {**other, "%gdn_step.2 custom-call": 0.02, "%gdn_chunk.4 custom-call": 0.004}
    for name in READERS:
        reader = manifest.load_module("metrics", name)
        assert reader.read({**_rctx(named), "trace": None}) is None
        assert reader.read({**_rctx(named), "traced_first": None}) is None
        assert reader.read({**_rctx(named), "peaks": None}) is None
        assert reader.read(_rctx(other)) is None, name  # the parent's program, or another cell's
        assert reader.read({**_rctx(named), "flops": manifest.load_module("flops", "jamba2-3b-decode")}) is None


def test_the_new_readers_read_a_share_under_100_with_a_match():
    named = {"%fusion.1 fusion": 0.5, "%gdn_step.2 custom-call": 0.012, "%gdn_step.9 custom-call": 0.012,
             "%gdn_chunk.4 custom-call": 0.002, "%gdn_chunk.5 custom-call": 0.002}
    c = _config()
    got = manifest.load_module("metrics", READERS[0]).read(_rctx(named))
    want = 100 * 4 * FLOPS.gdn_step_least_seconds(c, V5E, 128) / 0.024  # 4 traced steps of 128 running slots
    assert abs(got - want) < 1e-9 and 0 < want < 100
    got = manifest.load_module("metrics", READERS[1]).read(_rctx(named))
    want = 100 * FLOPS.gdn_chunk_least_seconds(c, V5E, 899) / 0.004  # the one prefill in the window
    assert abs(got - want) < 1e-9 and 0 < want < 100


def test_the_new_cell_is_in_the_serving_lists_and_the_new_readers_list_it_alone():
    man = manifest.load_manifest()
    # tpot_p50_ms and decode_tokens_per_s spread past half their bounds over the chip runs (PERF.md section 2):
    # the cell reports the time to the first token, which is a prefill and one decode step
    assert {m["name"] for m in manifest.metrics_for(man, "end_to_end", CELL)} == {"ttft_p50_ms", "setup_s"}
    assert {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)} == {
        "compile_s", "prefill_share.decode", "prefill_mfu.decode", *READERS}
    # found by name, not by place: a later PR appends after these entries
    by_name = {m["name"]: m for m in man["per_layer"]}
    assert all(by_name[name]["moves"] == "ttft_p50_ms" for name in READERS)
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == "kernels" and m["source"] == "device_trace"
        assert m["unit"] == "%" and m["better"] == "higher"
    for name in ("mla_decode_roofline.decode", "routed_moe_gmm_roofline.decode"):  # the Mistral cell's alone
        assert CELL not in by_name[name]["workloads"]
    # a share of a roofline travels with the whole program's share of the peak that moves the same metric
    assert CELL in by_name["prefill_mfu.decode"]["workloads"]
    names = [m["name"] for m in man["per_layer"]]
    assert names.index(READERS[0]) > names.index("mamba_scan_roofline.decode")  # appended
    assert CONFIG in {c["name"] for c in man["configs"]}
    _, cell, workload, _ = manifest.resolve(CELL)
    data = workload["data"]
    assert (data["clients"], data["prompt_len"], data["new_tokens"]) == (
        128, {"law": "uniform", "lo": 769, "hi": 1024}, {"law": "uniform", "lo": 1024, "hi": 2048})
    assert cell["chips"] == 1 and workload["driver"] == "decode" and data["kind"] == "closed_loop_prompts"
    assert len(cell["why"]) <= 200 and cell["why"] == workload["why"]


_clock = {}


def _measure(seed, fault=None, plant=None):
    man, entry, workload, config = manifest.resolve(CELL)
    driver = manifest.load_module("drivers", "decode")
    planted = driver.plant_fault
    if plant is not None:
        driver.plant_fault = plant
    ctx = {"manifest": man, "cell": entry, "workload": {**workload, "limits": TINY_LIMITS}, "config": config,
           "seed": seed, "seconds": 0.4, "trace": False, "tiny": True, "fault": fault,
           "t_process_start": time.perf_counter(), "clock": _clock.get("clock")}
    try:
        m = driver.measure(ctx)
    finally:
        driver.plant_fault = planted
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


def test_a_decode_step_that_hands_back_the_delta_rules_state_unwritten_reads_not_correct():
    # experiments/mla_fault_probe.py plants it with a copy of the state taken before the step, so that it survives
    # donation and reaches the run's own comparison; the latent pages and the windows are written as they should be
    probe = runpy.run_path(os.path.join(manifest.ROOT, "experiments", "mla_fault_probe.py"))
    driver, m = _measure(12, fault="state_unwritten", plant=probe["plant"])
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks) and checks["logit_gap"][0] > TINY_LIMITS["logit_gap"], checks
    assert m["bad"] == 0 and m["pages_lost"] == 0 and m["compiles_in_window"] == 0
