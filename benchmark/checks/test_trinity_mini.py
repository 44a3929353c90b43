"""The ``trinity-mini`` configuration's arithmetic and readers: the flops
file's numbers (pairs, parameters, 18.1 TF a step), the two roofline readers
and the padding reader on synthetic inputs (nothing without a match, a share
under 100 % with one), the configuration file against the published widths,
and the new cell's ``--tiny`` rehearsal on the CPU."""

import json
import os
import time

from harness import manifest, peaks

CELL = "trinity-mini-bsp1-train8k"
PUBLISHED = {  # the catalog row's config, numbers and flags; reduced keys left out
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "hidden_act": "silu", "model_type": "afmoe", "score_func": "sigmoid", "rope_scaling": None,
}


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "trinity-mini.json")) as f:
        return json.load(f)


def test_the_file_holds_every_published_width_and_states_the_cut():
    c = _config()
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    assert c["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert set(c["reduced"]) == {"num_hidden_layers", "num_dense_layers", "num_experts_held",
                                 "vocab_size"}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts_held"],
            c["vocab_size"]) == (5, 1, 16, 25024)
    assert c["vocab"] == c["vocab_size"] == 200192 // 8
    assert len(c["layers_run"]) == c["num_hidden_layers"]
    assert [k[2] for k in c["layers_run"]] == ["dense"] + ["routed"] * 4
    assert [k[0] for k in c["layers_run"]] == [2048] * 4 + [None]  # window x3 then full, after the dense one
    assert "8 chips share each layer" in c["deployment"] and c["assumed"]
    man = manifest.load_manifest()
    entry = {e["name"]: e for e in man["configs"]}["trinity-mini"]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]


def test_the_step_needs_13_6_TF_of_matmul_and_4_5_TF_of_attention():
    flops, c = manifest.load_module("flops", "trinity-mini"), _config()
    assert flops.attended_pairs(8192, 2048) == 14_681_088
    assert flops.attended_pairs(8192, None) == 33_558_528
    assert flops.flops_per_pair(c) == 49_152
    assert flops.attention_params(c) == 27_262_976
    assert flops.expert_params(c) == 6_291_456
    assert flops.experts_per_token_here(c) == 1.0
    assert round(flops.matmul_flops(c) / 1e12, 1) == 13.6
    assert round(flops.attention_flops(c) / 1e12, 2) == 4.54
    assert round(flops.step_flops(c) / 1e12, 1) == 18.1
    assert round(flops.routed_flops(c) / 1e12, 3) == 1.237
    assert flops.routed_rows(c) == 8192
    # nine products a layer, each moving rows x 2048, 16 x 2048 x 1024 of weights and rows x 1024
    assert flops.routed_bytes(c) == 4 * 9 * 2 * (16 * 2048 * 1024 + 8192 * 2048 + 8192 * 1024)
    assert flops.routed_flops(c, 4 * 8192) == flops.routed_flops(c)
    assert flops.routed_bytes(c, 4 * 8192) == flops.routed_bytes(c)


def _rctx(op_s, rows=()):
    class Rec:
        history = {"train": list(rows)}

    return {"trace": {"op_s": op_s, "steps": 8}, "peaks": peaks.PEAKS["TPU v5 lite"],
            "flops": manifest.load_module("flops", "trinity-mini"), "config": _config(),
            "recorder": Rec(), "first_step": 1, "last_step": 2, "cell": {"driver": "train"}}


def test_roofline_readers_read_nothing_without_a_match_and_a_share_with_one():
    gmm = manifest.load_module("metrics", "moe_gmm_roofline.train")
    attn = manifest.load_module("metrics", "window_attn_roofline.train")
    other = {"%fusion.1 fusion": 0.5, "%divide_add_fusion fusion": 0.2}
    assert gmm.read(_rctx(other)) is None and attn.read(_rctx(other)) is None
    assert gmm.read({**_rctx(other), "trace": None}) is None
    names = {**other, "%checkpoint_moe_gmm.3 custom-call": 0.10, "%jvp_moe_tgmm.1 custom-call": 0.06,
             "%jvp_flash_fwd_.2 custom-call": 0.2, "%transpose_flash_bwd_dq_2d.1 custom-call": 0.3}
    c, flops = _config(), manifest.load_module("flops", "trinity-mini")
    # compute-bound: 1.237 TF a step over 197 TF/s is 6.28 ms, against 5.16 ms of bytes
    assert flops.routed_flops(c) / 197e12 > flops.routed_bytes(c) / 819e9
    share = gmm.read(_rctx(names))  # rows without the counter: the expected load
    assert abs(share - 100 * 8 * flops.routed_flops(c) / 197e12 / 0.16) < 1e-9 and 0 < share < 100
    # the traced steps' own pairs, from the rows of calls FOLLOW + 2 on (a quarter of the
    # expected load: the weights' bytes now bound it)
    rows = [{"moe_pairs_here": 1e9}] * 4 + [{"moe_pairs_here": 8192.0}] * 8
    least = flops.routed_bytes(c, 8192) / 819e9
    assert least > flops.routed_flops(c, 8192) / 197e12
    assert abs(gmm.read(_rctx(names, rows)) - 100 * 8 * least / 0.16) < 1e-9
    share = attn.read(_rctx(names))
    assert abs(share - 100 * 8 * flops.attention_flops(c) / 197e12 / 0.5) < 1e-9 and 0 < share < 100


def test_pad_share_reads_the_rows_counters_or_nothing():
    pad = manifest.load_module("metrics", "moe_pad_share.train")
    rows = [{"loss": 1.0, "moe_pad_rows": 999, "moe_pairs_here": 1}] + [
        {"loss": 1.0, "moe_pad_rows": 2048.0, "moe_pairs_here": 32768.0}] * 2
    assert pad.read(_rctx({}, rows)) == 6.25  # the window's rows only
    assert pad.read(_rctx({}, [{"loss": 1.0}] * 3)) is None  # a program without the counters


def test_the_new_metrics_list_the_new_cell_only():
    man = manifest.load_manifest()
    got = {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)}
    assert {"moe_gmm_roofline.train", "window_attn_roofline.train", "moe_pad_share.train",
            "compile_s", "data_wait_ms.train", "mfu.train", "device_idle.train"} <= got
    assert "flash_attn_roofline.train" not in got
    for m in man["per_layer"]:
        if m["name"].startswith(("moe_", "window_")):
            assert m["workloads"] == [CELL]


def test_tiny_rehearsal_of_the_new_cell_runs_and_compares():
    man, entry, workload, config = manifest.resolve(CELL)
    driver = manifest.load_module("drivers", "train")
    ctx = {"manifest": man, "cell": entry, "workload": workload, "config": config,
           "seed": 3_000_000_019, "seconds": 0.5, "trace": False, "tiny": True,
           "t_process_start": time.perf_counter()}
    m = driver.measure(ctx)
    checks = driver.checks_of(m)
    for name in ("init_gap", "steps_not_on_device", "compiles_in_window"):
        assert checks[name][0] == 0, (name, checks)
    # bf16 against fp32 over 128 tokens a step: rounding, not a fault
    assert checks["loss_gap"][0] < 5e-3 and checks["grad_gap"][0] < 0.1 and checks["change_gap"][0] < 0.1
    rows = m["recorder"].history["train"]
    assert all("moe_pairs_here" in r and "moe_pad_rows" in r for r in rows)
    # a step that hands its state back unchanged reads 1 on the change
    ctx["fault"] = "state_unchanged"
    assert not driver.is_correct(driver.checks_of(driver.measure(ctx)))
