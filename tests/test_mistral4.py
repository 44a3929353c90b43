"""The ``mistral4`` block (latent attention beside routed experts) at a small
size on the CPU, against the benchmark's plain reference
(``benchmark/reference/mistral-small-4-decode.py``) on seeded random weights:
prefill then decode through the latent pages, absorbed against expanded, the
``mla_decode`` kernel against its ``jnp`` twin, the yarn numbers, the eight
shares of the routed layer, and the engine's programs, pools and records."""

import json
import os
import runpy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_checks import names_of  # noqa: F401 - sets the benchmark's import path up
from harness import manifest
from theanompi_tpu.models import get_model
from theanompi_tpu.models.mistral4 import (
    Mistral4LM, MistralSmall4_EP8, softmax_scale, yarn_frequencies)
from theanompi_tpu.ops.moe import route_topk
from theanompi_tpu.ops.pallas_mla import mla_cache_write, mla_decode, mla_decode_reference
from theanompi_tpu.serve.decode.engine import DecodeEngine
from theanompi_tpu.serve.decode.kvcache import PagedKVCache, pages_needed

REF = manifest.load_module("reference", "mistral-small-4-decode")
PAGE = 8


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "mistral-small-4-decode.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"]}


def _model(dtype=jnp.float32, **over):
    c = _config()
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in c["recipe_overrides"].items()}
    return MistralSmall4_EP8(MistralSmall4_EP8.default_recipe().replace(
        **{**kw, "compute_dtype": dtype, **over}))


def _weights(model, seed=5):
    return jax.jit(model.init)(jax.random.PRNGKey(seed))[0]


def _serve(model, params, prompts, n_new, max_seqs, attend=mla_decode):
    """Prefill every prompt into its own pages, then ``n_new`` decode steps
    over ``max_seqs`` slots (the slots past the prompts stay inactive).
    -> logits ``[n_new, len(prompts), V]`` and the greedy tokens."""
    longest = max(len(p) for p in prompts) + n_new
    per_seq = pages_needed(longest, PAGE)
    spec = model.cache_spec(PAGE)
    cache = PagedKVCache(n_layers=model.arch.n_layers, page_size=PAGE, n_pages=per_seq * max_seqs,
                         max_seqs=max_seqs, max_pages_per_seq=per_seq, k_page=spec["k_page"],
                         v_page=spec["v_page"], dtype=spec["dtype"])
    k_pool, v_pool = cache.k_pool, cache.v_pool
    prefill = jax.jit(lambda p, t, pg, k, v: model.decode_prefill(p, t, pg, k, v, page_size=PAGE))
    step = jax.jit(lambda p, k, v, tb, sl, la, ac, te: model.decode_step(
        p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=PAGE, attend=attend))
    slots = list(range(1, 1 + len(prompts)))  # slot 0 stays inactive
    for slot, prompt in zip(slots, prompts):
        cache.reserve(slot, len(prompt) + n_new)
        n = len(prompt) - 1
        bucket = pages_needed(n, PAGE) * PAGE
        toks = np.zeros((bucket,), np.int32)
        toks[:n] = prompt[:-1]
        pages = cache.page_tables[slot, :bucket // PAGE]
        k_pool, v_pool = prefill(params, jnp.asarray(toks), jnp.asarray(pages), k_pool, v_pool)
    seq_lens = np.zeros((max_seqs,), np.int32)
    last = np.zeros((max_seqs,), np.int32)
    active = np.zeros((max_seqs,), bool)
    for slot, prompt in zip(slots, prompts):
        seq_lens[slot], last[slot], active[slot] = len(prompt) - 1, prompt[-1], True
    logits, tokens = [], []
    for _ in range(n_new):
        nxt, lg, k_pool, v_pool = step(params, k_pool, v_pool, jnp.asarray(cache.page_tables),
                                       jnp.asarray(seq_lens), jnp.asarray(last), jnp.asarray(active),
                                       jnp.zeros((max_seqs,), jnp.float32))
        nxt = np.asarray(nxt)
        logits.append(np.asarray(lg)[slots])
        tokens.append(nxt[slots])
        for slot in slots:
            seq_lens[slot] += 1
            last[slot] = nxt[slot]
    return np.stack(logits), np.stack(tokens)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-4), (jnp.bfloat16, 6e-2)])
def test_prefill_then_decode_through_the_latent_pages_agrees_with_the_references_full_forward(dtype, tolerance):
    # ragged lengths, an inactive slot (0) and an empty one (4); the preset's original
    # context is 24, so the contexts of 21+ cross it as they grow and the query's scale changes
    model = _model(dtype)
    params = _weights(model)
    prompts, n_new = _prompts([5, 21, 40, 9]), 6
    with jax.default_matmul_precision("highest"):
        logits, tokens = _serve(model, params, prompts, n_new, max_seqs=6)
    samples = []
    for i, prompt in enumerate(prompts):
        hist = np.concatenate([prompt, tokens[:-1, i]])
        samples.append((hist, np.arange(len(prompt) - 1, len(prompt) - 1 + n_new)))
    ref = REF.run(_config(), 5, samples)["logits"]
    for i in range(len(prompts)):
        gap = np.linalg.norm(logits[:, i] - ref[i], axis=1) / np.linalg.norm(ref[i], axis=1)
        assert gap.max() < tolerance, (i, gap)
    assert any(len(p) - 1 < 24 <= len(p) - 1 + n_new for p in prompts)


def test_the_decode_step_with_the_kernel_equals_the_step_with_its_twin():
    model = _model(jnp.float32)
    params = _weights(model)
    with jax.default_matmul_precision("highest"):
        a, ta = _serve(model, params, _prompts([7, 30]), 4, max_seqs=4)
        b, tb = _serve(model, params, _prompts([7, 30]), 4, max_seqs=4, attend=mla_decode_reference)
    assert np.array_equal(ta, tb) and np.abs(a - b).max() < 1e-4 * np.abs(b).max()


def test_absorbed_equals_expanded_on_one_layer():
    model = _model(jnp.float32)
    p = _weights(model)["layers"][0]["attn"]
    T, r = 37, model.recipe
    h = jax.random.normal(jax.random.PRNGKey(1), (1, T, r.d_model))
    positions = jnp.arange(T)[None]
    with jax.default_matmul_precision("highest"):
        expanded, c_kv, k_pe = model._attn_expanded(p, h, positions)
        # the last position as a decode step over the 36 before it, cached in pages of 8
        n = T - 1
        npg = pages_needed(n, PAGE)
        pad = npg * PAGE - n
        c_pool = jnp.pad(c_kv[0, :n], ((0, pad), (0, 0))).reshape(1, npg, PAGE, -1)
        r_pool = jnp.swapaxes(jnp.pad(k_pe[0, :n], ((0, pad), (0, 0))).reshape(1, npg, PAGE, -1), 2, 3)
        tables = jnp.arange(npg, dtype=jnp.int32)[None]
        for attend in (mla_decode, mla_decode_reference):
            absorbed, c_new, r_new = model._attn_absorbed(
                p, h[0, n:], jnp.array([n]), c_pool, r_pool, tables, 0, attend)
            assert jnp.abs(absorbed[0] - expanded[0, n]).max() < 2e-5 * jnp.abs(expanded).max()
            assert jnp.allclose(c_new[0], c_kv[0, n], atol=1e-6) and jnp.allclose(r_new[0], k_pe[0, n], atol=1e-6)


_TINY, _CELL = (4, 32, 16, PAGE, 12), (32, 256, 64, 128, 11)  # H, R, Dr, page, M


@pytest.mark.parametrize("lens,dims,most,pps", [  # at most `most` pages a step -> `pps` pages a step
    ([0, 1, 8, 37, 88], _TINY, 6, 6),
    ([88, 0, 0, 16, 3], _TINY, 6, 6),
    ([7, 8, 9, 0, 15, 16, 17], _TINY, 6, 6),  # at, under and over a multiple of the page
    ([47, 48, 49, 0, 95, 40, 41], _TINY, 6, 6),  # and of a step's 6 pages; up to 48 the last step is dead
    ([0, 0, 0], _TINY, 6, 6),
    ([37, 0, 88, 95], _TINY, 5, 4),  # a table of 12 in 3 steps of 4
    ([37, 0, 88, 95], _TINY, None, 12),  # the module's own step: the whole table
    ([0, 127, 128, 129, 511, 512, 513, 1300], _CELL, 4, 4),  # the served cell's widths, 11 pages in 3 steps of 4
], ids=["mixed", "zeros-between", "page-edges", "step-edges", "all-empty", "ragged-steps", "one-step",
        "cell-widths"])
def test_mla_decode_in_interpret_mode_equals_its_jnp_twin(lens, dims, most, pps, monkeypatch):
    from theanompi_tpu.ops import pallas_mla as pm

    H, R, Dr, page, M = dims
    if most:
        monkeypatch.setattr(pm, "_STEP_BYTES", most * page * (R + Dr) * 2)
    assert pm._pages_a_step(M, page * (R + Dr) * 2) == pps
    S, L = len(lens), 2
    P = sum(pages_needed(n + 1, page) for n in lens)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    dt = jnp.bfloat16
    ql, qr = jax.random.normal(k[0], (S, H, R)).astype(dt), jax.random.normal(k[1], (S, H, Dr)).astype(dt)
    cn, rn = jax.random.normal(k[2], (S, R)).astype(dt), jax.random.normal(k[3], (S, Dr)).astype(dt)
    cp = jax.random.normal(k[4], (L, P + 1, page, R)).astype(dt)
    rp = jax.random.normal(k[5], (L, P + 1, Dr, page)).astype(dt)
    tables, perm, at = np.full((S, M), P, np.int32), np.random.default_rng(0).permutation(P), 0
    for s, n in enumerate(lens):
        npg = pages_needed(n + 1, page)  # the cached positions and the step's own
        tables[s, :npg] = perm[at:at + npg]
        at += npg
    lens_a = jnp.asarray(lens, jnp.int32)
    for layer in range(L):
        args = (ql, qr, cn, rn, cp, rp, jnp.asarray(tables), lens_a)
        a = mla_decode(*args, layer=layer, scale=0.2).astype(jnp.float32)
        b = mla_decode_reference(*args, layer=layer, scale=0.2).astype(jnp.float32)
        assert jnp.abs(a - b).max() < 2e-2 * jnp.abs(b).max()  # probabilities in bfloat16 for the second product
    # a slot of no cached position attends to its own row alone
    assert jnp.array_equal(a[lens.index(0)], jnp.broadcast_to(cn[lens.index(0)].astype(jnp.float32), (H, R)))
    # the write of every layer's own rows: bit for bit the scatter it replaces, nothing else touched
    wpage = jnp.asarray([tables[s, n // page] for s, n in enumerate(lens)], jnp.int32)
    c_rows, r_rows = jnp.stack([cn, -cn]), jnp.stack([rn, -rn])
    c_out, r_out = mla_cache_write(cp, rp, c_rows, r_rows, wpage, lens_a)
    assert jnp.array_equal(c_out, cp.at[:, wpage, lens_a % page].set(c_rows))
    assert jnp.array_equal(r_out, rp.at[:, wpage, :, lens_a % page].set(jnp.swapaxes(r_rows, 0, 1)))


def test_yarn_frequencies_and_the_softmax_scale_are_the_published_numbers():
    r = MistralSmall4_EP8.default_recipe()
    freq, low, high = yarn_frequencies(r)
    assert (low, high) == (12, 25)
    t = 10000.0 ** (-2 * np.arange(32) / 64)
    assert np.allclose(freq[:13], t[:13], rtol=1e-6) and np.allclose(freq[25:], t[25:] / 128, rtol=1e-6)
    assert np.allclose(freq[18], t[18] * (1 - 6 / 13) + t[18] / 128 * (6 / 13), rtol=1e-6)
    m = 0.1 * np.log(128) + 1
    assert round(m, 4) == 1.4852 and abs(softmax_scale(r) - 128 ** -0.5 * m * m) < 1e-12
    ref_freq, ref_low, ref_high = REF.yarn_frequencies(json.load(open(os.path.join(
        manifest.BENCH_DIR, "configs", "mistral-small-4-decode.json"))))
    assert (ref_low, ref_high) == (12, 25) and np.allclose(ref_freq, freq, rtol=1e-6)


def test_softmax_routing_is_the_references_and_sigmoid_routing_is_as_it_was():
    c = _config()
    h = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    router = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (64, 16))
    idx, w = route_topk(h, router, None, 4, 1.0, scoring="softmax")
    per_expert = jnp.zeros((40, 16)).at[jnp.arange(40)[:, None], idx].add(w)
    assert jnp.allclose(per_expert, REF.route(c, {"router": router}, h), atol=1e-6)
    assert jnp.allclose(jnp.sum(w, -1), 1.0, atol=1e-6)
    bias = jnp.zeros((16,)).at[3].set(10.0)
    idx_s, w_s = route_topk(h, router, bias, 4, 2.0)  # the default: sigmoid, the bias selects only
    assert bool(jnp.all(jnp.any(idx_s == 3, axis=-1))) and jnp.allclose(jnp.sum(w_s, -1), 2.0, atol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        route_topk(h, router, None, 4, 1.0, scoring="tanh")


def test_the_eight_shares_of_the_routed_layer_add_up_to_the_uncut_references_whole_layer():
    # 16 experts over 8 chips, 2 each; the shared expert, which every chip computes alike, counted once
    whole = {**_config(), "num_experts_held": 16, "first_expert_held": 0}
    model = _model(jnp.float32, experts_held=16)
    ffn = _weights(model)["layers"][1]["ffn"]
    h = jax.random.normal(jax.random.PRNGKey(4), (48, 64))
    op = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        sh = ffn["shared"]
        shared = REF.swiglu(sh["w1"], sh["w3"], sh["w2"], h, op)
        uncut = shared + REF.routed(whole, ffn, h, REF.route(whole, ffn, h), op, cap=48)
        total, rows = shared, 0
        for chip in range(8):
            share = _model(jnp.float32, experts_held=2, first_expert=2 * chip)
            mine = {**ffn, "experts": {k: v[2 * chip:2 * chip + 2] for k, v in ffn["experts"].items()}}
            part = share._moe(mine, h, 16) - shared
            total, rows = total + part, rows + int(jnp.sum(jnp.abs(part).sum(-1) > 0))
    assert jnp.abs(total - uncut).max() < 1e-5 * jnp.abs(uncut).max()
    assert rows >= 48 * 4 / 2  # every row's four choices lie on two to four chips


def test_the_latent_cache_costs_640_bytes_a_position_a_layer_at_the_published_widths():
    model = MistralSmall4_EP8()
    spec = model.cache_spec(128)
    assert spec["kind"] == "latent" and spec["donate"]
    assert spec["k_page"] == (128, 256) and spec["v_page"] == (64, 128)
    a_page = (np.prod(spec["k_page"]) + np.prod(spec["v_page"])) * jnp.dtype(spec["dtype"]).itemsize
    assert a_page == 128 * 640  # against 128 x 16,384 B of bfloat16 per-head K and V
    assert model.arch.max_len == 1048576 and model.arch.dtype == jnp.bfloat16
    assert get_model("mistral_small_4_ep8") is MistralSmall4_EP8 and get_model("mistral4_lm") is Mistral4LM


def _engine(model, **kw):
    return DecodeEngine(model, prefill_buckets=(16, 32), kv_pages=48, page_size=PAGE, max_seqs=4,
                        max_new_tokens=8, **kw)


def test_the_engine_serves_it_with_buckets_plus_one_programs_and_donated_pools(tmp_path):
    model = _model(jnp.bfloat16)
    params = _weights(model)
    eng = _engine(model, obs_dir=str(tmp_path))
    eng.set_params(params, {}, 0)
    k0, v0 = eng._cache.k_pool, eng._cache.v_pool
    assert k0.shape == (2, 49, PAGE, 32) and v0.shape == (2, 49, 16, PAGE) and k0.dtype == jnp.bfloat16
    assert eng.warmup() == 3  # two buckets + the one decode program
    assert k0.is_deleted() and v0.is_deleted()  # given to the programs: updated in place, no copy kept
    eng.start()
    prompts = _prompts([6, 17, 30, 12, 33], seed=3)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = [f.result(120).tokens for f in futs]
    assert eng.drain(30) and eng.compile_count == 3
    assert eng._cache.free_list.conserved()
    # the same tokens as the model's own surface, one request at a time
    for prompt, tokens in zip(prompts, got):
        _, alone = _serve(model, params, [prompt], 5, max_seqs=2)
        assert np.array_equal(tokens, alone[:, 0])
    stats = eng.stats()
    assert stats["tmpi_decode_kv_bytes_per_position"] == 2 * (32 + 16) * 2
    assert stats["tmpi_decode_kv_pool_bytes"] == 2 * 49 * PAGE * (32 + 16) * 2
    assert 'tmpi_decode_kv_pool_bytes{kind="latent"}' in eng.registry.to_prometheus()
    with open(tmp_path / "decode.jsonl") as f:
        records = [json.loads(line) for line in f]
    assert records and all(r["cache_kind"] == "latent" for r in records)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "decode.jsonl")) == []


@pytest.mark.parametrize("broken", ["_prefill", "_decode"])
def test_a_donated_program_that_raises_leaves_an_engine_that_serves_the_next_request(broken):
    model = _model(jnp.bfloat16)
    params = _weights(model)
    eng = _engine(model)
    eng.set_params(params, {}, 0)
    eng.warmup()
    program, calls = getattr(eng, broken), []

    def raises_once(*args):
        out = program(*args)  # the pools have gone into the call
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("after the donation")
        return out

    setattr(eng, broken, raises_once)
    eng.start()
    first, second = _prompts([17, 12], seed=5)
    with pytest.raises(RuntimeError, match="after the donation"):
        eng.submit(first, max_new_tokens=4).result(120)
    assert not eng._cache.k_pool.is_deleted() and not eng._cache.v_pool.is_deleted()
    assert eng._cache.pages_used == 0
    got = eng.submit(second, max_new_tokens=4).result(120).tokens
    assert eng.drain(30) and eng.compile_count == 3 and eng._cache.free_list.conserved()
    _, alone = _serve(model, params, [second], 4, max_seqs=2)
    assert np.array_equal(got, alone[:, 0])


@pytest.mark.parametrize("fault", ["state_unchanged", "prefill_unchanged"])
def test_a_program_that_leaves_the_cache_as_it_found_it_reads_not_correct_in_the_runs_own_comparison(fault):
    # the harness's own state_unchanged hands back pools that the donating programs have given away and stops
    # the run; experiments/mla_fault_probe.py plants it with copies, so the cell's rehearsal compares it
    probe = runpy.run_path(os.path.join(os.path.dirname(manifest.BENCH_DIR), "experiments", "mla_fault_probe.py"))
    limits = manifest.load_module("checks", "test_mistral_small_4").TINY_LIMITS
    man, entry, workload, config = manifest.resolve("mistral-small-4-decode-doc8k")
    driver = manifest.load_module("drivers", "decode")
    driver.plant_fault = probe["plant"]
    m = driver.measure({"manifest": man, "cell": entry, "workload": {**workload, "limits": limits},
                        "config": config, "seed": 12, "seconds": 0.4, "trace": False, "tiny": True,
                        "fault": fault, "t_process_start": time.perf_counter()})
    checks = driver.checks_of(m)
    assert not driver.is_correct(checks) and checks["logit_gap"][0] > limits["logit_gap"], checks
    assert m["bad"] == 0 and m["pages_lost"] == 0 and m["compiles_in_window"] == 0
    by_iteration = probe["distributions"](m)["rows"]
    assert by_iteration[-1]["median"] > limits["logit_gap"]  # the rows kept some decode steps in are far
    if fault == "state_unchanged":  # a first token has no decoded row behind it
        assert by_iteration[0]["iteration"] == 1 and by_iteration[0]["largest"] < limits["logit_gap"]


def test_the_dense_lm_keeps_its_pools_its_programs_and_their_count():
    from theanompi_tpu.models.lm import TransformerLMModel

    model = TransformerLMModel(TransformerLMModel.default_recipe().replace(
        input_shape=(64,), d_model=32, n_heads=2, n_layers=2, d_ff=64))
    eng = _engine(model)
    eng.set_params(*model.init(jax.random.PRNGKey(0)), 0)
    k0 = eng._cache.k_pool
    assert k0.shape == eng._cache.v_pool.shape == (2, 49, PAGE, 32) and k0.dtype == jnp.float32  # lane-dense rows: H * hd
    assert eng.cache_kind == "kv" and eng.warmup() == 3
    assert not k0.is_deleted() and eng._cache.k_pool is k0  # not donated, warmup's writes dropped
    assert eng.stats()["tmpi_decode_kv_bytes_per_position"] == 2 * 2 * 32 * 4


def test_a_training_checkpoint_of_bfloat16_leaves_loads_for_serving_bit_for_bit(tmp_path):
    # an .npz keeps bfloat16 as raw 2-byte records; tmpi serve --decode reads it back as the leaves it was
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import latest_checkpoint, save_checkpoint

    model = _model(jnp.bfloat16)
    state = init_train_state(model, jax.random.PRNGKey(3))
    save_checkpoint(str(tmp_path), state, step=7, rng=jax.random.PRNGKey(0))
    eng = _engine(model)
    assert eng.load_initial(str(tmp_path)) == 7 and latest_checkpoint(str(tmp_path), verify=True)
    loaded, made = jax.tree_util.tree_leaves(eng._served.params), jax.tree_util.tree_leaves(state.params)
    assert all(a.dtype == jnp.bfloat16 and jnp.array_equal(a, b) for a, b in zip(loaded, made))
