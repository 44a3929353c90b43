"""The ``evabyte`` stack (EVA attention: an exact window block beside one
compressed key and value a chunk, in one softmax; 8 byte heads) at a small
size on the CPU, against the benchmark's plain reference
(``benchmark/reference/evabyte-decode.py``) on seeded random weights: the full
forward with all 8 heads, prefill then decode through the ENGINE's two
programs across chunk and window-block boundaries, each kernel against its
``jnp`` twin at ragged lengths, a slot used twice, the two kinds of state in
one cache manager, and the other models' programs unchanged."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_checks import names_of  # noqa: F401 - sets the benchmark's import path up
from harness import manifest
from theanompi_tpu.models import get_model
from theanompi_tpu.models.evabyte import EvaByte, EvaByte_Stage4
from theanompi_tpu.ops import pallas_eva as pe
from theanompi_tpu.serve.decode.engine import DecodeEngine
from theanompi_tpu.serve.decode.kvcache import PagedKVCache
from theanompi_tpu.serve.decode.scheduler import DecodeSequence

REF = manifest.load_module("reference", "evabyte-decode")
W, C = 32, 4  # the tiny preset's window block and chunk: a page is one block's 8 compressed rows
BUCKETS = (32, 64, 96)


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "evabyte-decode.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"]}


def _model(dtype=jnp.float32, **over):
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in _config()["recipe_overrides"].items()}
    return EvaByte_Stage4(EvaByte_Stage4.default_recipe().replace(**{**kw, "compute_dtype": dtype, **over}))


def _weights(model, seed=5):
    return jax.jit(model.init)(jax.random.PRNGKey(seed))[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 320, size=n).astype(np.int32) for n in lengths]


def _engine(model, max_seqs=6, **kw):
    return DecodeEngine(model, prefill_buckets=BUCKETS, kv_pages=24, page_size=W, max_seqs=max_seqs,
                        max_new_tokens=12, **kw)


def _serve(eng, params, prompts, n_new):
    """The engine's own two programs, driven as its loop drives them (admit,
    prefill with slot and real length, decode steps), keeping every step's
    logits. -> logits ``[n_new, len(prompts), V]`` and the greedy tokens."""
    seqs = [DecodeSequence(p, max_new_tokens=n_new) for p in prompts]
    for seq in seqs:
        eng._sched.add(seq)
    admitted, _ = eng._sched.admit(0.0)
    assert len(admitted) == len(prompts)
    c = eng._cache
    for seq in admitted:
        pf = eng._sched.prefill_args(seq)
        if pf is not None:
            _, toks, pages = pf
            c.k_pool, c.v_pool = eng._prefill(params, jnp.asarray(toks), jnp.asarray(pages), c.k_pool,
                                              c.v_pool, np.int32(seq.slot), np.int32(seq.n_cache))
    logits, tokens = [], []
    for it in range(n_new):
        nxt, lg, c.k_pool, c.v_pool = eng._decode(params, c.k_pool, c.v_pool, eng._sched.step_arrays(it))
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        logits.append(np.stack([lg[s.slot] for s in seqs]))
        tokens.append(np.asarray([nxt[s.slot] for s in seqs]))
        for s in seqs:
            s.generated.append(int(nxt[s.slot]))
    for s in seqs:
        eng._sched.remove(s.slot, "finished")
    return np.stack(logits), np.stack(tokens)


def _gap(got, ref):
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


# float32: the same arithmetic in another order (2.9e-7 read). bfloat16: the program rounds every matmul's operands,
# q, k, v and the compressed rows to bfloat16 where the reference stays in float32: 0.0040 read at these sizes over
# all 8 heads, beside 0.0038 for the reference with its operands rounded (the witness) and 0.051 for int8.
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 8e-3)])
def test_the_full_forward_agrees_with_the_reference_at_every_position_and_every_head(dtype, tolerance):
    model = _model(dtype)
    params = _weights(model, seed=7)
    hist = _prompts([150], seed=1)[0]  # four window blocks and a part: rows of both kinds from position 32 on
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, {}, jnp.asarray(hist[None]))[0][0], np.float32)
    ref = REF.run(_config(), 7, [(hist, np.arange(150))], heads=8)["logits"][0]
    assert got.shape == ref.shape == (150, 8, 320)
    gap = _gap(got, ref)
    assert gap.max() < tolerance, (np.unravel_index(gap.argmax(), gap.shape), gap.max())
    assert gap[W:].max() > 0  # the compressed rows are in play past the first block


@pytest.mark.parametrize("precision,least", [("bfloat16", 1e-3), ("int8", 2e-2)])
def test_the_reference_in_a_lower_precision_lies_outside_the_float32_tolerance(precision, least):
    hist = _prompts([150], seed=1)[0]
    ref = REF.run(_config(), 7, [(hist, np.arange(150))], heads=8)
    low = REF.run(_config(), 7, [(hist, np.arange(150))], precision=precision, params=ref["init"],
                  heads=8)["logits"][0]
    assert _gap(low, ref["logits"][0]).max() > least > 2e-5


# float32 as above (2.4e-7 read). bfloat16: 0.0033 read: the cache holds bf16 keys, values and compressed rows, as
# the prompt's forms do. Ragged lengths: a context inside its second block that completes chunks while decoding (44),
# one that crosses a window boundary while decoding (60 -> 71: the ring restarts and a page of compressed rows turns
# visible), one that ends EXACTLY on a block boundary (32: the prefill leaves an empty ring), a single byte (no
# prefill at all), one inside its first chunk (5), and one that crosses from its third block into its fourth (93).
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 8e-3)])
def test_prefill_then_decode_through_the_engine_agrees_with_the_references_full_forward(dtype, tolerance):
    model = _model(dtype)
    params = _weights(model)
    eng = _engine(model)
    eng.set_params(params, {}, 0)
    prompts, n_new = _prompts([45, 61, 33, 1, 6, 94]), 12
    with jax.default_matmul_precision("highest"):
        logits, tokens = _serve(eng, eng._served.params, prompts, n_new)
    samples = []
    for i, prompt in enumerate(prompts):
        hist = np.concatenate([prompt, tokens[:-1, i]])
        samples.append((hist, np.arange(len(prompt) - 1, len(prompt) - 1 + n_new)))
    ref = REF.run(_config(), 5, samples)["logits"]
    for i in range(len(prompts)):
        gap = _gap(logits[:, i], ref[i])
        assert gap.max() < tolerance, (i, gap)
    crossing = [p for p in prompts if (len(p) - 1) // W != (len(p) - 2 + n_new) // W]
    assert len(crossing) >= 2 and (len(prompts[2]) - 1) % W == 0
    assert eng.compile_count == len({eng._sched.bucket_for(len(p) - 1) for p in prompts if len(p) > 1}) + 1
    assert eng._cache.free_list.conserved()


def test_the_programs_with_the_kernels_equal_the_programs_with_their_twins():
    model = _model(jnp.float32)
    params = _weights(model)
    tokens = jnp.asarray(_prompts([96], seed=3)[0])
    with jax.default_matmul_precision("highest"):
        a = model._prompt(params, tokens)[0]
        b = model._prompt(params, tokens, attend=pe.eva_prefill_reference)[0]
    assert np.abs(np.asarray(a - b)).max() < 1e-5 * np.abs(np.asarray(b)).max()


def _decode_case(seed=0, L=2, S=6, H=4, D=16, P=20, M=5):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    rng = np.random.default_rng(seed)
    ring = jax.random.normal(keys[0], (L, S, 2, W, H * D))
    k_pages, v_pages = (jax.random.normal(key, (L, P + 1, W // C, H * D)) for key in keys[1:3])
    q, k_new, v_new = (jax.random.normal(key, (S, H * D)) for key in keys[3:])
    tables = jnp.asarray(rng.permutation(P)[:S * M].reshape(S, M) if S * M <= P
                         else rng.integers(0, P, (S, M)), jnp.int32)
    # ragged: nothing cached, inside the first chunk, the last row of the first block, the first of the second
    # (an empty ring, one page), mid-way in the third, the last position a table of 5 pages holds
    lens = jnp.asarray([0, 3, 31, 32, 77, 159][:S], jnp.int32)
    return q, k_new, v_new, ring, k_pages, v_pages, tables, lens


@pytest.mark.parametrize("tile", [8, 32])
def test_eva_decode_equals_its_twin_at_ragged_lengths(tile):
    args = _decode_case(P=40)
    with jax.default_matmul_precision("highest"):
        a = pe.eva_decode(*args, layer=1, n_heads=4, scale=0.25, tile=tile)
        b = pe.eva_decode_reference(*args, layer=1, n_heads=4, scale=0.25)
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    # a slot of no cached position attends over its own row alone
    assert np.abs(np.asarray(a[0]) - np.asarray(args[2])[0]).max() < 1e-6


def test_eva_decode_reads_no_row_past_a_slots_lengths():
    # rows past t % W of the ring and every page past block t // W hold garbage of another occupant: nothing moves
    q, k_new, v_new, ring, k_pages, v_pages, tables, lens = _decode_case(P=40)
    r, w = np.asarray(lens) % W, np.asarray(lens) // W
    dirty_ring = np.asarray(ring).copy()
    dirty_k, dirty_v = np.asarray(k_pages).copy(), np.asarray(v_pages).copy()
    for s in range(len(r)):
        dirty_ring[:, s, :, r[s]:] = 1e4
        for b in range(w[s], tables.shape[1]):
            dirty_k[:, int(tables[s, b])] = -1e4
            dirty_v[:, int(tables[s, b])] = 1e4
    args = (q, k_new, v_new)
    a = pe.eva_decode(*args, ring, k_pages, v_pages, tables, lens, layer=0, n_heads=4, scale=0.25, tile=8)
    b = pe.eva_decode(*args, jnp.asarray(dirty_ring), jnp.asarray(dirty_k), jnp.asarray(dirty_v), tables, lens,
                      layer=0, n_heads=4, scale=0.25, tile=8)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_eva_cache_write_puts_each_row_in_place_and_touches_nothing_else():
    _, _, _, ring, k_pages, v_pages, _, lens = _decode_case()
    L, S, HD = ring.shape[0], ring.shape[1], ring.shape[-1]
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    kv = jax.random.normal(keys[0], (L, S, 2, HD))
    ck, cv = (jax.random.normal(key, (L, S, HD)) for key in keys[1:])
    comp_page = jnp.asarray([20, 3, 7, 20, 11, 2], jnp.int32)  # 20: the scratch page, for two slots
    r = np.asarray(lens) % W
    ring2, k2, v2 = pe.eva_cache_write(ring, k_pages, v_pages, kv, ck, cv, comp_page, lens, chunk=C)
    want = np.asarray(ring).copy()
    for s in range(S):
        want[:, s, :, r[s]] = np.asarray(kv)[:, s]
    assert np.array_equal(np.asarray(ring2), want)
    real = [s for s in range(S) if comp_page[s] != 20]
    for got, pages, rows in ((k2, k_pages, ck), (v2, v_pages, cv)):
        want = np.asarray(pages).copy()
        for s in real:
            want[:, int(comp_page[s]), r[s] // C] = np.asarray(rows)[:, s]
        got = np.asarray(got)
        assert np.array_equal(got[:, :20], want[:, :20])  # every page but the scratch page


@pytest.mark.parametrize("tq,tk", [(8, 8), (16, 8), (8, 32), (32, 16)])
def test_eva_prefill_equals_its_twin_and_holds_no_square_of_the_prompt(tq, tk):
    H, T, D = 2, 96, 16
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k, v = (jax.random.normal(key, (H, T, D)) for key in keys[:3])
    kc, vc = (jax.random.normal(key, (H, T // C, D)) for key in keys[3:])
    with jax.default_matmul_precision("highest"):
        a = pe.eva_prefill(q, k, v, kc, vc, window=W, chunk=C, scale=0.25, tq=tq, tk=tk)
        b = pe.eva_prefill_reference(q, k, v, kc, vc, window=W, chunk=C, scale=0.25)
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    text = jax.jit(lambda *x: pe.eva_prefill(*x, window=W, chunk=C, scale=0.25, tq=tq, tk=tk)).lower(
        q, k, v, kc, vc).as_text()
    assert f"{T}x{T}x" not in text and f"x{T}x{T}" not in text


def test_eva_compress_is_the_references_compression():
    H, D, n = 4, 16, 6
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    k, v = (jax.random.normal(key, (n * C, H, D)) for key in keys[:2])
    mu, phi = (0.25 * jax.random.normal(key, (H, D)) for key in keys[2:])
    with jax.default_matmul_precision("highest"):
        a = pe.eva_compress(k.reshape(n, C, H, D), v.reshape(n, C, H, D), mu, phi)
        b = REF.compress({"chunk_size": C, "head_dim": D}, k, v, mu, phi, lambda x: x)
    assert all(np.abs(np.asarray(x - y)).max() < 1e-5 for x, y in zip(a, b))
    # by hand for one chunk and head: the softmax over the chunk of mu . x and of phi . x - |x|^2 / 2
    x = np.asarray(k)[:C, 0] * D ** -0.25
    wk = np.exp(x @ np.asarray(mu)[0])
    wv = np.exp(x @ np.asarray(phi)[0] - 0.5 * (x * x).sum(-1))
    assert np.allclose(np.asarray(a[0])[0, 0], (wk / wk.sum()) @ np.asarray(k)[:C, 0], atol=1e-5)
    assert np.allclose(np.asarray(a[1])[0, 0], (wv / wv.sum()) @ np.asarray(v)[:C, 0], atol=1e-5)


def test_a_slot_used_twice_never_sees_its_previous_occupants_ring_or_rows():
    # the first occupant decodes across two blocks in slot 0 and leaves its ring and pages behind; the second
    # (shorter, in the same slot and on pages the first held) must read what a fresh engine reads
    model = _model(jnp.float32)
    params = _weights(model)
    eng = _engine(model, max_seqs=1)
    eng.set_params(params, {}, 0)
    first, second = _prompts([90, 40], seed=4)
    with jax.default_matmul_precision("highest"):
        _serve(eng, eng._served.params, [first], 12)
        again, _ = _serve(eng, eng._served.params, [second], 12)
        fresh = _engine(model, max_seqs=1)
        fresh.set_params(params, {}, 0)
        alone, _ = _serve(fresh, fresh._served.params, [second], 12)
    assert np.abs(again - alone).max() < 1e-5 * np.abs(alone).max()
    assert eng._cache.free_list.conserved()


def test_two_kinds_of_state_in_one_cache_manager():
    model = _model(jnp.bfloat16)
    spec = model.cache_spec(W)
    assert spec["kind"] == "compressed" and spec["donate"] and "paged_layers" not in spec
    assert spec["k_page"] == spec["v_page"] == (W // C, 64)
    with pytest.raises(ValueError, match="window"):
        model.cache_spec(16)
    cache = PagedKVCache(n_layers=4, page_size=W, n_pages=12, max_seqs=3, max_pages_per_seq=4,
                         k_page=spec["k_page"], v_page=spec["v_page"], dtype=spec["dtype"], kind=spec["kind"],
                         slots=spec["slots"])
    assert cache.k_pool.shape == (4, 13, 8, 64) and cache.k_pool.dtype == jnp.bfloat16
    assert set(cache.v_pool) == {"v", "ring"} and cache.v_pool["ring"].shape == (4, 3, 2, W, 64)
    assert cache.pool_bytes_by_kind == {"compressed": 2 * 4 * 13 * 8 * 64 * 2, "ring": 4 * 3 * 2 * W * 64 * 2}
    # a position adds its share of a compressed pair in every layer; the ring is bytes a SLOT, not a position
    assert cache.bytes_per_position_by_kind == {"compressed": 2 * 4 * 64 * 2 // C}
    assert cache.max_context == 4 * W and not cache.pools_deleted()
    big = EvaByte_Stage4().cache_spec(2048)
    assert big["k_page"] == (128, 4096) and big["slots"]["ring"] == {"layers": 4, "row": (2, 2048, 4096),
                                                                   "dtype": jnp.bfloat16}
    assert get_model("evabyte_stage4") is EvaByte_Stage4 and get_model("evabyte_lm") is EvaByte


def test_the_rows_a_query_reads_by_hand():
    model = _model(jnp.float32)
    rows = model.cache_rows(np.asarray([0, 31, 32, 95, 100]))
    assert list(rows["eva_window_positions"]) == [1, 32, 1, 32, 5]
    assert list(rows["eva_compressed_rows"]) == [0, 0, 8, 16, 24]
    assert model.visible_share(np.asarray([], np.int32)) == 1.0
    assert model.visible_share(np.asarray([0, 31])) == 1.0  # the first block: every position exactly
    assert abs(model.visible_share(np.asarray([100])) - (5 + 24) / 101) < 1e-12
    # the published sizes at the cell's mean context: 1,024 exact keys and 1,408 compressed rows of 23,552
    big = EvaByte_Stage4()
    t = 11 * 2048 + 1023
    assert abs(big.visible_share(np.asarray([t])) - (1024 + 11 * 128) / (t + 1)) < 1e-12


def test_the_engine_serves_it_on_the_normal_path_and_conserves_pages_and_ring(tmp_path):
    model = _model(jnp.bfloat16)
    params = _weights(model)
    eng = _engine(model, max_seqs=4, obs_dir=str(tmp_path))
    eng.set_params(params, {}, 0)
    k0, held = eng._cache.k_pool, eng._cache.v_pool
    assert eng.warmup() == len(BUCKETS) + 1
    assert k0.is_deleted() and all(a.is_deleted() for a in held.values())  # donated: updated in place
    eng.start()
    # more requests than slots: every slot is used twice, and a second user must not read the first's rows
    prompts = _prompts([50, 17, 70, 1, 33, 90, 6, 64], seed=3)
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    got = [f.result(300).tokens for f in futs]
    assert eng.drain(60) and eng.compile_count == len(BUCKETS) + 1
    cache = eng._cache
    assert cache.free_list.conserved() and cache.pages_used == 0 and not cache.pools_deleted()
    assert {k: v.shape for k, v in cache.v_pool.items()} == {k: v.shape for k, v in held.items()}
    for prompt, tokens in zip(prompts, got):  # the same tokens as each request served ALONE on a fresh engine
        alone = _engine(model, max_seqs=4)
        alone.set_params(params, {}, 0)
        _, want = _serve(alone, alone._served.params, [prompt], 6)
        assert np.array_equal(tokens, want[:, 0]), len(prompt)
    stats, text = eng.stats(), eng.registry.to_prometheus()
    assert stats["tmpi_decode_kv_bytes_per_position"] == 2 * 4 * 64 * 2 // C
    assert stats["tmpi_decode_kv_pool_bytes"] == cache.pool_bytes
    assert stats["tmpi_decode_ring_bytes"] == 4 * 4 * 2 * W * 64 * 2
    assert 0 < stats["tmpi_decode_visible_context_share"] <= 1
    assert stats["tmpi_decode_eva_window_positions"] >= 1 and stats["tmpi_decode_eva_compressed_rows"] >= 0
    for kind in ("compressed", "ring"):
        assert f'tmpi_decode_kv_pool_bytes{{kind="{kind}"}}' in text
    assert 'tmpi_decode_kv_bytes_per_position{kind="ring"}' not in text  # a ring does not grow with the context
    # the sums an iteration kept in the span store, where the benchmark's readers find them by number
    spans = eng._spans
    it = eng._iterations - 1
    assert spans.counted("eva_window_positions", it) >= 1 and spans.counted("eva_compressed_rows", it) is not None
    with open(tmp_path / "decode.jsonl") as f:
        records = [json.loads(line) for line in f]
    assert records and all(r["cache_kind"] == "compressed" for r in records)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "decode.jsonl")) == []


@pytest.mark.parametrize("scope", ["eva_decode", "eva_compress"])
def test_the_decode_program_names_its_parts_and_gathers_no_whole_ring(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c = eng._cache
    lowered = eng._decode.lower(params, c.k_pool, c.v_pool, eng._sched.step_arrays(0))
    assert scope in lowered.as_text(debug_info=True)
    assert "6x32x64" not in lowered.as_text()  # a slot's whole ring, gathered, would be [6, 32, 64]


@pytest.mark.parametrize("scope", ["eva_prefill", "eva_compress"])
def test_the_prefill_program_names_its_parts_and_holds_no_square_of_the_prompt(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c, T = eng._cache, 96
    lowered = eng._prefill.lower(params, jnp.zeros((T,), jnp.int32), jnp.zeros((T // W,), jnp.int32),
                                 c.k_pool, c.v_pool, np.int32(0), np.int32(0))
    assert scope in lowered.as_text(debug_info=True)
    assert f"x{T}x{T}x" not in lowered.as_text() and f"<{T}x{T}x" not in lowered.as_text()


def _other_model(name):
    if name == "lm136m":
        from theanompi_tpu.models.lm import TransformerLMModel

        return TransformerLMModel(TransformerLMModel.default_recipe().replace(
            input_shape=(64,), d_model=32, n_heads=2, n_layers=2, d_ff=64)), 8
    if name == "mistral4":
        from theanompi_tpu.models.mistral4 import Mistral4LM

        return Mistral4LM(), 8
    from theanompi_tpu.models.minicpm_sala import MiniCPMSALA

    return MiniCPMSALA(), 8


@pytest.mark.parametrize("name", ["lm136m", "mistral4", "minicpm_sala"])
def test_the_other_models_engines_compile_as_many_programs_as_before_and_keep_no_row_counts(name):
    model, page = _other_model(name)
    eng = DecodeEngine(model, prefill_buckets=(16, 32), kv_pages=48, page_size=page, max_seqs=4, max_new_tokens=8)
    eng.set_params(*model.init(jax.random.PRNGKey(0)), 0)
    rows = {"lm136m": ["kv_positions"]}.get(name, [])  # the positions its decode kernel copies
    assert (eng._cache_rows is None) == (not rows) and list(eng._g_rows) == rows
    assert eng.warmup() == 3  # two buckets + the one decode program, as before
    eng.start()
    out = eng.generate(_prompts([20])[0] % 64, max_new_tokens=4)
    assert len(out.tokens) == 4 and eng.drain(30) and eng.compile_count == 3
    assert not any("eva_" in k for k in eng.stats()) and "eva_window_positions" not in eng._spans.counts


def test_tmpi_serve_decode_serves_it_from_a_checkpoint_by_its_zoo_name(tmp_path):
    # the normal path: the CLI, a training checkpoint's bfloat16 leaves, the same engine; no side script
    import subprocess
    import sys

    from theanompi_tpu.tools.check_obs_schema import validate_record
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = EvaByte()
    save_checkpoint(str(tmp_path), init_train_state(model, jax.random.PRNGKey(0)), 3, rng=jax.random.PRNGKey(1))
    done = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "serve", "--ckpt-dir", str(tmp_path), "--model",
         "evabyte_lm", "--decode", "--prefill-buckets", "32,64", "--kv-pages", "16", "--page-size", "32",
         "--max-seqs", "4", "--max-new-tokens", "4", "--selftest", "5", "--obs-dir", str(tmp_path / "obs")],
        cwd=os.path.dirname(manifest.BENCH_DIR), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    stats = json.loads(done.stdout.strip().splitlines()[-1])
    assert stats["kind"] == "decode" and stats["params_step"] == 3 and stats["cache_kind"] == "compressed"
    assert stats["metrics"]["tmpi_decode_served_total"] == 5.0 and stats["metrics"]["tmpi_decode_failed_total"] == 0.0
    assert stats["metrics"]["tmpi_decode_kv_pages_out_total"] == stats["metrics"]["tmpi_decode_kv_pages_in_total"]
    assert stats["metrics"]["tmpi_decode_ring_bytes"] > 0 and validate_record(stats) == []
