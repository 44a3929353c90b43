"""The ``minicpm_sala`` stack (block-sparse attention by selection beside
lightning linear attention) at a small size on the CPU, against the
benchmark's plain reference (``benchmark/reference/minicpm-sala-decode.py``) on
seeded random weights: the full forward, prefill then decode through the
ENGINE's two programs across the context where selection starts, the chunked
scan (kernel and twin) against the recurrence, each kernel against its ``jnp``
twin, the three kinds of state in one cache manager, and the other models'
programs unchanged."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_checks import names_of  # noqa: F401 - sets the benchmark's import path up
from harness import manifest
from theanompi_tpu.models import get_model
from theanompi_tpu.models.minicpm_sala import (
    LIGHTNING, SPARSE, MiniCPM_SALA_Stage8, MiniCPMSALA, _rotate, _turn, decay_rates)
from theanompi_tpu.ops import pallas_lightning as pl_lightning
from theanompi_tpu.ops import pallas_sparse as pl_sparse
from theanompi_tpu.serve.decode.engine import DecodeEngine
from theanompi_tpu.serve.decode.kvcache import PagedKVCache
from theanompi_tpu.serve.decode.scheduler import DecodeSequence

REF = manifest.load_module("reference", "minicpm-sala-decode")
PAGE = 8  # the tiny preset's sparse block
# The tiny preset's query sees everything up to position 46 (block 0, the two
# or three blocks of the 16-position window, 2 chosen blocks): from position 47
# on, three or more blocks are left to choose 2 from.
SELECTION_STARTS = 47


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "minicpm-sala-decode.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"]}


def _model(dtype=jnp.float32, **over):
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in _config()["recipe_overrides"].items()}
    return MiniCPM_SALA_Stage8(MiniCPM_SALA_Stage8.default_recipe().replace(
        **{**kw, "compute_dtype": dtype, **over}))


def _weights(model, seed=5):
    return jax.jit(model.init)(jax.random.PRNGKey(seed))[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n).astype(np.int32) for n in lengths]


def _engine(model, **kw):
    return DecodeEngine(model, prefill_buckets=(32, 64, 96), kv_pages=64, page_size=PAGE, max_seqs=4,
                        max_new_tokens=8, **kw)


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


# float32: the two are the same arithmetic in another order (2.4e-7 read). bfloat16: the program rounds every
# matmul's operands, q, k, v and the compressed keys to bfloat16 where the reference stays in float32: 0.0030 read
# at these sizes, beside 0.0024 for the reference with its operands rounded (the witness) and 0.018 for int8.
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 8e-3)])
def test_the_full_forward_agrees_with_the_reference_at_every_position(dtype, tolerance):
    model = _model(dtype)
    params = _weights(model, seed=7)
    hist = _prompts([128], seed=1)[0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, {}, jnp.asarray(hist[None]))[0][0], np.float32)
    ref = REF.run(_config(), 7, [(hist, np.arange(128))])["logits"][0]
    gap = np.linalg.norm(got - ref, axis=1) / np.linalg.norm(ref, axis=1)
    assert gap.max() < tolerance, (int(gap.argmax()), gap.max())
    assert 128 > 2 * SELECTION_STARTS  # most rows choose


@pytest.mark.parametrize("precision,least", [("bfloat16", 1e-3), ("int8", 1e-2)])
def test_the_reference_in_a_lower_precision_lies_outside_the_float32_tolerance(precision, least):
    hist = _prompts([128], seed=1)[0]
    ref = REF.run(_config(), 7, [(hist, np.arange(128))])
    low = REF.run(_config(), 7, [(hist, np.arange(128))], precision=precision, params=ref["init"])["logits"][0]
    gap = np.linalg.norm(low - ref["logits"][0], axis=1) / np.linalg.norm(ref["logits"][0], axis=1)
    assert gap.max() > least > 2e-5


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 1.2e-2)])
def test_prefill_then_decode_through_the_engine_agrees_with_the_references_full_forward(dtype, tolerance):
    # ragged lengths: one context crosses the point where selection starts WHILE decoding (45 -> 52), one is
    # past it from the first step, one is a single token (no prefill at all), one ends inside its first page
    model = _model(dtype)
    params = _weights(model)
    eng = _engine(model)
    eng.set_params(params, {}, 0)
    prompts, n_new = _prompts([45, 90, 1, 6]), 8
    with jax.default_matmul_precision("highest"):
        logits, tokens = _serve(eng, eng._served.params, prompts, n_new)
    samples = []
    for i, prompt in enumerate(prompts):
        hist = np.concatenate([prompt, tokens[:-1, i]])
        samples.append((hist, np.arange(len(prompt) - 1, len(prompt) - 1 + n_new)))
    ref = REF.run(_config(), 5, samples)["logits"]
    for i in range(len(prompts)):
        gap = np.linalg.norm(logits[:, i] - ref[i], axis=1) / np.linalg.norm(ref[i], axis=1)
        assert gap.max() < tolerance, (i, gap)
    assert any(len(p) - 1 < SELECTION_STARTS <= len(p) - 1 + n_new for p in prompts)
    assert eng.compile_count == 4  # the buckets of 32, 64 and 96 and the one decode program
    assert eng._cache.free_list.conserved()


def test_the_programs_with_the_kernels_equal_the_programs_with_their_twins():
    model = _model(jnp.float32)
    params = _weights(model)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, 512, size=96).astype(np.int32))

    def twin(q, k, v, mask, tiles, counts, *, scale, block, tq, tk):
        return pl_sparse.sparse_prefill_reference(q, k, v, mask, scale=scale, block=block)

    with jax.default_matmul_precision("highest"):
        a, _, states = model._prompt(params, tokens, 77)
        b, _, twin_states = model._prompt(params, tokens, 77, attend=twin, scan=pl_lightning.lightning_chunk_reference)
    assert np.abs(np.asarray(a - b)).max() < 1e-5 * np.abs(np.asarray(b)).max()
    for s, t in zip(states, twin_states):  # the states at the real length
        assert np.abs(np.asarray(s - t)).max() < 1e-5 * np.abs(np.asarray(t)).max()


@pytest.mark.parametrize("scan", ["lightning_chunk", "lightning_chunk_reference"])
@pytest.mark.parametrize("n_real", [96, 77, 33, 16, 1])
def test_the_chunked_scan_is_the_recurrence_at_a_real_length_that_is_no_multiple_of_the_chunk(n_real, scan):
    T, H, D = 96, 4, 16
    q, k, v = (jax.random.normal(key, (T, H, D)) for key in jax.random.split(jax.random.PRNGKey(2), 3))
    still = (jnp.ones((T, D // 2)), jnp.zeros((T, D // 2)))  # a rotation by nought: q and k as they are
    with jax.default_matmul_precision("highest"):
        o, last = getattr(pl_lightning, scan)(q, k, v, decay_rates(H), jnp.int32(n_real), 16, still)
        o_ref, last_ref = pl_lightning.lightning_reference(q, k, v, decay_rates(H), n_real)
    assert np.abs(np.asarray(o - o_ref))[:n_real].max() < 1e-4  # the padded rows are never read
    assert np.abs(np.asarray(last - last_ref)).max() < 1e-4  # the state at the REAL length


@pytest.mark.parametrize("scan", ["lightning_chunk", "lightning_chunk_reference"])
def test_the_scan_rotates_q_and_k_as_the_decode_step_does(scan):
    """A scan takes q and k unrotated in fp32 and rotates and rounds them
    itself: as ``_rotate`` then the compute dtype do, outside."""
    T, H, D, n_real = 64, 4, 16, 45
    q, k, v = (jax.random.normal(key, (T, H, D)) for key in jax.random.split(jax.random.PRNGKey(6), 3))
    pos, v = jnp.arange(T), v.astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        o, last = getattr(pl_lightning, scan)(q, k, v, decay_rates(H), jnp.int32(n_real), 16,
                                              turn=_turn(pos, D, 10000.0))
        o_ref, last_ref = pl_lightning.lightning_reference(
            *(_rotate(x, pos, 10000.0).astype(jnp.bfloat16) for x in (q, k)), v, decay_rates(H), n_real)
    o, o_ref = o[:n_real], o_ref[:n_real]
    assert np.abs(np.asarray(o - o_ref)).max() < 1e-5 * np.abs(np.asarray(o_ref)).max()
    assert np.abs(np.asarray(last - last_ref)).max() < 1e-5 * np.abs(np.asarray(last_ref)).max()


def test_the_references_chunks_are_its_recurrence_one_position_at_a_time():
    T, H, D = 128, 4, 16
    q, k, v = (jax.random.normal(key, (T, H, D)) for key in jax.random.split(jax.random.PRNGKey(4), 3))
    decay = REF.decays({"lightning_nh": H})
    assert abs(float(decay[0]) - np.exp(-2.0 ** -2.0)) < 1e-7  # 4 heads: 2^(-8 / 4)
    with jax.default_matmul_precision("highest"):
        a = REF.recurrence_in_chunks(q, k, v, decay, lambda x: x)
        b = REF.recurrence(q, k, v, decay)
    assert np.abs(np.asarray(a - b)).max() < 1e-4


def test_the_decay_of_the_fastest_head_is_0_43_and_a_split_of_its_powers_would_overflow():
    rates = np.asarray(decay_rates(32))
    assert abs(np.exp(-rates[0]) - 0.431) < 1e-3 and np.exp(-rates[31]) > 0.996
    assert rates[0] * 127 > np.log(np.finfo(np.float32).max)  # l^-127 is no float32: the masked matrix is


def test_lightning_step_equals_its_twin_and_starts_a_slot_at_position_0_from_nought():
    S, H, D, L = 3, 8, 16, 2
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    state = jax.random.normal(keys[0], (L, S, H, D, D))
    q, k, v = (jax.random.normal(key, (S, H, D)) for key in keys[1:])
    lens = jnp.asarray([0, 5, 9], jnp.int32)
    o, new = pl_lightning.lightning_step(q, k, v, decay_rates(H), state, lens, layer=1)
    o_ref, new_ref = pl_lightning.lightning_step_reference(q, k, v, decay_rates(H), state, lens, layer=1)
    assert np.abs(np.asarray(o - o_ref)).max() < 1e-5 and np.abs(np.asarray(new - new_ref)).max() < 1e-6
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))  # the other layer's part is untouched
    outer = np.asarray(k)[0][:, :, None] * np.asarray(v)[0][:, None, :]
    assert np.abs(np.asarray(new[1, 0]) - outer).max() < 1e-6  # slot 0: nought, then its own outer product


def _decode_case(seed=0):
    S, G, R, D, page, P, N, L = 3, 2, 4, 16, 8, 20, 5, 2
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    rng = np.random.default_rng(seed)
    pools = [jax.random.normal(key, (L, P + 1, page, G * D)) for key in keys[:2]]
    q = jax.random.normal(keys[2], (S, G, R, D))
    k_new, v_new = (jax.random.normal(key, (S, G, D)) for key in keys[3:])
    blocks = jnp.asarray(np.sort(rng.integers(0, 6, (S, G, N)), -1).astype(np.int32))
    pages = jnp.asarray(rng.integers(0, P, (S, G, N)).astype(np.int32))
    counts = jnp.asarray([[1, 3], [5, 2], [4, 5]], jnp.int32)
    lens = jnp.asarray([0, 37, 44], jnp.int32)
    return q, k_new, v_new, *pools, pages, blocks, counts, lens


def test_sparse_decode_equals_its_twin_over_chosen_pages_counts_and_lengths():
    args = _decode_case()
    a = pl_sparse.sparse_decode(*args, layer=1, scale=0.25)
    b = pl_sparse.sparse_decode_reference(*args, layer=1, scale=0.25)
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    # a slot of no cached position attends over its own row alone
    assert np.abs(np.asarray(a[0]) - np.asarray(args[2])[0][:, None, :]).max() < 1e-6


def test_sparse_cache_write_puts_each_row_into_its_page_and_touches_nothing_else():
    _, _, _, k_pool, v_pool, *_ = _decode_case()
    L, S, W = 2, 3, k_pool.shape[-1]
    rows = [jax.random.normal(key, (L, S, W)) for key in jax.random.split(jax.random.PRNGKey(9), 2)]
    write_page, lens = jnp.asarray([3, 7, 20]), jnp.asarray([0, 37, 44])
    k2, v2 = pl_sparse.sparse_cache_write(k_pool, v_pool, *rows, write_page, lens)
    assert np.array_equal(np.asarray(k2), np.asarray(k_pool.at[:, write_page, lens % 8].set(rows[0])))
    assert np.array_equal(np.asarray(v2), np.asarray(v_pool.at[:, write_page, lens % 8].set(rows[1])))


@pytest.mark.parametrize("tq,tk", [(8, 8), (16, 8), (8, 16)])
def test_sparse_prefill_equals_its_twin_and_visits_only_the_tiles_some_row_sees(tq, tk):
    G, R, T, D, block = 2, 4, 32, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    rng = np.random.default_rng(0)
    q = jax.random.normal(keys[0], (G, R, T, D))
    k, v = (jax.random.normal(key, (G, T, D)) for key in keys[1:])
    own = np.arange(T) // block
    seen = (rng.random((G, T, T // block)) < 0.3) & (np.arange(T // block)[None, None, :] <= own[None, :, None])
    seen[:, np.arange(T), own] = True  # every row sees its own block
    mask = np.zeros((G, T, 128), np.float32)
    mask[:, :, :T // block] = seen
    tiles, counts = pl_sparse.tile_lists(jnp.asarray(mask), tq, tk, block)
    a = pl_sparse.sparse_prefill(q, k, v, jnp.asarray(mask), tiles, counts, scale=0.25, block=block, tq=tq, tk=tk)
    b = pl_sparse.sparse_prefill_reference(q, k, v, jnp.asarray(mask), scale=0.25, block=block)
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    by_tile = seen.reshape(G, T // tq, tq, T // tk, tk // block).any(axis=(2, 4))
    assert np.array_equal(np.asarray(counts), by_tile.sum(-1)) and counts.sum() < G * (T // tq) * (T // tk)
    for g, i in ((0, 1), (1, T // tq - 1)):
        assert list(np.asarray(tiles[g, i, :counts[g, i]])) == list(np.nonzero(by_tile[g, i])[0])


def test_the_visible_blocks_are_the_first_the_windows_and_the_two_best_of_the_rest():
    model = _model(jnp.float32)
    r = model.recipe
    G, R, D, nB = r.n_kv_heads, r.n_heads // r.n_kv_heads, r.head_dim, 12
    q = jax.random.normal(jax.random.PRNGKey(0), (G, R, D))
    ck = jax.random.normal(jax.random.PRNGKey(1), ((nB * 8 - 4) // 2 + 1, G * D))  # whole windows of 4, every 2
    for t, n_seen in ((3, 1), (46, 6), (47, 5), (95, 5)):
        seen = np.asarray(model._visible(q, ck, jnp.int32(t), nB))
        first_local = max(t - 15, 0) // 8
        assert seen[:, 0].all() and seen[:, first_local:t // 8 + 1].all() and not seen[:, t // 8 + 1:].any()
        assert (seen.sum(-1) == n_seen).all(), (t, seen.sum(-1))
    assert model.max_chosen == 1 + 3 + 2  # position 46: blocks 0, 3 to 5 and the two left
    # by hand for one K/V head at t = 95: the softmax over the 46 usable keys, summed over the head's queries,
    # the largest over the five windows that overlap a block (four for block 0), the two best of blocks 1 .. 9
    s = np.einsum("rd,cd->rc", np.asarray(q[0]), np.asarray(ck[:46, :D])) * D ** -0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    P = (p / p.sum(-1, keepdims=True)).sum(0)
    score = [max(P[j] for j in range(46) if 2 * j < 8 * (b + 1) and 2 * j + 4 > 8 * b) for b in range(1, 10)]
    best = set(1 + np.argsort(-np.asarray(score), kind="stable")[:2])
    assert set(np.nonzero(seen[0])[0]) == {0, 10, 11} | best


def test_three_kinds_of_state_in_one_cache_manager():
    model = _model(jnp.bfloat16)
    spec = model.cache_spec(PAGE)
    assert spec["kind"] == "kv" and spec["donate"] and spec["paged_layers"] == 1
    assert spec["k_page"] == spec["v_page"] == (PAGE, 2 * 16)
    with pytest.raises(ValueError, match="sparse block"):
        model.cache_spec(16)
    cache = PagedKVCache(n_layers=spec["paged_layers"], page_size=PAGE, n_pages=24, max_seqs=3,
                         max_pages_per_seq=8, k_page=spec["k_page"], v_page=spec["v_page"],
                         dtype=spec["dtype"], kind=spec["kind"], slots=spec["slots"])
    assert cache.k_pool.shape == (1, 25, PAGE, 32) and cache.k_pool.dtype == jnp.bfloat16
    assert set(cache.v_pool) == {"v", "compressed", "state"}
    assert cache.v_pool["compressed"].shape == (1, 3, 32, 32)  # a row every 2 of 64 positions
    assert cache.v_pool["state"].shape == (3, 3, 4, 16, 16) and cache.v_pool["state"].dtype == jnp.float32
    assert cache.pool_bytes_by_kind == {"kv": 2 * 25 * PAGE * 32 * 2, "compressed": 3 * 32 * 32 * 2,
                                        "state": 3 * 3 * 4 * 16 * 16 * 4}
    assert cache.pool_bytes == sum(cache.pool_bytes_by_kind.values())
    assert cache.bytes_per_position_by_kind == {"kv": 2 * 32 * 2, "compressed": 32 * 2 // 2}
    assert cache.bytes_per_position == 128 + 32 and not cache.pools_deleted()
    # the published widths: 2 x 512 B of K and V a sparse layer, 32 B of compressed key, 2 MB of state a slot a layer
    big = MiniCPM_SALA_Stage8().cache_spec(64)
    assert big["k_page"] == (64, 256) and big["paged_layers"] == 2
    assert big["slots"]["state"] == {"layers": 6, "row": (32, 128, 128), "dtype": jnp.float32}
    assert big["slots"]["compressed"]["positions_per_row"] == 16
    assert get_model("minicpm_sala_stage8") is MiniCPM_SALA_Stage8 and get_model("minicpm_sala_lm") is MiniCPMSALA
    assert MiniCPM_SALA_Stage8().recipe.mixer_types == (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING) * 2


def test_the_engine_serves_it_on_the_normal_path_and_conserves_pages_keys_and_state(tmp_path):
    model = _model(jnp.bfloat16)
    params = _weights(model)
    eng = _engine(model, obs_dir=str(tmp_path))
    eng.set_params(params, {}, 0)
    k0, held = eng._cache.k_pool, eng._cache.v_pool
    assert eng.warmup() == 4  # three buckets + the one decode program
    assert k0.is_deleted() and all(a.is_deleted() for a in held.values())  # donated: updated in place
    eng.start()
    # more requests than slots: every slot is used twice, and a second user must start from a nought state
    prompts = _prompts([50, 17, 70, 1, 33, 90, 6, 64], seed=3)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = [f.result(300).tokens for f in futs]
    assert eng.drain(60) and eng.compile_count == 4
    cache = eng._cache
    assert cache.free_list.conserved() and cache.pages_used == 0 and not cache.pools_deleted()
    assert {k: v.shape for k, v in cache.v_pool.items()} == {k: v.shape for k, v in held.items()}
    # the same tokens as each request served ALONE on a fresh engine
    for prompt, tokens in zip(prompts, got):
        alone = _engine(model)
        alone.set_params(params, {}, 0)
        _, want = _serve(alone, alone._served.params, [prompt], 5)
        assert np.array_equal(tokens, want[:, 0]), len(prompt)
    stats, text = eng.stats(), eng.registry.to_prometheus()
    assert stats["tmpi_decode_kv_bytes_per_position"] == 128 + 32
    assert stats["tmpi_decode_kv_pool_bytes"] == cache.pool_bytes
    assert stats["tmpi_decode_state_bytes"] == 3 * 4 * 4 * 16 * 16 * 4
    assert 0 < stats["tmpi_decode_visible_context_share"] <= 1
    for kind in ("kv", "compressed", "state"):
        assert f'tmpi_decode_kv_pool_bytes{{kind="{kind}"}}' in text
    assert 'tmpi_decode_kv_bytes_per_position{kind="compressed"}' in text
    assert 'tmpi_decode_kv_bytes_per_position{kind="state"}' not in text  # a state does not grow with the context
    with open(tmp_path / "decode.jsonl") as f:
        records = [json.loads(line) for line in f]
    assert records and all(r["cache_kind"] == "kv" for r in records)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "decode.jsonl")) == []


def test_the_share_of_its_context_a_sparse_layer_sees_by_hand():
    model = _model(jnp.float32)
    assert model.visible_share(np.asarray([], np.int32)) == 1.0
    assert model.visible_share(np.asarray([0, 10, 46])) == 1.0  # everything, up to position 46
    # t = 95: blocks 0, 10, 11 and two chosen = 5 blocks of 8 = 40 of 96 positions
    assert abs(model.visible_share(np.asarray([95])) - 40 / 96) < 1e-12
    big = MiniCPM_SALA_Stage8()
    assert big.visible_share(np.asarray([6207])) == 1.0 and big.max_chosen == 98
    assert abs(big.visible_share(np.asarray([16383])) - (64 + 2048 + 64 * 64) / 16384) < 1e-12


@pytest.mark.parametrize("scope", ["sparse_select", "sparse_decode", "lightning_step"])
def test_the_decode_program_names_its_parts(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c = eng._cache
    text = eng._decode.lower(params, c.k_pool, c.v_pool, eng._sched.step_arrays(0)).as_text(debug_info=True)
    assert scope in text


@pytest.mark.parametrize("scope", ["sparse_select", "sparse_prefill", "lightning_chunk"])
def test_the_prefill_program_names_its_parts_and_holds_no_square_of_the_prompt(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c, T = eng._cache, 96
    lowered = eng._prefill.lower(params, jnp.zeros((T,), jnp.int32), jnp.zeros((T // PAGE,), jnp.int32),
                                 c.k_pool, c.v_pool, np.int32(0), np.int32(0))
    assert scope in lowered.as_text(debug_info=True)
    assert f"x{T}x{T}x" not in lowered.as_text() and f"<{T}x{T}x" not in lowered.as_text()


def test_the_prefill_program_holds_no_state_a_chunk():
    """The scan is one kernel a lightning layer: no ``[N, H, D, D]`` float32
    (a state a chunk and head) is made outside it, where the ``jnp`` form
    makes two a layer."""
    model = _model(jnp.bfloat16)
    r, T = model.recipe, 96
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    square = f"{T // r.chunk}x{r.n_heads}x{r.head_dim}x{r.head_dim}xf32"

    def text(scan):
        return jax.jit(lambda p, t: model._prompt(p, t, 77, scan=scan)).lower(
            params, jnp.zeros((T,), jnp.int32)).as_text()

    assert square not in text(pl_lightning.lightning_chunk)
    assert square in text(pl_lightning.lightning_chunk_reference)  # the test sees what it looks for


def _other_engine(name):
    if name == "lm136m":
        from theanompi_tpu.models.lm import TransformerLMModel

        model = TransformerLMModel(TransformerLMModel.default_recipe().replace(
            input_shape=(64,), d_model=32, n_heads=2, n_layers=2, d_ff=64))
        return model, (2, 49, PAGE, 32), jnp.float32, "kv"
    from theanompi_tpu.models.mistral4 import Mistral4LM

    return Mistral4LM(), (2, 49, PAGE, 32), jnp.bfloat16, "latent"


@pytest.mark.parametrize("name", ["lm136m", "mistral4"])
def test_the_other_models_engines_keep_their_pools_their_programs_and_their_count(name):
    model, k_shape, dtype, kind = _other_engine(name)
    eng = DecodeEngine(model, prefill_buckets=(16, 32), kv_pages=48, page_size=PAGE, max_seqs=4, max_new_tokens=8)
    eng.set_params(*model.init(jax.random.PRNGKey(0)), 0)
    c = eng._cache
    assert c.k_pool.shape == k_shape and c.k_pool.dtype == dtype and eng.cache_kind == kind
    assert isinstance(c.v_pool, jax.Array) and c.pool_bytes_by_kind.keys() == {kind}  # two arrays, nothing a slot
    assert not eng._slot_state and eng._visible_share is None
    assert eng.warmup() == 3  # two buckets + the one decode program, as before
    eng.start()
    out = eng.generate(_prompts([20])[0] % 64, max_new_tokens=4)
    assert len(out.tokens) == 4 and eng.drain(30) and eng.compile_count == 3
    # the prefill still takes five arguments: no slot, no length
    assert "tmpi_decode_visible_context_share" not in eng.stats()


def test_tmpi_serve_decode_serves_it_from_a_checkpoint_by_its_zoo_name(tmp_path):
    # the normal path: the CLI, a training checkpoint's bfloat16 leaves, the same engine; no side script
    import subprocess
    import sys

    from theanompi_tpu.tools.check_obs_schema import validate_record
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = MiniCPMSALA()
    save_checkpoint(str(tmp_path), init_train_state(model, jax.random.PRNGKey(0)), 3, rng=jax.random.PRNGKey(1))
    done = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "serve", "--ckpt-dir", str(tmp_path), "--model",
         "minicpm_sala_lm", "--decode", "--prefill-buckets", "16,64", "--kv-pages", "64", "--page-size", "8",
         "--max-seqs", "4", "--max-new-tokens", "4", "--selftest", "5", "--obs-dir", str(tmp_path / "obs")],
        cwd=os.path.dirname(manifest.BENCH_DIR), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    stats = json.loads(done.stdout.strip().splitlines()[-1])
    assert stats["kind"] == "decode" and stats["params_step"] == 3 and stats["cache_kind"] == "kv"
    assert stats["metrics"]["tmpi_decode_served_total"] == 5.0 and stats["metrics"]["tmpi_decode_failed_total"] == 0.0
    assert stats["metrics"]["tmpi_decode_kv_pages_out_total"] == stats["metrics"]["tmpi_decode_kv_pages_in_total"]
    assert stats["metrics"]["tmpi_decode_state_bytes"] > 0 and validate_record(stats) == []
