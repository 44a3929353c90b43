"""``ops/pallas_paged.py kv_decode``, the served LM's decode attention over
its K/V pages, against its ``jnp`` twin (the gather through the page table
that the decode step ran before it), in the Pallas interpreter; and the
count of the positions it copies, the LM's ``cache_rows``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops import pallas_paged
from theanompi_tpu.ops.pallas_paged import kv_decode, kv_decode_reference
from theanompi_tpu.serve.decode.kvcache import pages_needed

M, H, HD = 6, 2, 16  # table pages, heads, head size: rows of 32 lanes


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("pps", [1, 3, M], ids=["one-page-a-step", "three-pages-a-step", "one-step"])
@pytest.mark.parametrize("page", [4, 16])
def test_kv_decode_in_interpret_mode_equals_its_jnp_twin(page, pps, dtype, monkeypatch):
    D = H * HD
    page_bytes = page * D * 4 * 2  # a page of fp32 K and of V
    if pps < M:
        monkeypatch.setattr(pallas_paged, "_STEP_BYTES", pps * page_bytes)
    assert pallas_paged.pages_a_step(M, page_bytes) == pps
    # no cached position, one, a page, a page and one, the whole table, a mixed
    # length; the last slot is inactive: no length, every entry the scratch page
    lens = [0, 1, page, page + 1, M * page, 3 * page + 2, 0]
    S, L = len(lens), 2
    P = sum(min(M, pages_needed(n + 1, page)) for n in lens)
    k = jax.random.split(jax.random.PRNGKey(page * 10 + pps), 5)
    q = jax.random.normal(k[0], (S, D)).astype(dtype)
    # cached and new rows are values of the compute dtype, held in the pool's fp32
    kn, vn, kp, vp = (jax.random.normal(kk, shape).astype(dtype).astype(jnp.float32) for kk, shape in zip(
        k[1:], ((S, D), (S, D), (L, P + 1, page, D), (L, P + 1, page, D))))
    tables, perm, at = np.full((S, M), P, np.int32), np.random.default_rng(page).permutation(P), 0
    for s, n in enumerate(lens[:-1]):
        npg = min(M, pages_needed(n + 1, page))  # the cached positions and the step's own
        tables[s, :npg] = perm[at:at + npg]
        at += npg
    args = (q, kn, vn, kp, vp, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
    for layer in range(L):
        a = kv_decode(*args, layer=layer, n_heads=H, scale=HD ** -0.5).astype(jnp.float32)
        b = kv_decode_reference(*args, layer=layer, n_heads=H, scale=HD ** -0.5).astype(jnp.float32)
        assert a.shape == (S, D) and a.dtype == jnp.float32
        # the same products at the same precision: apart by the order of the sums
        # and, for a bfloat16 output, one rounding of it
        tol = 2 ** -7 if dtype == jnp.bfloat16 else 1e-5
        assert jnp.abs(a - b).max() <= tol * jnp.abs(b).max(), layer
    # a slot of no cached position attends to its own row alone, active or not
    for s in (0, S - 1):
        assert jnp.array_equal(a[s], vn[s].astype(dtype).astype(jnp.float32))


def test_the_lm_counts_the_positions_of_its_live_pages_and_the_engine_shows_them():
    from theanompi_tpu.models.lm import TransformerLMModel
    from theanompi_tpu.serve.decode.engine import DecodeEngine

    model = TransformerLMModel(TransformerLMModel.default_recipe().replace(
        input_shape=(64,), d_model=32, n_heads=2, n_layers=2, d_ff=64))
    model.cache_spec(16)
    rows = model.cache_rows(np.asarray([0, 1, 16, 17]))
    assert list(rows) == ["kv_positions"] and list(rows["kv_positions"]) == [0, 16, 16, 32]
    model.cache_spec(8)
    assert list(model.cache_rows(np.asarray([0, 1, 16, 17]))["kv_positions"]) == [0, 8, 16, 24]
    eng = DecodeEngine(model, prefill_buckets=(16, 32), kv_pages=48, page_size=8, max_seqs=4, max_new_tokens=8)
    eng.set_params(*model.init(jax.random.PRNGKey(0)), 0)
    eng.warmup()
    eng.start()
    prompt = np.random.default_rng(0).integers(0, 64, size=20).astype(np.int32)
    assert len(eng.generate(prompt, max_new_tokens=4).tokens) == 4 and eng.drain(30)
    # the last step's 22 cached positions (19 of the prompt, 3 generated): three pages of 8
    assert eng.stats()["tmpi_decode_kv_positions"] == 24
    assert eng._spans.counted("kv_positions", eng._iterations - 1) == 24


def test_tensor_sharded_serving_runs_the_kernel_on_every_device_and_decodes_the_full_forwards_tokens():
    """``tmpi serve --decode --shard tensor`` on two devices: the params
    Megatron-sharded, the pools whole on each device. The decode step runs
    ``kv_decode`` in a ``shard_map`` (the compiler does not partition a Mosaic
    kernel), the pools come back as they went in, so the engine compiles no
    more programs than on one device, and greedy tokens are the full
    forward's."""
    from jax.sharding import Mesh

    from test_decode_correctness import oracle_next
    from theanompi_tpu.models.lm import TransformerLMModel
    from theanompi_tpu.parallel.recipe import ShardingRecipe
    from theanompi_tpu.serve.decode.engine import DecodeEngine

    model = TransformerLMModel(TransformerLMModel.default_recipe().replace(
        input_shape=(64,), num_classes=32, d_model=32, n_heads=2, n_layers=2, d_ff=64))
    recipe = ShardingRecipe.serve_tensor(model, mesh=Mesh(np.array(jax.devices()[:2]), ("model",)))
    eng = DecodeEngine(model, prefill_buckets=(8,), kv_pages=32, page_size=4, max_seqs=4,
                       max_new_tokens=6, sharding=recipe)
    params, state = model.init(jax.random.PRNGKey(0))
    eng.set_params(params, state, 0)
    assert eng._served.params["blocks"][0]["qkv"].sharding.spec[2] == "model"
    assert eng.warmup() == 2
    eng.start()
    prompts = [np.asarray(p, np.int32) for p in ([3, 9, 1, 4, 7], [5, 2])]
    try:
        got = [f.result(60).tokens for f in [eng.submit(p, max_new_tokens=6) for p in prompts]]
    finally:
        assert eng.drain(60)
    assert eng.compile_count == 2 and eng._cache.k_pool.sharding.is_fully_replicated
    for p, toks in zip(prompts, got):
        ctx = [int(t) for t in p]
        for i, tok in enumerate(toks):
            assert tok == oracle_next(model.arch, params, ctx), (list(p), i)
            ctx.append(tok)
