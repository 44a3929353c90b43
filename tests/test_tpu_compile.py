"""The main path's Pallas kernels and the one-chip LM train step, compiled
at real widths for a DESCRIBED TPU v5e (no chip attached): what Mosaic or
XLA:TPU would refuse on the chip, it refuses here, at no chip time.

Interpret-mode tests cannot see any of this (tile alignment, VMEM budget,
HBM fit). Nothing runs: a pass is not a chip run and says nothing about
results or times.

The topology is described inside a module-scoped fixture of THIS file only
(never at import, never in conftest.py): the process that describes it
loads libtpu and keeps it until it exits, so under pytest-xdist exactly one
worker may do so. The kernels ask ``interpret_mode()`` which backend is
live and would take their CPU branch here, so each test steers the name
its kernel module imported."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """conftest.py turns the persistent cache on, and a described-chip
    executable written to it cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _mosaic(monkeypatch, module):
    monkeypatch.setattr(module, "_interpret", lambda: False)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_flash_attention_forward_and_backward_at_the_136m_shape(
        one_chip, no_persistent_cache, monkeypatch):
    """B=8 H=12 T=1024 D=64 bf16 causal, default 512x512 blocks: D=64 is
    half a lane tile, and the ONE backward kernel keeps the query side and
    the fp32 ``dq`` it revisits VMEM-resident."""
    from theanompi_tpu.ops import pallas_attention as pa

    _mosaic(monkeypatch, pa)
    q = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=one_chip)

    def attend(q, k, v):
        return pa.flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    fwd = jax.jit(attend).lower(q, q, q).compile()
    assert _kernels(fwd) == 1
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert _kernels(bwd) == 2  # the forward, kept for the residuals, and flash_bwd
    assert "flash_bwd" in bwd.as_text() and "flash_bwd_2d" not in bwd.as_text()


def test_fused_update_grid_branch_on_a_ragged_leaf(
        one_chip, no_persistent_cache, monkeypatch):
    """A leaf whose size is no multiple of 512*128: the on-TPU row grid,
    which interpret mode never takes (ops/pallas_update._block_rows)."""
    from theanompi_tpu.ops import pallas_update as pu

    _mosaic(monkeypatch, pu)
    n = 9216 * 4096 + 12345  # AlexNet fc6 and a ragged tail
    assert n % (512 * 128)
    p = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)

    def update(p, v, g):
        return pu.fused_update_leaf(p, v, g, 0.01, 1.0, momentum=0.9,
                                    weight_decay=5e-4, nesterov=False)

    assert _kernels(jax.jit(update).lower(p, p, p).compile()) == 1


@pytest.mark.parametrize("rows", [
    8 * 2 ** 20 // 512,   # an 8 MB gradient bucket
    9216 * 4096 // 128 + 8,  # a whole AlexNet fc6 leaf, ragged last block
])
def test_int8_block_quant_round_trip(one_chip, no_persistent_cache,
                                     monkeypatch, rows):
    """One block per leaf was refused from 32 MB up (scoped VMEM); the
    row grid must take any leaf the codec hands it."""
    from theanompi_tpu.ops import pallas_quant as pq

    _mosaic(monkeypatch, pq)
    x = jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip)

    def round_trip(x):
        return pq.dequantize_int8_block(*pq.quantize_int8_block(x))

    assert _kernels(jax.jit(round_trip).lower(x).compile()) == 2


def test_maxpool3x3_forward_and_backward(one_chip, no_persistent_cache,
                                         monkeypatch):
    """The opt-in (TMPI_PALLAS_POOL=1) GoogLeNet inception pool, off the
    main path, at an inception-3 shape."""
    from theanompi_tpu.ops import pallas_pool as pp

    _mosaic(monkeypatch, pp)
    x = jax.ShapeDtypeStruct((64, 28, 28, 192), jnp.bfloat16,
                             sharding=one_chip)

    def loss(x):
        return pp.maxpool3x3_s1(x).astype(jnp.float32).sum()

    assert _kernels(jax.jit(jax.grad(loss)).lower(x).compile()) == 2


def test_lm_136m_train_step_fits_one_v5e(topo, one_chip, no_persistent_cache,
                                         monkeypatch):
    """The whole jitted BSP-1 step of TransformerLM_136M (the program
    chip_smoke.py's train-lm phase runs), from eval_shape shapes: it
    compiles with its 24 attention kernels (a forward and ONE backward a
    layer) and fits 16 GB of HBM."""
    from theanompi_tpu.models.lm import TransformerLM_136M
    from theanompi_tpu.ops import pallas_attention as pa
    from theanompi_tpu.parallel.bsp import make_bsp_train_step
    from theanompi_tpu.train import init_train_state

    _mosaic(monkeypatch, pa)
    model = TransformerLM_136M()
    r = model.recipe

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(described, jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0))))
    key = described(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((r.batch_size, *r.input_shape), jnp.int32,
                                  sharding=one_chip)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    compiled = make_bsp_train_step(model, mesh).lower(
        state, tokens, tokens, key).compile()

    assert _kernels(compiled) == 2 * r.n_layers
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    n_params = sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(state.params))
    assert 130e6 < n_params < 140e6
    assert live < V5E_HBM_BYTES, f"{live / 1e9:.2f} GB does not fit one v5e"


def test_grouped_matmuls_forward_and_backward_at_the_trinity_mini_shape(
        one_chip, no_persistent_cache, monkeypatch):
    """One routed projection of the cut Trinity-Mini layer: 8,192 tokens x
    8 choices in the worst-case buffer, 16 experts of 2048 x 1024, bf16,
    256-row tiles: the scalar-prefetched tile map, the transposed-weight
    form (dX) and the accumulating ``moe_tgmm`` (dW)."""
    from theanompi_tpu.ops import pallas_moe as pm

    _mosaic(monkeypatch, pm)
    M = pm.padded_rows(8192 * 8, 16, 256)
    x = jax.ShapeDtypeStruct((M, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((16, 2048, 1024), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)

    def loss(x, w, sizes):
        return pm.gmm(x, w, sizes).astype(jnp.float32).sum()

    fwd = jax.jit(pm.gmm).lower(x, w, sizes).compile()
    assert _kernels(fwd) == 1
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w, sizes).compile()
    assert _kernels(bwd) == 2  # dX + dW (a sum's gradient needs no forward value)


@pytest.mark.parametrize("window", [2048, None], ids=["window_2048", "full"])
def test_flash_attention_at_8192_tokens_with_grouped_heads(
        one_chip, no_persistent_cache, monkeypatch, window):
    """T=8192, 32 query heads over 4 K/V heads of 128, bf16: the forward
    keeps one head's K and V whole in VMEM, the backward is ONE kernel on
    the 2-D grid (under a window only the window's blocks long) whose fp32
    ``dq``, 4 MB a buffer, stays whole in VMEM under the limit the call
    gives."""
    from theanompi_tpu.ops import pallas_attention as pa

    _mosaic(monkeypatch, pa)
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return pa.flash_attention(q, k, v, causal=True,
                                  window=window).astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    assert _kernels(bwd) == 2
    assert "flash_bwd_2d" in bwd.as_text()
    assert pa._bwd_2d_vmem_bytes(8192, 128) == (8 << 20) + (24 << 20)


@pytest.mark.slow  # 52 s of a many-threaded compile: run it before a chip call, not in tier-1
def test_trinity_mini_cut_train_step_fits_one_v5e(topo, one_chip,
                                                  no_persistent_cache, monkeypatch):
    """The whole jitted BSP-1 step of TrinityMini_EP8 (the benchmark's cell
    ``trinity-mini-bsp1-train8k``): 705 M parameters with fp32 gradients and
    Adam moments, 8,192 tokens, remat per layer; 3 flash (the forward, its
    recompute, the one backward) and 12 grouped kernels a layer."""
    from theanompi_tpu.models.afmoe import TrinityMini_EP8
    from theanompi_tpu.ops import pallas_attention as pa
    from theanompi_tpu.ops import pallas_moe as pm
    from theanompi_tpu.parallel.bsp import make_bsp_train_step
    from theanompi_tpu.train import init_train_state

    _mosaic(monkeypatch, pa)
    _mosaic(monkeypatch, pm)
    model = TrinityMini_EP8()
    r = model.recipe

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(described, jax.eval_shape(
        lambda: init_train_state(model, jax.random.PRNGKey(0))))
    key = described(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((r.batch_size, *r.input_shape), jnp.int32,
                                  sharding=one_chip)
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    compiled = make_bsp_train_step(model, mesh).lower(
        state, tokens, tokens, key).compile()

    assert _kernels(compiled) == 3 * len(model.kinds) + 12 * model.n_routed
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 11e9 < live < 14e9, f"{live / 1e9:.2f} GB"


def _mistral_small_4_decode(one_chip, monkeypatch):
    """The served model at its published widths, its engine's pools and the
    shapes of a decode step, all as shapes on the described chip."""
    from theanompi_tpu.models.mistral4 import MistralSmall4_EP8
    from theanompi_tpu.ops import pallas_attention as pa
    from theanompi_tpu.ops import pallas_mla as pm
    from theanompi_tpu.ops import pallas_moe as pg

    for module in (pa, pm, pg):
        _mosaic(monkeypatch, module)
    model, page, pages, S = MistralSmall4_EP8(), 128, 2176, 32

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    spec = model.cache_spec(page)
    pools = [jax.ShapeDtypeStruct((6, pages + 1, *spec[k]), jnp.bfloat16, sharding=one_chip)
             for k in ("k_page", "v_page")]
    slots = {d: jax.ShapeDtypeStruct((S,), d, sharding=one_chip) for d in (jnp.int32, jnp.bool_, jnp.float32)}
    tables = jax.ShapeDtypeStruct((S, 69), jnp.int32, sharding=one_chip)
    return model, page, params, pools, slots, tables


def test_mistral_small_4_decode_step_updates_its_donated_pools_in_place(
        one_chip, no_persistent_cache, monkeypatch):
    """32 slots over a 1.07 GB latent pool, 6 layers at the published
    widths: six ``mla_decode`` calls, one ``mla_cache_write``, eighteen
    grouped products; the donated pools alias the outputs and NO copy of a
    pool is made (a scatter of single positions made the compiler re-lay a
    whole pool out twice a step; ``[page, 64]`` rotated pages a copy a layer)."""
    model, page, params, pools, slots, tables = _mistral_small_4_decode(one_chip, monkeypatch)

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, *pools, tables, slots[jnp.int32], slots[jnp.int32], slots[jnp.bool_],
        slots[jnp.float32]).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 6 + 1 + 18
    assert "mla_decode" in text and "mla_cache_write" in text and "moe_gmm" in text
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    assert pool_bytes == 2177 * 128 * 3840 and mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.1e9  # 19 MB; a copy of a pool would be 0.2 or 0.9 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.slow
def test_mistral_small_4_prefill_of_8192_positions_fits_beside_its_weights(
        one_chip, no_persistent_cache, monkeypatch):
    """The expanded prefill of one 8,192 bucket: flash attention at 32 heads
    of 128, the grouped products over 36,864 buffer rows, whole pages
    written to the donated pools; 0.77 GB of temporaries beside 5.75 GB of
    weights and the pools."""
    model, page, params, pools, slots, _ = _mistral_small_4_decode(one_chip, monkeypatch)
    tokens = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip)
    pages = jax.ShapeDtypeStruct((8192 // page,), jnp.int32, sharding=one_chip)

    def prefill(p, t, pg, k, v):
        return model.decode_prefill(p, t, pg, k, v, page_size=page)

    mem = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, tokens, pages, *pools).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2177 * 128 * 3840 and mem.temp_size_in_bytes < 1.5e9


def _lm136m_decode(one_chip):
    """The cell ``lm136m-decode-closed`` as shapes on the described chip: the
    136M model, its engine's two fp32 pools of 1,536 pages + scratch as the
    model's ``cache_spec`` shapes them, 24 slots of 64 pages."""
    from theanompi_tpu.models.lm import TransformerLM_136M

    model, page, pages, S, M = TransformerLM_136M(), 16, 1536, 24, 64

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    spec = model.cache_spec(page)
    assert spec["kind"] == "kv" and not spec["donate"]
    pools = [on_chip((model.arch.n_layers, pages + 1, *spec[k]), spec["dtype"])
             for k in ("k_page", "v_page")]
    assert all(p.shape == (12, 1537, 16, 768) and p.dtype == jnp.float32 for p in pools)
    return model, page, params, pools, on_chip, (S, M)


def _assert_no_relayout_of_a_pool(compiled, pools):
    """What PR 34 took out must stay out: minor dimensions ``[12, 64]`` fill
    no ``(8, 128)`` tile and cost 4 whole-pool copies, 48 layer-sized copies
    and 7.27 GB of temporaries a decode step (4 and 1.85 GB a prefill),
    donated or not. Left: the ONE copy a pool that an undonated argument
    forces."""
    pool, layer = tuple(pools[0].shape), tuple(pools[0].shape[1:])
    whole, layers, other = 0, [], []
    for shape, op in re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", compiled.as_text()):
        dims = tuple(int(d) for d in shape.split(","))
        if dims in (layer, (1, *layer)):
            layers.append((op, dims))
        elif op == "copy" and dims == pool:
            whole += 1
        elif op == "copy" and math.prod(dims) >= math.prod(layer):
            other.append(dims)
    assert not layers, f"results shaped like a layer of a pool: {layers}"
    assert not other, f"copies as large as a layer of a pool: {other}"
    assert whole <= len(pools), f"{whole} whole-pool copies"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1e9, f"{mem.temp_size_in_bytes / 1e9:.2f} GB of temporaries"
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 6e9
    assert compiled.as_text().count(" scatter(") == len(pools)  # ONE write a pool


def test_lm136m_decode_step_copies_each_undonated_pool_once_and_no_layer_of_it(
        one_chip, no_persistent_cache, monkeypatch):
    """24 slots x 64 pages over two 0.906 GB pools: one ``kv_decode`` call a
    layer reads each slot's live pages straight out of the pools as they
    stood (no gather by page, no slot's rows at the table's whole length),
    whole 768-lane rows throughout (a reshape of the cached rows to
    ``[12, 64]`` re-lays them into padded tiles: 0.42 GB of temporaries
    where 0.01 GB stand), one scatter a pool after the last layer."""
    from theanompi_tpu.ops import pallas_paged

    _mosaic(monkeypatch, pallas_paged)
    model, page, params, pools, on_chip, (S, M) = _lm136m_decode(one_chip)

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step).lower(
        params, *pools, on_chip((S, M), jnp.int32), on_chip((S,), jnp.int32),
        on_chip((S,), jnp.int32), on_chip((S,), jnp.bool_), on_chip((S,), jnp.float32)).compile()
    _assert_no_relayout_of_a_pool(compiled, pools)
    text = compiled.as_text()
    assert "f32[24,1024,12,64]" not in text  # a slot's gathered rows, by heads
    assert "kv_decode" in text and _kernels(compiled) == model.arch.n_layers
    assert "[1536,16,768]" not in text  # the gather of every slot's whole table, of K or V
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_lm136m_decode_step_compiles_tensor_sharded_over_four_chips(
        topo, no_persistent_cache, monkeypatch):
    """``tmpi serve --decode --shard tensor`` on a 2x2 v5e host: the params
    Megatron-sharded and the pools whole on every chip
    (``ShardingRecipe.serve_tensor``). The compiler does not partition a
    Mosaic kernel (lowered for four devices unmapped, ``kv_decode`` is
    refused), so each chip runs it over all heads on its own copy of the
    pools: the same 12 calls, copies and scatters as on one chip."""
    from jax.sharding import NamedSharding

    from theanompi_tpu.ops import pallas_paged
    from theanompi_tpu.parallel.recipe import ShardingRecipe

    _mosaic(monkeypatch, pallas_paged)
    model, page, params, pools, _, (S, M) = _lm136m_decode(SingleDeviceSharding(topo.devices[0]))
    recipe = ShardingRecipe.serve_tensor(model, mesh=Mesh(np.array(topo.devices), ("model",)))
    specs = jax.tree_util.tree_leaves(recipe.roles["params"], is_leaf=lambda x: isinstance(x, P))

    def placed(a, spec=P()):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(recipe.mesh, spec))

    leaves, tree = jax.tree_util.tree_flatten(params)
    params = jax.tree_util.tree_unflatten(tree, [placed(a, s) for a, s in zip(leaves, specs)])
    assert any(s != P() for s in specs)
    pools = [placed(p) for p in pools]

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step).lower(params, *pools, *(placed(jax.ShapeDtypeStruct(shape, dtype)) for shape, dtype in (
        ((S, M), jnp.int32), ((S,), jnp.int32), ((S,), jnp.int32), ((S,), jnp.bool_), ((S,), jnp.float32)))).compile()
    _assert_no_relayout_of_a_pool(compiled, pools)
    text = compiled.as_text()
    assert "kv_decode" in text and _kernels(compiled) == model.arch.n_layers
    assert "[1536,16,768]" not in text  # no gather of every slot's whole table


def test_lm136m_prefill_writes_each_pool_once(one_chip, no_persistent_cache):
    """One prompt of a 512 bucket: the forward, then all layers' pages to
    each pool in one write; 0.03 GB of temporaries."""
    model, page, params, pools, on_chip, _ = _lm136m_decode(one_chip)
    bucket = 512

    def prefill(p, t, pg, k, v):
        return model.decode_prefill(p, t, pg, k, v, page_size=page)

    compiled = jax.jit(prefill).lower(
        params, on_chip((bucket,), jnp.int32), on_chip((bucket // page,), jnp.int32), *pools).compile()
    _assert_no_relayout_of_a_pool(compiled, pools)


def _minicpm_sala_decode(one_chip, monkeypatch):
    """The cell ``minicpm-sala-decode-doc16k`` as shapes on the described chip:
    the stage of 8 layers at the published widths and its engine's three kinds
    of state as ``PagedKVCache`` shapes them (16 slots of 265 pages of 64)."""
    from theanompi_tpu.models.minicpm_sala import MiniCPM_SALA_Stage8
    from theanompi_tpu.ops import pallas_lightning as plg
    from theanompi_tpu.ops import pallas_sparse as ps

    for module in (plg, ps):
        _mosaic(monkeypatch, module)
    model, page, pages, S, M = MiniCPM_SALA_Stage8(), 64, 4240, 16, 265

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    spec = model.cache_spec(page)
    k_pool = on_chip((spec["paged_layers"], pages + 1, *spec["k_page"]), spec["dtype"])
    held = {"v": on_chip(k_pool.shape, spec["dtype"])}
    for name, a in spec["slots"].items():
        rows = () if a.get("positions_per_row") is None else (-(-M * page // a["positions_per_row"]),)
        held[name] = on_chip((a["layers"], S, *rows, *a["row"]), a["dtype"])
    assert k_pool.shape == (2, 4241, 64, 256) and held["compressed"].shape == (2, 16, 1060, 256)
    assert held["state"].shape == (6, 16, 32, 128, 128) and held["state"].dtype == jnp.float32
    return model, page, params, k_pool, held, on_chip, (S, M)


def _held_bytes(k_pool, held):
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize for a in (k_pool, *held.values()))


def test_minicpm_sala_decode_step_walks_chosen_pages_and_updates_every_pool_in_place(
        one_chip, no_persistent_cache, monkeypatch):
    """16 slots over 0.56 GB of K and V pages (2 sparse layers), 17 MB of
    compressed keys and 0.2 GB of float32 state (6 lightning layers): two
    ``sparse_decode`` calls over at most 98 chosen pages a (slot, K/V head),
    six ``lightning_step`` calls, one ``sparse_cache_write``; everything
    donated aliases its output, NO pool is copied and no slot's whole context
    is gathered (``[16, 16960, 256]`` would be 139 MB a pool a layer)."""
    model, page, params, k_pool, held, on_chip, (S, M) = _minicpm_sala_decode(one_chip, monkeypatch)

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_pool, held, on_chip((S, M), jnp.int32), on_chip((S,), jnp.int32), on_chip((S,), jnp.int32),
        on_chip((S,), jnp.bool_), on_chip((S,), jnp.float32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 2 + 6 + 1
    assert all(name in text for name in ("sparse_decode", "lightning_step", "sparse_cache_write"))
    assert _held_bytes(k_pool, held) == 774_569_984 and mem.alias_size_in_bytes >= 774_569_984
    assert mem.temp_size_in_bytes < 0.1e9  # 23 MB; a copy of the K pages would be 0.28 GB, of the state 0.2
    assert not re.search(rf"\[{S},{M * page},\d+\]", text) and not re.search(rf"\[{S},{M},{page},\d+\]", text)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_minicpm_sala_prefill_of_16384_positions_holds_no_square_of_the_prompt(
        one_chip, no_persistent_cache, monkeypatch):
    """One bucket of 16,384 rows for its slot at a traced real length: two
    ``sparse_prefill`` flash passes over their lists of live tile pairs (a
    grid of 8,448 steps a layer at K/V tiles of 512, two int32 lists of
    them prefetched), six ``lightning_chunk`` scans, whole pages and the
    slot's rows written to the donated pools; 1.576 GB of temporaries
    beside 5.64 GB of weights (1.72 with the ``jnp`` scans, whose ``[128,
    32, 128, 128]`` fp32 arrays are gone, as are the rotation's halves).
    Nothing is shaped ``[.., T, T]``: shown on a bucket of 8,192, because at
    16,384 the SwiGLU's ``[T, 16384]`` is a square by accident."""
    model, page, params, k_pool, held, on_chip, _ = _minicpm_sala_decode(one_chip, monkeypatch)

    def prefill(p, t, pg, k, v, slot, n_real):
        return model.decode_prefill(p, t, pg, k, v, slot, n_real, page_size=page)

    def compiled_at(T):
        return jax.jit(prefill, donate_argnums=(3, 4)).lower(
            params, on_chip((T,), jnp.int32), on_chip((T // page,), jnp.int32), k_pool, held,
            on_chip((), jnp.int32), on_chip((), jnp.int32)).compile()

    compiled = compiled_at(16384)
    mem = compiled.memory_analysis()
    assert _kernels(compiled) == 2 + 6
    assert all(name in compiled.as_text() for name in ("sparse_prefill", "lightning_chunk"))
    assert mem.alias_size_in_bytes >= 774_569_984 and mem.temp_size_in_bytes < 1.65e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
    assert "8192,8192" not in compiled_at(8192).as_text()


def test_lightning_chunk_at_the_minicpm_sala_width(one_chip, no_persistent_cache, monkeypatch):
    """The prompt's scan alone at the cell's width (16,384 rows, 32 heads of
    128, chunks of 128), as the model calls it (q and k unrotated in fp32 and
    rotated inside by a lane roll, v in bfloat16): Mosaic's tile and VMEM
    rules for blocks of ``[128, 8 x 128]``, the fp32 products at ``HIGHEST``
    and the states held across the chunks as the output block."""
    from theanompi_tpu.models.minicpm_sala import _turn, decay_rates
    from theanompi_tpu.ops import pallas_lightning as plg

    _mosaic(monkeypatch, plg)
    T, H, D = 16384, 32, 128
    qk = jax.ShapeDtypeStruct((T, H, D), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((T, H, D), jnp.bfloat16, sharding=one_chip)
    n_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def scan(q, k, v, n_real):
        return plg.lightning_chunk(q, k, v, decay_rates(H), n_real, 128, _turn(jnp.arange(T), D, 10000.0))

    compiled = jax.jit(scan).lower(qk, qk, v, n_real).compile()
    assert _kernels(compiled) == 1 and "lightning_chunk" in compiled.as_text()
    o, state = jax.eval_shape(scan, qk, qk, v, n_real)
    assert o.shape == (T, H, D) and state.shape == (H, D, D) and state.dtype == o.dtype == jnp.float32


def _evabyte_decode(one_chip, monkeypatch):
    """The cell ``evabyte-decode-bytes24k`` as shapes on the described chip:
    the stage of 4 layers at the published widths and its engine's two kinds
    of state as ``PagedKVCache`` shapes them (24 slots of 14 pages of one
    window block, 128 compressed rows a page; a ring of 2,048 rows a slot)."""
    from theanompi_tpu.models.evabyte import EvaByte_Stage4
    from theanompi_tpu.ops import pallas_eva as pe

    _mosaic(monkeypatch, pe)
    model, page, pages, S, M = EvaByte_Stage4(), 2048, 336, 24, 14

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    spec = model.cache_spec(page)
    k_pool = on_chip((model.arch.n_layers, pages + 1, *spec["k_page"]), spec["dtype"])
    ring = spec["slots"]["ring"]
    held = {"v": on_chip(k_pool.shape, spec["dtype"]),
            "ring": on_chip((ring["layers"], S, *ring["row"]), ring["dtype"])}
    assert k_pool.shape == (4, 337, 128, 4096) and held["ring"].shape == (4, 24, 2, 2048, 4096)
    return model, page, params, k_pool, held, on_chip, (S, M)


def test_evabyte_decode_step_reads_its_rows_and_updates_every_pool_in_place(
        one_chip, no_persistent_cache, monkeypatch):
    """24 slots over 2.83 GB of compressed pages and 3.22 GB of ring (4
    layers): four ``eva_decode`` calls, one ``eva_cache_write``; everything
    donated aliases its output, NO pool is copied and no slot's whole ring or
    page list is gathered (``[24, 2048, 4096]`` would be 0.4 GB a layer)."""
    model, page, params, k_pool, held, on_chip, (S, M) = _evabyte_decode(one_chip, monkeypatch)

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_pool, held, on_chip((S, M), jnp.int32), on_chip((S,), jnp.int32), on_chip((S,), jnp.int32),
        on_chip((S,), jnp.bool_), on_chip((S,), jnp.float32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 4 + 1
    assert "eva_decode" in text and "eva_cache_write" in text
    assert _held_bytes(k_pool, held) == 6_048_186_368 and mem.alias_size_in_bytes >= 6_048_186_368
    assert mem.temp_size_in_bytes < 0.1e9  # a copy of a layer's ring would be 0.8 GB, of the pages 0.7
    assert not re.search(rf"\[{S},2048,4096\]", text) and not re.search(rf"\[{S},{M * 128},4096\]", text)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_evabyte_prefill_of_24576_positions_holds_no_square_of_the_prompt(
        one_chip, no_persistent_cache, monkeypatch):
    """One bucket of 24,576 rows for its slot at a traced real length: three
    ``eva_prefill`` flash passes (the last layer's attention feeds nothing a
    prefill keeps, and the compiler drops it), whole pages of compressed rows and the
    slot's ring written to the donated pools; 3.1 GB of temporaries beside
    1.64 GB of weights and 6.05 GB of pools fit the chip, and nothing is shaped
    ``[.., T, T]`` or ``[.., T, T / 16]``."""
    model, page, params, k_pool, held, on_chip, _ = _evabyte_decode(one_chip, monkeypatch)

    def prefill(p, t, pg, k, v, slot, n_real):
        return model.decode_prefill(p, t, pg, k, v, slot, n_real, page_size=page)

    T = 24576
    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, on_chip((T,), jnp.int32), on_chip((T // page,), jnp.int32), k_pool, held,
        on_chip((), jnp.int32), on_chip((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 3 and "eva_prefill" in text
    # 3.09 GB: the SwiGLU's [24576, 11008] intermediates lead; a copy of a pool would add 2.8 or 3.2 GB
    assert mem.alias_size_in_bytes >= 6_048_186_368 and mem.temp_size_in_bytes < 3.5e9
    assert f"{T},{T}" not in text and f"{T},{T // 16}" not in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def _jamba2_decode(one_chip, monkeypatch):
    """The cell ``jamba2-3b-decode-doc32k`` as shapes on the described chip:
    the stage of 14 layers at the published widths and its engine's three
    kinds of state as ``PagedKVCache`` shapes them (16 slots of 131 pages of
    256 positions in the one attention layer; a conv window and a state a
    slot in each of the 13 Mamba layers)."""
    from theanompi_tpu.models.jamba import Jamba2_3B_Stage2
    from theanompi_tpu.ops import pallas_attention as pa
    from theanompi_tpu.ops import pallas_mamba as pm
    from theanompi_tpu.ops import pallas_sparse as ps

    for module in (pm, ps):
        _mosaic(monkeypatch, module)
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    model, page, pages, S, M = Jamba2_3B_Stage2(), 256, 2096, 16, 131

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    spec = model.cache_spec(page)
    k_pool = on_chip((spec["paged_layers"], pages + 1, *spec["k_page"]), spec["dtype"])
    held = {"v": on_chip(k_pool.shape, spec["dtype"])}
    for name, a in spec["slots"].items():
        held[name] = on_chip((a["layers"], S, *a["row"]), a["dtype"])
    assert k_pool.shape == (1, 2097, 256, 128) and held["conv"].shape == (13, 16, 3, 5120)
    assert held["ssm"].shape == (13, 16, 16, 5120) and held["ssm"].dtype == jnp.float32
    return model, page, params, k_pool, held, on_chip, (S, M)


def test_jamba2_decode_step_reads_every_page_of_a_slot_and_updates_every_pool_in_place(
        one_chip, no_persistent_cache, monkeypatch):
    """16 slots over 0.27 GB of K and V pages (one attention layer, one K/V
    head) and 75 MB of conv windows and float32 states (13 Mamba layers): one
    ``sparse_decode`` over every page of the slot (20 query heads over the one
    K/V head), one ``sparse_cache_write``, the Mamba steps as fusions;
    everything donated aliases its output and no slot's whole context is
    gathered (``[16, 33536, 128]`` would be 137 MB)."""
    model, page, params, k_pool, held, on_chip, (S, M) = _jamba2_decode(one_chip, monkeypatch)

    def step(p, k, v, tb, sl, la, ac, te):
        return model.decode_step(p, k, v, tb, sl, la, ac, te, jax.random.PRNGKey(0), page_size=page)

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, k_pool, held, on_chip((S, M), jnp.int32), on_chip((S,), jnp.int32), on_chip((S,), jnp.int32),
        on_chip((S,), jnp.bool_), on_chip((S,), jnp.float32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 1 + 1
    assert "sparse_decode" in text and "sparse_cache_write" in text
    assert _held_bytes(k_pool, held) == 349_405_184 and mem.alias_size_in_bytes >= 349_405_184
    assert mem.temp_size_in_bytes < 0.2e9
    assert not re.search(rf"\[{S},{M * page},128\]", text) and not re.search(rf"\[{S},{M},{page},128\]", text)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_jamba2_prefill_of_32768_positions_fits_beside_its_weights(
        one_chip, no_persistent_cache, monkeypatch):
    """One bucket of 32,768 rows for its slot at a traced real length: one
    ``flash_fwd`` at 20 heads over one K/V head (its resident K and V pass the
    default scoped VMEM: the forward states its limit), thirteen
    ``mamba_scan`` kernels, whole pages and the slot's windows and states
    written to the donated pools; nothing is shaped ``[.., T, T]`` or ``[T,
    16, 5120]`` (a state a row: 10.7 GB)."""
    model, page, params, k_pool, held, on_chip, _ = _jamba2_decode(one_chip, monkeypatch)

    def prefill(p, t, pg, k, v, slot, n_real):
        return model.decode_prefill(p, t, pg, k, v, slot, n_real, page_size=page)

    T = 32768
    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, on_chip((T,), jnp.int32), on_chip((T // page,), jnp.int32), k_pool, held,
        on_chip((), jnp.int32), on_chip((), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(compiled) == 1 + 13 and "mamba_scan" in text and "flash_fwd" in text
    assert mem.alias_size_in_bytes >= 349_405_184 and mem.temp_size_in_bytes < 4e9
    assert f"{T},{T}" not in text and f"{T},16,5120" not in text
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES
