"""The ``jamba`` stack (Mamba-1 selective state-space layers beside attention
layers) at a small size on the CPU, against the benchmark's plain reference
(``benchmark/reference/jamba2-3b-decode.py``) on seeded random weights: the
prompt's scan (kernel and twin) against the reference's recurrence one
position at a time, the decode step's state and window, the full forward,
prefill then decode through the ENGINE's two programs at prompt lengths around
the convolution's window and across a page edge, three kinds of state in one
cache manager, the stage's layer kinds and parameter count, and the CLI."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_checks import names_of  # noqa: F401 - sets the benchmark's import path up
from harness import manifest
from theanompi_tpu.models import get_model
from theanompi_tpu.models.jamba import ATTENTION, MAMBA, Jamba, Jamba2_3B_Stage2, layer_kinds
from theanompi_tpu.ops import pallas_mamba as pm
from theanompi_tpu.serve.decode.engine import DecodeEngine
from theanompi_tpu.serve.decode.kvcache import PagedKVCache
from theanompi_tpu.serve.decode.scheduler import DecodeSequence

REF = manifest.load_module("reference", "jamba2-3b-decode")
PAGE = 8


def _config():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "jamba2-3b-decode.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"]}


def _model(dtype=jnp.float32, cls=Jamba2_3B_Stage2, **over):
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in _config()["recipe_overrides"].items()}
    return cls(Jamba2_3B_Stage2.default_recipe().replace(**{**kw, "compute_dtype": dtype, **over}))


def _weights(model, seed=5):
    return jax.jit(model.init)(jax.random.PRNGKey(seed))[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n).astype(np.int32) for n in lengths]


def _engine(model, max_seqs=8, **kw):
    return DecodeEngine(model, prefill_buckets=(32, 64, 96), kv_pages=96, page_size=PAGE, max_seqs=max_seqs,
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


def _gaps(got, ref):
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


# -- the scan ------------------------------------------------------------------
def _scan_case(T=96, E=256, N=16, dtype=jnp.float32, seed=0):
    """Inputs as a Mamba layer hands them to the scan: ``dt`` after the
    softplus (0.001-0.3), ``A`` Mamba's ``-(1 .. N)`` a channel."""
    rng = np.random.default_rng(seed)
    x, z = (jnp.asarray(rng.normal(size=(T, E)), dtype) for _ in range(2))
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(T, E))), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(T, N)), jnp.float32) for _ in range(2))
    A = -jnp.asarray(np.tile(np.arange(1, N + 1)[:, None], (1, E)) * rng.uniform(0.5, 1.5, size=(1, E)), jnp.float32)
    return x, dt, B, C, z, A, jnp.asarray(rng.uniform(0.5, 1.5, size=E), jnp.float32)


def _recurrence(x, dt, B, C, z, A, D, n_real):
    """The reference's own recurrence, one position at a time, over the first
    ``n_real`` rows: (gated ``y [n_real, E]``, the state ``[N, E]``)."""
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        y, h = REF.recurrence(dt[:n_real], x[:n_real].astype(f32), B[:n_real], C[:n_real], A.T, D)
    return y * jax.nn.silu(z[:n_real].astype(f32)), h.T


# float32 in and out: the kernel, its twin and the recurrence are the same sums in other orders (1.2e-7 of the
# largest state entry read at these sizes); the state after n_real rows is the recurrence's over those rows alone
@pytest.mark.parametrize("scan", ["mamba_scan", "mamba_scan_reference"])
@pytest.mark.parametrize("n_real", [96, 77, 64, 33, 16, 1])
def test_the_scan_is_the_recurrence_and_padded_rows_leave_the_state_as_it_was(scan, n_real):
    args = _scan_case()
    y, h = getattr(pm, scan)(*args, n_real, chunk=32, block=128)
    want_y, want_h = _recurrence(*args, n_real)
    assert np.abs(np.asarray(h - want_h)).max() < 1e-5 * np.abs(np.asarray(want_h)).max()
    assert np.abs(np.asarray(y[:n_real] - want_y)).max() < 1e-5 * np.abs(np.asarray(want_y)).max()


def test_the_kernel_rounds_only_its_output_where_its_inputs_are_bfloat16():
    # x and z in bfloat16 as the model gives them: the output is rounded once (2^-8 relative), the state not at all
    args = _scan_case(dtype=jnp.bfloat16)
    y, h = pm.mamba_scan(*args, 77, chunk=32, block=128)
    want_y, want_h = _recurrence(*args, 77)
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    assert np.abs(np.asarray(h - want_h)).max() < 1e-5 * np.abs(np.asarray(want_h)).max()
    assert np.abs(np.asarray(y[:77].astype(jnp.float32) - want_y)).max() <= 2 ** -8 * np.abs(np.asarray(want_y)).max()


def test_the_kernel_refuses_shapes_that_are_no_whole_chunks_or_blocks():
    args = _scan_case(T=96, E=256)
    with pytest.raises(ValueError, match="whole chunks"):
        pm.mamba_scan(*args, 96, chunk=24, block=128)
    with pytest.raises(ValueError, match="whole blocks"):
        pm.mamba_scan(*args, 96, chunk=32, block=96)


def test_mamba_step_is_the_recurrence_one_position_on_and_starts_a_fresh_slot_from_nought():
    x, dt, B, C, z, A, D = _scan_case(T=12, E=128)
    S = 3
    _, h5 = _recurrence(x, dt, B, C, z, A, D, 5)
    held = jnp.stack([h5, h5, jnp.full_like(h5, 7.0)])  # slot 2 holds a previous occupant's state
    rows = lambda a: jnp.stack([a[5], a[5], a[0]])  # noqa: E731
    y, h = pm.mamba_step(rows(x), rows(dt), rows(B), rows(C), rows(z), A, D, held, jnp.asarray([False, False, True]))
    want_y, want_h = _recurrence(x, dt, B, C, z, A, D, 6)
    first_y, first_h = _recurrence(x, dt, B, C, z, A, D, 1)
    assert h.shape == (S, 16, 128) and y.shape == (S, 128)
    for s, (wy, wh) in enumerate([(want_y[5], want_h), (want_y[5], want_h), (first_y[0], first_h)]):
        assert np.abs(np.asarray(h[s] - wh)).max() < 1e-5 * np.abs(np.asarray(wh)).max()
        assert np.abs(np.asarray(y[s] - wy)).max() < 1e-5 * np.abs(np.asarray(wy)).max()


# -- the model against the reference ------------------------------------------------
# float32: the same arithmetic in another order (2.2e-7 read). bfloat16: the program rounds every matmul's
# operands, the x and z halves of W_in and the convolution's output to bfloat16 where the reference stays in
# float32: 0.0034 read at these sizes.
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 8e-3)])
def test_the_full_forward_agrees_with_the_reference_at_every_position(dtype, tolerance):
    model = _model(dtype)
    params = _weights(model, seed=7)
    hist = _prompts([128], seed=1)[0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, {}, jnp.asarray(hist[None]))[0][0], np.float32)
    ref = REF.run(_config(), 7, [(hist, np.arange(128))])
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)) and a.dtype == b.dtype, params, ref["init"]))
    assert _gaps(got, ref["logits"][0]).max() < tolerance


@pytest.mark.parametrize("precision,least", [("bfloat16", 1e-3), ("int8", 1e-2)])
def test_the_reference_in_a_lower_precision_lies_outside_the_float32_tolerance(precision, least):
    hist = _prompts([128], seed=1)[0]
    ref = REF.run(_config(), 7, [(hist, np.arange(128))])
    low = REF.run(_config(), 7, [(hist, np.arange(128))], precision=precision, params=ref["init"])["logits"][0]
    assert _gaps(low, ref["logits"][0]).max() > least > 2e-5


def _bf16_state_scan(x, dt, B, C, z, A, D, n_real, *, chunk, block):
    """The recurrence with its state rounded to bfloat16 at every row."""
    del chunk, block
    f32 = jnp.float32
    dt = jnp.where((jnp.arange(x.shape[0]) < n_real)[:, None], dt, 0.0)

    def one(h, row):
        d, xr, b, c = row
        h = (jnp.exp(d[None] * A) * h + (d * xr)[None] * b[:, None]).astype(jnp.bfloat16).astype(f32)
        return h, c @ h + D * xr

    h, y = jax.lax.scan(one, jnp.zeros(A.shape, f32), (dt, x.astype(f32), B, C))
    return (y * jax.nn.silu(z.astype(f32))).astype(z.dtype), h


class _NoInnerNorms(Jamba2_3B_Stage2):
    def _selective(self, p, x):
        r = self.recipe
        dbc = self._dot(x, p["w_x"])
        R, N = r.dt_rank, r.d_state
        dt = jax.nn.softplus(self._dot(dbc[..., :R], p["w_dt"]) + p["b_dt"].astype(jnp.float32))
        return dt, dbc[..., R:R + N], dbc[..., R + N:]


@pytest.mark.parametrize("fault", ["bfloat16_state", "no_inner_norms"])
def test_a_bfloat16_state_or_dropped_inner_norms_fail_the_float32_tolerance(fault):
    model = _model(jnp.float32, cls=_NoInnerNorms if fault == "no_inner_norms" else Jamba2_3B_Stage2)
    params = _weights(model, seed=7)
    hist = _prompts([128], seed=1)[0]
    scan = _bf16_state_scan if fault == "bfloat16_state" else pm.mamba_scan
    with jax.default_matmul_precision("highest"):
        x = model._prompt(params, jnp.asarray(hist), 128, scan=scan)[0]
        got = np.asarray(model._logits(params, x))
    gap = _gaps(got, REF.run(_config(), 7, [(hist, np.arange(128))])["logits"][0]).max()
    assert gap > 2 * 2e-5, gap  # read: 8.8e-5 with the state in bfloat16, 4.4 times the tolerance


# prompt lengths 1 to 5 (no prefill at all, then a prefill shorter than the convolution's window of 3 cached rows,
# one that fills it, two past it), 9 and 17 (8 and 16 cached: a page edge), 45 (across six pages)
@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 1.2e-2)])
def test_prefill_then_decode_through_the_engine_agrees_with_the_references_full_forward(dtype, tolerance):
    model = _model(dtype)
    params = _weights(model)
    eng = _engine(model)
    eng.set_params(params, {}, 0)
    prompts, n_new = _prompts([1, 2, 3, 4, 5, 9, 17, 45]), 8
    with jax.default_matmul_precision("highest"):
        logits, tokens = _serve(eng, eng._served.params, prompts, n_new)
    samples = [(np.concatenate([p, tokens[:-1, i]]), np.arange(len(p) - 1, len(p) - 1 + n_new))
               for i, p in enumerate(prompts)]
    ref = REF.run(_config(), 5, samples)["logits"]
    for i, prompt in enumerate(prompts):
        assert _gaps(logits[:, i], ref[i]).max() < tolerance, (len(prompt), _gaps(logits[:, i], ref[i]))
    assert eng.compile_count == 3  # the buckets of 32 and 64 these prompts use, and the one decode program
    assert eng._cache.free_list.conserved()


def test_the_programs_with_the_kernel_equal_the_programs_with_its_twin():
    model = _model(jnp.float32)
    params = _weights(model)
    tokens = jnp.asarray(_prompts([96], seed=3)[0])
    with jax.default_matmul_precision("highest"):
        a, paged_a, held_a = model._prompt(params, tokens, 77)
        b, paged_b, held_b = model._prompt(params, tokens, 77, scan=pm.mamba_scan_reference)
    # the output, the attention layer's K and V, each Mamba layer's window and its state at the real length
    leaves = jax.tree_util.tree_leaves
    for got, want in zip(leaves((a, paged_a, held_a)), leaves((b, paged_b, held_b))):
        assert np.abs(np.asarray(got - want)).max() <= 1e-5 * np.abs(np.asarray(want)).max()


def test_the_prefill_keeps_the_last_real_pre_convolution_rows_with_noughts_before_position_0():
    model = _model(jnp.float32)
    params = _weights(model)
    p = params["layers"][0]["mixer"]
    tokens = jnp.asarray(_prompts([32], seed=4)[0])
    h = jax.nn.one_hot(tokens, 64)  # any rows: the window is what W_in made of them
    x_pre = np.asarray(model._dot(h, p["w_in"])[:, :model.d_inner])
    for n_real in (1, 2, 3, 20):
        _, window, _ = model._mamba_prompt(p, h, n_real)
        want = np.concatenate([np.zeros((3, model.d_inner), np.float32), x_pre])[n_real:n_real + 3]
        assert np.array_equal(np.asarray(window), want), n_real


# -- the stage, the cache, the engine ----------------------------------------------
def test_the_published_stack_has_attention_at_7_and_21_and_the_stage_is_its_first_period():
    kinds = layer_kinds(28, 14, 7)
    assert [i for i, k in enumerate(kinds) if k == ATTENTION] == [7, 21] and kinds.count(MAMBA) == 26
    stage = Jamba2_3B_Stage2()
    assert stage.kinds == kinds[:14] and stage.attn_layers == (7,) and len(stage.mamba_layers) == 13
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda k: stage.init(k)[0], jax.random.PRNGKey(0)))
    assert sum(int(a.size) for a in leaves) == 1_598_556_096 and all(a.dtype == jnp.bfloat16 for a in leaves)
    r = stage.recipe
    assert (r.d_model, r.d_ff, r.n_heads, r.n_kv_heads, r.head_dim, r.num_classes) == (2560, 8192, 20, 1, 128, 65536)
    assert (stage.d_inner, r.d_state, r.d_conv, r.dt_rank, r.rms_eps) == (5120, 16, 4, 160, 1e-6)
    assert get_model("jamba2_3b_stage2") is Jamba2_3B_Stage2 and get_model("jamba_lm") is Jamba


def test_three_kinds_of_state_in_one_cache_manager():
    model = _model(jnp.bfloat16)
    spec = model.cache_spec(PAGE)
    assert spec["kind"] == "kv" and spec["donate"] and spec["paged_layers"] == 1
    assert spec["k_page"] == spec["v_page"] == (PAGE, 16)
    cache = PagedKVCache(n_layers=spec["paged_layers"], page_size=PAGE, n_pages=24, max_seqs=3,
                         max_pages_per_seq=8, k_page=spec["k_page"], v_page=spec["v_page"],
                         dtype=spec["dtype"], kind=spec["kind"], slots=spec["slots"])
    assert cache.k_pool.shape == (1, 25, PAGE, 16) and set(cache.v_pool) == {"v", "conv", "ssm"}
    assert cache.v_pool["conv"].shape == (3, 3, 3, 128) and cache.v_pool["conv"].dtype == jnp.bfloat16
    assert cache.v_pool["ssm"].shape == (3, 3, 16, 128) and cache.v_pool["ssm"].dtype == jnp.float32
    assert cache.pool_bytes_by_kind == {"kv": 2 * 25 * PAGE * 16 * 2, "conv": 3 * 3 * 3 * 128 * 2,
                                        "ssm": 3 * 3 * 16 * 128 * 4}
    assert cache.bytes_per_position_by_kind == {"kv": 2 * 16 * 2}  # a window and a state do not grow
    big = Jamba2_3B_Stage2().cache_spec(256)
    assert big["k_page"] == (256, 128) and big["paged_layers"] == 1
    assert big["slots"] == {"conv": {"layers": 13, "row": (3, 5120), "dtype": jnp.bfloat16},
                            "ssm": {"layers": 13, "row": (16, 5120), "dtype": jnp.float32}}


def test_the_rows_and_state_a_step_reads_by_hand():
    rows = _model(jnp.float32).cache_rows(np.asarray([0, 7, 100]))
    assert list(rows["mqa_rows"]) == [1, 8, 101]
    assert list(rows["ssm_state_bytes"]) == [2 * 3 * 16 * 128 * 4] * 3  # read and written, 3 Mamba layers
    big = Jamba2_3B_Stage2().cache_rows(np.asarray([29000]))
    assert big["ssm_state_bytes"][0] == 2 * 13 * 327_680 and big["mqa_rows"][0] == 29001


def test_the_engine_serves_it_on_the_normal_path_and_conserves_pages_and_state(tmp_path):
    model = _model(jnp.bfloat16)
    params = _weights(model)
    eng = _engine(model, max_seqs=3, obs_dir=str(tmp_path))
    eng.set_params(params, {}, 0)
    k0, held = eng._cache.k_pool, eng._cache.v_pool
    assert eng.warmup() == 4  # three buckets + the one decode program
    assert k0.is_deleted() and all(a.is_deleted() for a in held.values())  # donated: updated in place
    eng.start()
    # more requests than slots: every slot is used again, and a second user must start from a nought state
    prompts = _prompts([50, 2, 70, 1, 33, 90, 6, 64], seed=3)
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    got = [f.result(300).tokens for f in futs]
    assert eng.drain(60) and eng.compile_count == 4
    cache = eng._cache
    assert cache.free_list.conserved() and cache.pages_used == 0 and not cache.pools_deleted()
    for prompt, tokens in zip(prompts, got):  # the same tokens as each request served ALONE on a fresh engine
        alone = _engine(model, max_seqs=3)
        alone.set_params(params, {}, 0)
        _, want = _serve(alone, alone._served.params, [prompt], 5)
        assert np.array_equal(tokens, want[:, 0]), len(prompt)
    stats, text = eng.stats(), eng.registry.to_prometheus()
    assert stats["tmpi_decode_conv_bytes"] == 3 * 3 * 3 * 128 * 2
    assert stats["tmpi_decode_ssm_bytes"] == 3 * 3 * 16 * 128 * 4
    assert stats["tmpi_decode_ssm_state_bytes"] == 2 * 3 * 16 * 128 * 4 and stats["tmpi_decode_mqa_rows"] > 1
    for kind in ("kv", "conv", "ssm"):
        assert f'tmpi_decode_kv_pool_bytes{{kind="{kind}"}}' in text
    with open(tmp_path / "decode.jsonl") as f:
        records = [json.loads(line) for line in f]
    assert records and all(r["cache_kind"] == "kv" for r in records)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "decode.jsonl")) == []


@pytest.mark.parametrize("scope", ["mqa_decode", "mamba_step", "mamba_conv", "sparse_decode"])
def test_the_decode_program_names_its_parts(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c = eng._cache
    assert scope in eng._decode.lower(params, c.k_pool, c.v_pool, eng._sched.step_arrays(0)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["mamba_scan", "mamba_conv", "flash_fwd"])
def test_the_prefill_program_names_its_parts_and_holds_no_state_a_row(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c, T = eng._cache, 96
    lowered = eng._prefill.lower(params, jnp.zeros((T,), jnp.int32), jnp.zeros((T // PAGE,), jnp.int32),
                                 c.k_pool, c.v_pool, np.int32(0), np.int32(0))
    assert scope in lowered.as_text(debug_info=True)
    assert f"{T}x16x128xf32" not in lowered.as_text()  # the twin's [T, N, E]: a state a row


def test_tmpi_serve_decode_serves_it_from_a_checkpoint_by_its_zoo_name(tmp_path):
    # the normal path: the CLI, a training checkpoint's bfloat16 leaves, the same engine; no side script
    import subprocess
    import sys

    from theanompi_tpu.tools.check_obs_schema import validate_record
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = Jamba()
    save_checkpoint(str(tmp_path), init_train_state(model, jax.random.PRNGKey(0)), 3, rng=jax.random.PRNGKey(1))
    done = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "serve", "--ckpt-dir", str(tmp_path), "--model",
         "jamba_lm", "--decode", "--prefill-buckets", "16,64", "--kv-pages", "64", "--page-size", "8",
         "--max-seqs", "4", "--max-new-tokens", "4", "--selftest", "5", "--obs-dir", str(tmp_path / "obs")],
        cwd=os.path.dirname(manifest.BENCH_DIR), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    stats = json.loads(done.stdout.strip().splitlines()[-1])
    assert stats["kind"] == "decode" and stats["params_step"] == 3 and stats["cache_kind"] == "kv"
    assert stats["metrics"]["tmpi_decode_served_total"] == 5.0 and stats["metrics"]["tmpi_decode_failed_total"] == 0.0
    assert stats["metrics"]["tmpi_decode_kv_pages_out_total"] == stats["metrics"]["tmpi_decode_kv_pages_in_total"]
    assert stats["metrics"]["tmpi_decode_ssm_bytes"] > 0 and validate_record(stats) == []
