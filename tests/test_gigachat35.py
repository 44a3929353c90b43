"""The ``gigachat3_5`` stack (gated delta-rule linear attention beside gated
latent attention and routed experts) at a small size on the CPU, against the
benchmark's plain reference (``benchmark/reference/gigachat35-decode.py``) on
seeded random weights: both delta-rule kernels (and their twins) against the
recurrence one position at a time, flash attention with V of its own width,
the absorbed latent attention at GigaChat's ratio of latent to rotated key,
the share of the experts against the uncut layer, the full forward, prefill
then decode through the ENGINE's two programs, three kinds of state in one
cache manager, the published stack's kinds and parameter count, and the CLI."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark_checks import names_of  # noqa: F401 - sets the benchmark's import path up
from harness import manifest
from theanompi_tpu.models import get_model
from theanompi_tpu.models.gigachat35 import FULL, LINEAR, GigaChat35, GigaChat35_EP32, _norm
from theanompi_tpu.ops import pallas_gdn as pg
from theanompi_tpu.ops.moe import routed_experts
from theanompi_tpu.ops.pallas_attention import flash_attention
from theanompi_tpu.ops.pallas_mla import mla_decode, mla_decode_reference
from theanompi_tpu.ops.ring_attention import full_attention_reference
from theanompi_tpu.serve.decode.engine import DecodeEngine
from theanompi_tpu.serve.decode.kvcache import PagedKVCache
from theanompi_tpu.serve.decode.scheduler import DecodeSequence

REF = manifest.load_module("reference", "gigachat35-decode")
PAGE = 8
# float32 in and out: the program, its kernels and the reference are the same sums in other orders (the largest
# relative gap of a logits row read 3.6e-6 at these sizes)
F32_TOLERANCE = 2e-5


def _config(**over):
    with open(os.path.join(manifest.BENCH_DIR, "configs", "gigachat35-decode.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"], **over}


def _model(dtype=jnp.float32, **over):
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in _config()["recipe_overrides"].items()}
    return GigaChat35_EP32(GigaChat35_EP32.default_recipe().replace(**{**kw, "compute_dtype": dtype, **over}))


def _weights(model, seed=5):
    return jax.jit(model.init)(jax.random.PRNGKey(seed))[0]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 512, size=n).astype(np.int32) for n in lengths]


def _engine(model, max_seqs=8, **kw):
    return DecodeEngine(model, prefill_buckets=(16, 32, 48), kv_pages=72, page_size=PAGE, max_seqs=max_seqs,
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


# -- the delta rule --------------------------------------------------------------------
def _delta_case(T=64, Hk=2, H=4, D=32, seed=0, dtype=jnp.float32, alike=False):
    """Inputs as a delta-rule layer hands them to the kernels: q and k
    L2-normed a key head (k from a SiLU, so alike keys are common), q scaled,
    beta in (0, 1), g the log decay of A between 0.05 and 2 a head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    q = (l2(jax.random.normal(ks[0], (T, Hk, D))) * D ** -0.5).astype(dtype)
    k = jnp.ones((T, Hk, D)) if alike else jax.nn.silu(jax.random.normal(ks[1], (T, Hk, D)))
    k = l2(k).astype(dtype)
    v = jax.random.normal(ks[2], (T, H, D)).astype(dtype)
    beta = jnp.ones((T, H)) if alike else jax.nn.sigmoid(jax.random.normal(ks[3], (T, H)))
    g = (jnp.zeros((T, H)) if alike
         else -jnp.linspace(0.05, 2.0, H)[None] * jax.nn.softplus(jax.random.normal(ks[4], (T, H))))
    return q, k, v, beta, g


def _close(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() <= rel * np.abs(want).max()


# float32: the chunk form, its twin and the recurrence are the same sums in other orders (1e-7 of the largest entry
# read at these sizes); the state after n_real rows is the recurrence's over those rows alone, whatever follows
@pytest.mark.parametrize("chunk", ["gdn_chunk", "gdn_chunk_reference"])
@pytest.mark.parametrize("n_real", [64, 50, 32, 17, 1])
def test_the_chunk_form_is_the_recurrence_and_padded_rows_leave_the_state_as_it_was(chunk, n_real):
    q, k, v, beta, g = _delta_case()
    o, state = getattr(pg, chunk)(q, k, v, beta, g, jnp.int32(n_real), chunk=16)
    want_o, want_state = pg.gdn_reference(q[:n_real], k[:n_real], v[:n_real], beta[:n_real], g[:n_real])
    assert _close(o[:n_real], want_o, 1e-5) and _close(state, want_state, 1e-5)
    if chunk == "gdn_chunk" and n_real <= 48:
        assert not np.asarray(o[-16:]).any()  # a chunk wholly past the prompt computes nothing


def test_alike_keys_without_decay_are_exact_by_blocks():
    # every key the same, beta 1, no decay: (I + A) is all ones on and below the diagonal and its inverse is
    # 1 on the diagonal, -1 below it; a sum of powers of A would pass through entries of C(62, 31) ~ 4.7e17.
    # The state is then the last row's v k^T alone: a delta rule with beta 1 replaces, it does not add
    q, k, v, beta, g = _delta_case(alike=True)
    o, state = pg.gdn_chunk(q, k, v, beta, g, jnp.int32(64), chunk=64)
    want_o, want_state = pg.gdn_reference(q, k, v, beta, g)
    assert _close(o, want_o, 1e-5) and _close(state, want_state, 1e-5)
    assert _close(state, v[-1][:, None, :] * jnp.repeat(k[-1], 2, axis=0)[:, :, None], 1e-5)


def test_the_chunk_form_refuses_a_prompt_of_no_whole_chunks_or_a_chunk_of_no_power_of_two():
    q, k, v, beta, g = _delta_case(T=48)
    with pytest.raises(ValueError, match="no whole chunks"):
        pg.gdn_chunk(q, k, v, beta, g, jnp.int32(48), chunk=32)
    with pytest.raises(ValueError, match="no power of two"):
        pg.gdn_chunk(q, k, v, beta, g, jnp.int32(48), chunk=24)


def test_the_step_is_the_recurrence_one_position_on_and_starts_a_fresh_slot_from_nought():
    q, k, v, beta, g = _delta_case(T=12)
    # slot 0 steps through all 12 positions; slot 1 starts at position 0 over a state full of another's rows
    state = jnp.stack([jnp.zeros((2, 4, 32, 32)), jnp.full((2, 4, 32, 32), 7.0)], 1)  # [L=2, S=2, H, D, D]
    outs = []
    for t in range(12):
        lens = jnp.asarray([t, 0], jnp.int32)
        rows = [jnp.stack([a[t], a[t]]) for a in (q, k, v, beta, g)]
        o, state = pg.gdn_step(*rows, state, lens, layer=1)
        outs.append(o)
        if t == 0:
            assert _close(o[0], o[1], 1e-6)  # both from nought
    want_o, want_state = pg.gdn_reference(q, k, v, beta, g)
    assert _close(jnp.stack([x[0] for x in outs]), want_o, 1e-5) and _close(state[1, 0], want_state, 1e-5)
    assert not np.asarray(state[0, 0]).any() and (np.asarray(state[0, 1]) == 7.0).all()  # layer 0 untouched


def test_the_step_kernel_equals_its_twin():
    q, k, v, beta, g = _delta_case(T=3, seed=4)
    state = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 4, 32, 32))
    lens = jnp.asarray([5, 0, 7], jnp.int32)
    o, got = pg.gdn_step(q, k, v, beta, g, state, lens, layer=1)
    want_o, want = pg.gdn_step_reference(q, k, v, beta, g, state, lens, layer=1)
    assert _close(o, want_o, 1e-6) and _close(got, want, 1e-6)


# -- the two kernels GigaChat shares ---------------------------------------------------
# float32 inputs at highest: the kernel's blocks and the plain softmax are the same sums in other orders
@pytest.mark.parametrize("causal,groups", [(True, 4), (False, 4), (True, 2)])
def test_flash_attention_takes_v_of_its_own_width(causal, groups):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 40, 4, 48))
    k = jax.random.normal(ks[1], (2, 40, groups, 48))
    v = jax.random.normal(ks[2], (2, 40, groups, 16))
    got = flash_attention(q, k, v, causal=causal, scale=0.2, block_q=16, block_k=16, precision="highest")
    kk, vv = (jnp.repeat(a, 4 // groups, axis=2) for a in (k, v))
    want = full_attention_reference(q, kk, vv, causal=causal, scale=0.2, precision=jax.lax.Precision.HIGHEST)
    assert got.shape == (2, 40, 4, 16) and _close(got, want, 1e-5)


def test_flash_attention_with_v_of_its_own_width_has_no_backward():
    q = jnp.ones((1, 16, 2, 32))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda v: jnp.sum(flash_attention(q, q, v, causal=True)))(jnp.ones((1, 16, 2, 16)))


def test_mla_decode_at_gigachats_ratio_of_latent_to_rotated_key():
    # GigaChat: 64 heads over a latent of 512 and a rotated key of 64 (8 to 1); Mistral: 32 over 256 and 64
    S, H, R, Dr, page, M = 3, 16, 128, 16, PAGE, 5
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    q_lat, q_rope = jax.random.normal(ks[0], (S, H, R)), jax.random.normal(ks[1], (S, H, Dr))
    c_new, r_new = jax.random.normal(ks[2], (S, R)), jax.random.normal(ks[3], (S, Dr))
    c_pool = jax.random.normal(ks[4], (2, 16, page, R))
    r_pool = jax.random.normal(ks[5], (2, 16, Dr, page))
    tables = jnp.asarray(np.random.default_rng(0).permutation(15)[:S * M].reshape(S, M), jnp.int32)
    lens = jnp.asarray([0, 9, 37], jnp.int32)  # an inactive slot, a page edge passed, five pages
    with jax.default_matmul_precision("highest"):
        got = mla_decode(q_lat, q_rope, c_new, r_new, c_pool, r_pool, tables, lens, layer=1, scale=0.1)
        want = mla_decode_reference(q_lat, q_rope, c_new, r_new, c_pool, r_pool, tables, lens, layer=1, scale=0.1)
    assert _close(got, want, 1e-5)


def test_the_swiglu_limit_clamps_the_gate_from_above_and_the_up_projection_both_ways():
    x = jnp.asarray([[3.0, -3.0]])
    w_gate = jnp.asarray([[[4.0, -1.0], [0.0, 0.0]]])  # gates 12 and -3
    w_up = jnp.asarray([[[4.0, -4.0], [0.0, 0.0]]])  # ups 12 and -12
    w_down = jnp.eye(2)[None]
    idx, w = jnp.zeros((1, 1), jnp.int32), jnp.ones((1, 1))
    got, _ = routed_experts(x, idx, w, w_gate, w_up, w_down, 0, 1, tm=8, limit=10.0)
    want = jnp.asarray([[jax.nn.silu(10.0) * 10.0, jax.nn.silu(-3.0) * -10.0]])
    assert _close(got, want, 1e-6)
    free, _ = routed_experts(x, idx, w, w_gate, w_up, w_down, 0, 1, tm=8)
    assert _close(free, jnp.asarray([[jax.nn.silu(12.0) * 12.0, jax.nn.silu(-3.0) * -12.0]]), 1e-6)


# -- the experts' shares -----------------------------------------------------------------
def test_the_shares_of_the_experts_add_up_to_the_uncut_reference_layer():
    """Four chips of 8 experts each: their routed parts, with the shared
    expert (which every chip computes alike) counted once, are the uncut
    reference layer's output over all 32."""
    c = _config(n_routed_experts=32, num_experts_per_tok=4, num_experts_held=32)
    uncut = REF.init(c, 11)["layers"][1]["mlp"]
    h = _norm(jax.random.normal(jax.random.PRNGKey(4), (24, 64)), jnp.zeros(64), 1e-6)
    op = lambda a: a  # noqa: E731
    with jax.default_matmul_precision("highest"):
        per_expert = REF.route(c, uncut, h)
        want = REF.swiglu(uncut["shared"], h, op, 10.0) + REF.routed(c, uncut, h, per_expert, op, 24)
        total = 0.0
        for s in range(4):
            model = _model(jnp.float32, n_experts=32, experts_per_token=4, experts_held=8, first_expert=8 * s)
            share = {**uncut, "experts": jax.tree_util.tree_map(lambda a: a[8 * s:8 * (s + 1)], uncut["experts"])}
            total = total + model._moe(share, h, 16)
        total = total - 3 * model._swiglu(uncut["shared"], h)
    assert _close(total, want, 1e-5)
    assert np.asarray(jnp.sum(per_expert > 0, axis=1) == 4).all()  # four experts a row, over the four shares


# -- the whole model against the reference ---------------------------------------------------
def test_the_full_forward_agrees_with_the_reference_at_every_position():
    model = _model(jnp.float32)
    params = _weights(model, seed=7)
    hist = _prompts([48], seed=1)[0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, {}, jnp.asarray(hist[None]))[0][0], np.float32)
    ref = REF.run(_config(), 7, [(hist, np.arange(48))])
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)) and a.dtype == b.dtype, params, ref["init"]))
    assert _gaps(got, ref["logits"][0]).max() < F32_TOLERANCE


@pytest.mark.parametrize("precision,least", [("bfloat16", 1e-3), ("int8", 1e-2)])
def test_the_reference_in_a_lower_precision_lies_outside_the_float32_tolerance(precision, least):
    hist = _prompts([48], seed=1)[0]
    ref = REF.run(_config(), 7, [(hist, np.arange(48))])
    low = REF.run(_config(), 7, [(hist, np.arange(48))], precision=precision, params=ref["init"])["logits"][0]
    assert _gaps(low, ref["logits"][0]).max() > least > F32_TOLERANCE


class _BFloat16State(GigaChat35_EP32):
    """The prompt's delta rule, one position at a time, with its state
    rounded to bfloat16 at every chunk's end."""

    def _gdn_prompt(self, p, h, n_real, chunk=pg.gdn_chunk):
        def rounded(q, k, v, beta, g, n, *, chunk):
            T, C = q.shape[0], chunk
            state = jnp.zeros((v.shape[1], q.shape[2], v.shape[2]), jnp.float32)
            outs = []
            for s in range(0, T, C):
                o, state = _carry(q[s:s + C], k[s:s + C], v[s:s + C], beta[s:s + C], g[s:s + C], n - s, state)
                outs.append(o)
                state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return jnp.concatenate(outs), state

        return super()._gdn_prompt(p, h, n_real, chunk=rounded)


def _carry(q, k, v, beta, g, n, state):
    """The recurrence over one chunk's rows from ``state``."""
    H = v.shape[1]
    qq, kk = (jnp.repeat(a.astype(jnp.float32), H // a.shape[1], axis=1) for a in (q, k))

    def one(M, x):
        t, q_t, k_t, v_t, b_t, g_t = x
        M_in = jnp.exp(g_t)[:, None, None] * M
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, M_in))
        M_new = jnp.where(t < n, M_in + k_t[:, :, None] * u[:, None, :], M)
        return M_new, jnp.einsum("hk,hkv->hv", q_t, M_new)

    last, o = jax.lax.scan(one, state, (jnp.arange(q.shape[0]), qq, kk, v.astype(jnp.float32),
                                        beta.astype(jnp.float32), g.astype(jnp.float32)))
    return o, last


def test_a_bfloat16_state_fails_the_float32_tolerance():
    model = _BFloat16State(_model(jnp.float32).recipe)
    params = _weights(model, seed=7)
    hist = _prompts([48], seed=1)[0]
    with jax.default_matmul_precision("highest"):
        x = model._prompt(params, jnp.asarray(hist), 48)[0]
        got = np.asarray(model._logits(params, x))
    gap = _gaps(got, REF.run(_config(), 7, [(hist, np.arange(48))])["logits"][0]).max()
    assert gap > 2 * F32_TOLERANCE, gap


# prompt lengths 1 to 5 (no prefill at all, then a prefill shorter than the convolution's window of 3 cached rows,
# one that fills it, two past it), 9 and 17 (8 and 16 cached: a page edge and a chunk edge), 45 (across six pages
# and three chunks, ending mid-chunk)
def test_prefill_then_decode_through_the_engine_agrees_with_the_references_full_forward():
    model = _model(jnp.float32)
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
        assert _gaps(logits[:, i], ref[i]).max() < F32_TOLERANCE, (len(prompt), _gaps(logits[:, i], ref[i]))
    assert eng.compile_count == 2 + 1  # the buckets of 16 and 48 these prompts use, and the one decode program
    assert eng._cache.free_list.conserved()


def test_the_programs_with_the_kernel_equal_the_programs_with_its_twin():
    model = _model(jnp.float32)
    params = _weights(model)
    tokens = jnp.asarray(_prompts([48], seed=3)[0])
    with jax.default_matmul_precision("highest"):
        a, latent_a, held_a = model._prompt(params, tokens, 37)
        b, latent_b, held_b = model._prompt(params, tokens, 37, chunk=pg.gdn_chunk_reference)
    # the output, the latent layer's rows, each delta-rule layer's window and its state at the real length
    leaves = jax.tree_util.tree_leaves
    for got, want in zip(leaves((a, latent_a, held_a)), leaves((b, latent_b, held_b))):
        assert _close(got, want, 1e-5)


def test_the_prefill_keeps_the_last_real_pre_convolution_rows_with_noughts_before_position_0():
    model = _model(jnp.float32)
    params = _weights(model)
    p = params["layers"][0]["mixer"]
    h = jax.nn.one_hot(jnp.asarray(_prompts([32], seed=4)[0]) % 64, 64)
    pre = np.asarray(model._pre_conv(p, h))
    for n_real in (1, 2, 3, 20):
        _, window, _ = model._gdn_prompt(p, h, n_real)
        want = np.concatenate([np.zeros((3, model.conv_width), np.float32), pre])[n_real:n_real + 3]
        assert np.array_equal(np.asarray(window), want), n_real


def test_the_published_stack_and_the_chips_share_of_it():
    model = GigaChat35_EP32()
    r = model.recipe
    assert r.layer_ids == (0, 3, 4, 5, 6) and r.full_attention_layers == tuple(range(3, 40, 4))
    assert model.kinds == (LINEAR, FULL, LINEAR, LINEAR, LINEAR) and model.dense == (True,) + (False,) * 4
    assert model.full_layers == (1,) and model.linear_layers == (0, 2, 3, 4)
    assert (r.d_model, r.d_dense, r.num_classes) == (7168, 18432, 16032)
    assert (r.gdn_key_heads, r.gdn_value_heads, r.gdn_head_dim, r.gdn_conv) == (32, 64, 128, 4)
    assert (r.n_heads, r.q_lora, r.kv_lora, r.qk_nope, r.qk_rope, r.v_head) == (64, 1536, 512, 128, 64, 128)
    assert (r.n_experts, r.experts_per_token, r.experts_held, r.d_expert, r.route_scale) == (256, 8, 8, 2048, 2.5)
    assert model.conv_width == 16384 and r.swiglu_limit == 10.0
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(a.size) for a in leaves) == 3_322_436_608  # 6.64 GB in bfloat16
    assert {a.dtype for a in leaves} == {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
    assert [l["mlp"]["bias"].shape for l in shapes["layers"][1:]] == [(256,)] * 4  # the selection bias, fp32
    assert get_model("gigachat35_ep32") is GigaChat35_EP32 and get_model("gigachat35_lm") is GigaChat35


def test_three_kinds_of_state_in_one_cache_manager():
    model = _model(jnp.bfloat16)
    spec = model.cache_spec(PAGE)
    assert spec["kind"] == "latent" and spec["donate"] and spec["paged_layers"] == 1
    assert spec["k_page"] == (PAGE, 32) and spec["v_page"] == (16, PAGE)
    cache = PagedKVCache(n_layers=spec["paged_layers"], page_size=PAGE, n_pages=24, max_seqs=3,
                         max_pages_per_seq=8, k_page=spec["k_page"], v_page=spec["v_page"],
                         dtype=spec["dtype"], kind=spec["kind"], slots=spec["slots"])
    assert cache.k_pool.shape == (1, 25, PAGE, 32) and set(cache.v_pool) == {"v", "conv", "state"}
    assert cache.v_pool["conv"].shape == (4, 3, 3, 128) and cache.v_pool["conv"].dtype == jnp.bfloat16
    assert cache.v_pool["state"].shape == (4, 3, 4, 16, 16) and cache.v_pool["state"].dtype == jnp.float32
    assert cache.pool_bytes_by_kind == {"latent": 25 * PAGE * (32 + 16) * 2, "conv": 4 * 3 * 3 * 128 * 2,
                                        "state": 4 * 3 * 4 * 16 * 16 * 4}
    assert cache.bytes_per_position_by_kind == {"latent": (32 + 16) * 2}  # a window and a state do not grow
    big = GigaChat35_EP32().cache_spec(128)
    assert big["k_page"] == (128, 512) and big["v_page"] == (64, 128) and big["paged_layers"] == 1
    assert big["slots"] == {"conv": {"layers": 4, "row": (3, 16384), "dtype": jnp.bfloat16},
                            "state": {"layers": 4, "row": (64, 128, 128), "dtype": jnp.float32}}


def test_the_rows_and_state_a_step_reads_by_hand():
    rows = _model(jnp.float32).cache_rows(np.asarray([0, 7, 100]))
    assert list(rows["latent_rows"]) == [1, 8, 101]
    assert list(rows["gdn_state_bytes"]) == [2 * 4 * 4 * 16 * 16 * 4] * 3  # read and written, 4 delta-rule layers
    big = GigaChat35_EP32().cache_rows(np.asarray([1900]))
    assert big["gdn_state_bytes"][0] == 2 * 4 * 64 * 128 * 128 * 4 and big["latent_rows"][0] == 1901


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
    prompts = _prompts([40, 2, 47, 1, 33, 20, 6, 45], seed=3)
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
    assert stats["tmpi_decode_conv_bytes"] == 4 * 3 * 3 * 128 * 2
    assert stats["tmpi_decode_state_bytes"] == 4 * 3 * 4 * 16 * 16 * 4
    assert stats["tmpi_decode_gdn_state_bytes"] == 2 * 4 * 4 * 16 * 16 * 4 and stats["tmpi_decode_latent_rows"] > 1
    for kind in ("latent", "conv", "state"):
        assert f'tmpi_decode_kv_pool_bytes{{kind="{kind}"}}' in text
    with open(tmp_path / "decode.jsonl") as f:
        records = [json.loads(line) for line in f]
    assert records and all(r["cache_kind"] == "latent" for r in records)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "decode.jsonl")) == []


@pytest.mark.parametrize("scope", ["gdn_step", "gdn_conv", "mla_decode", "mla_cache_write", "moe_routed"])
def test_the_decode_program_names_its_parts(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c = eng._cache
    assert scope in eng._decode.lower(params, c.k_pool, c.v_pool, eng._sched.step_arrays(0)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["gdn_chunk", "gdn_conv", "mla_prefill", "flash_fwd"])
def test_the_prefill_program_names_its_parts(scope):
    model = _model(jnp.bfloat16)
    eng = _engine(model)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    c, T = eng._cache, 48
    lowered = eng._prefill.lower(params, jnp.zeros((T,), jnp.int32), jnp.zeros((T // PAGE,), jnp.int32),
                                 c.k_pool, c.v_pool, np.int32(0), np.int32(0))
    assert scope in lowered.as_text(debug_info=True)


def test_tmpi_serve_decode_serves_it_from_a_checkpoint_by_its_zoo_name(tmp_path):
    # the normal path: the CLI, a training checkpoint's leaves, the same engine; no side script
    import subprocess
    import sys

    from theanompi_tpu.tools.check_obs_schema import validate_record
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = GigaChat35()
    save_checkpoint(str(tmp_path), init_train_state(model, jax.random.PRNGKey(0)), 3, rng=jax.random.PRNGKey(1))
    done = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "serve", "--ckpt-dir", str(tmp_path), "--model",
         "gigachat35_lm", "--decode", "--prefill-buckets", "16,64", "--kv-pages", "64", "--page-size", "8",
         "--max-seqs", "4", "--max-new-tokens", "4", "--selftest", "5", "--obs-dir", str(tmp_path / "obs")],
        cwd=os.path.dirname(manifest.BENCH_DIR), env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    stats = json.loads(done.stdout.strip().splitlines()[-1])
    assert stats["kind"] == "decode" and stats["params_step"] == 3 and stats["cache_kind"] == "latent"
    assert stats["metrics"]["tmpi_decode_served_total"] == 5.0 and stats["metrics"]["tmpi_decode_failed_total"] == 0.0
    assert stats["metrics"]["tmpi_decode_kv_pages_out_total"] == stats["metrics"]["tmpi_decode_kv_pages_in_total"]
    assert stats["metrics"]["tmpi_decode_state_bytes"] > 0 and validate_record(stats) == []
