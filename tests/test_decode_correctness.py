"""Decode-path correctness: incremental paged-KV decode must be
bit-identical (greedy argmax at EVERY step) to the full-context training
forward — the oracle that proves the cache gather/scatter, position
offsets, and masking are right. Plus pad/bucket identity for prefill
and determinism of temperature sampling under an explicit key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.serve.decode.kvcache import PagedKVCache, pages_needed

PAGE = 4


def tiny_lm(**kw):
    cfg = dict(vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64,
               max_len=64, attn="ring")
    cfg.update(kw)
    arch = TransformerLM(**cfg)
    params = arch.init(jax.random.PRNGKey(0))
    return arch, params


def tiny_moe():
    """The MoE LM through the same two paged functions (its ``ffn=`` hook).
    A capacity no token can exceed: the training forward then drops nothing
    and is the oracle of the dense top-1 decode FFN."""
    from theanompi_tpu.models.moe import MoETransformerLM

    arch = MoETransformerLM(vocab=32, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_len=64, n_experts=4,
                            capacity_factor=4.0, attn="ring")
    return arch, arch.init(jax.random.PRNGKey(1))


ARCHS = {"dense": tiny_lm, "moe": tiny_moe}


def make_cache(arch, n_pages=16, max_seqs=2, max_pages_per_seq=8):
    page = (PAGE, arch.d_model)  # n_heads * head_dim: lane-dense rows
    return PagedKVCache(
        n_layers=arch.n_layers, page_size=PAGE, k_page=page, v_page=page,
        n_pages=n_pages, max_seqs=max_seqs,
        max_pages_per_seq=max_pages_per_seq,
    )


def run_prefill(arch, params, cache, slot, prompt, bucket=None):
    """Cache positions 0..len(prompt)-2 of ``slot``'s reserved pages,
    padded to ``bucket`` (default: smallest page-multiple)."""
    n_cache = len(prompt) - 1
    if n_cache <= 0:
        return
    Tb = bucket or pages_needed(n_cache, PAGE) * PAGE
    toks = np.zeros((Tb,), np.int32)
    toks[:n_cache] = prompt[:n_cache]
    pages = np.full((Tb // PAGE,), cache.scratch, np.int32)
    npg = pages_needed(n_cache, PAGE)
    pages[:npg] = cache.page_tables[slot, :npg]
    cache.k_pool, cache.v_pool = arch.prefill_cache(
        params, jnp.asarray(toks), jnp.asarray(pages),
        cache.k_pool, cache.v_pool, page_size=PAGE,
    )


def decode_once(arch, params, cache, slots, stale=None):
    """One decode iteration; ``slots`` maps slot -> (seq_len, last_tok,
    temperature), ``stale`` an INACTIVE slot -> the seq_len it is left
    with. Returns the [S] next-token array."""
    S = cache.max_seqs
    seq_lens = np.zeros((S,), np.int32)
    last = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    temp = np.zeros((S,), np.float32)
    for s, (sl, lt, tp) in slots.items():
        seq_lens[s], last[s], active[s], temp[s] = sl, lt, True, tp
    for s, sl in (stale or {}).items():
        seq_lens[s] = sl
    nxt, _logits, cache.k_pool, cache.v_pool = arch.decode_step(
        params, cache.k_pool, cache.v_pool,
        jnp.asarray(cache.page_tables), jnp.asarray(seq_lens),
        jnp.asarray(last), jnp.asarray(active), jnp.asarray(temp),
        jax.random.PRNGKey(0), page_size=PAGE,
    )
    return np.asarray(nxt)


def greedy_generate(arch, params, cache, slot, prompt, n_new, bucket=None):
    cache.reserve(slot, len(prompt) + n_new)
    run_prefill(arch, params, cache, slot, prompt, bucket=bucket)
    out, seq_len, last = [], len(prompt) - 1, prompt[-1]
    for _ in range(n_new):
        nxt = decode_once(arch, params, cache, {slot: (seq_len, last, 0.0)})
        last = int(nxt[slot])
        out.append(last)
        seq_len += 1
    return out


def oracle_next(arch, params, ctx):
    """Full-context forward's greedy next token."""
    logits = arch.forward(
        params, jnp.asarray(np.asarray(ctx, np.int32))[None]
    )
    if isinstance(logits, tuple):  # the MoE forward: (logits, aux, dropped)
        assert float(logits[2]) == 0.0  # nothing dropped: a sound oracle
        logits = logits[0]
    return int(jnp.argmax(logits[0, -1].astype(jnp.float32)))


@pytest.mark.parametrize("prompt_len", [1, 2, 5, 9])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_incremental_greedy_matches_full_forward(kind, prompt_len):
    """``prompt_len`` 1: nothing is prefilled and the first step's softmax
    sees the step's own row only; 5 and 9: the first decoded row lands on
    a page's first offset, and the 8 steps cross every offset of a page."""
    arch, params = ARCHS[kind]()
    cache = make_cache(arch)
    rng = np.random.RandomState(prompt_len)
    prompt = [int(t) for t in rng.randint(0, arch.vocab, size=prompt_len)]
    n_new = 8
    got = greedy_generate(arch, params, cache, 0, prompt, n_new)
    ctx = list(prompt)
    for step, tok in enumerate(got):
        want = oracle_next(arch, params, ctx)
        assert tok == want, (
            f"step {step}: incremental {tok} != full-context {want} "
            f"(ctx len {len(ctx)})"
        )
        ctx.append(tok)
    cache.release(0)
    assert cache.free_list.conserved()


def noise_pools(cache, seed=0):
    """Both pools filled with noise: an untouched byte is then told from a
    rewritten one, and a read past the mask would show in the tokens."""
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    cache.k_pool = jax.random.normal(kk, cache.k_pool.shape, cache.k_pool.dtype)
    cache.v_pool = jax.random.normal(kv, cache.v_pool.shape, cache.v_pool.dtype)


# (architecture, slot 0's prompt length, is slot 0 active?). The step writes
# position prompt_len - 1: offset (prompt_len - 1) % PAGE of its page.
WRITE_CASES = {
    "page_first_offset": ("dense", 2 * PAGE + 1, True),
    "page_last_offset": ("dense", 2 * PAGE, True),
    "no_cached_row": ("dense", 1, True),
    "inactive_slot": ("dense", PAGE + 2, False),
    "moe_first_offset": ("moe", PAGE + 1, True),
    "moe_last_offset": ("moe", PAGE, True),
}


@pytest.mark.parametrize("case", WRITE_CASES)
def test_decode_step_writes_one_row_a_layer_and_nothing_else(case):
    """One step over two slots: slot 1 always decodes, slot 0 by the case.
    Every byte of both pools outside the active slots' (page, offset) rows
    and the scratch page is bit-identical before and after; an inactive
    slot's row goes to the scratch page though its table still names real
    pages; the active slots' tokens are the full forward's, now and one
    step later (the row just written is read back from the pool)."""
    kind, prompt_len, slot0_active = WRITE_CASES[case]
    arch, params = ARCHS[kind]()
    cache = make_cache(arch)
    noise_pools(cache)
    rng = np.random.RandomState(prompt_len)
    prompts = {0: [int(t) for t in rng.randint(0, arch.vocab, size=prompt_len)],
               1: [int(t) for t in rng.randint(0, arch.vocab, size=3)]}
    for s, pr in prompts.items():
        cache.reserve(s, len(pr) + 4)
        run_prefill(arch, params, cache, s, pr)
    state = {s: (len(pr) - 1, pr[-1], 0.0) for s, pr in prompts.items()}
    stale = {}
    if not slot0_active:  # table and length still name a real page's row
        stale = {0: state.pop(0)[0]}
    ctx = {s: list(prompts[s]) for s in state}
    for step in range(2):
        before = np.asarray(cache.k_pool), np.asarray(cache.v_pool)
        nxt = decode_once(arch, params, cache, state, stale)
        touched = np.zeros(before[0].shape[:3], bool)
        touched[:, cache.scratch] = True
        for s, (sl, _lt, _tp) in state.items():
            touched[:, cache.page_tables[s, sl // PAGE], sl % PAGE] = True
        for was, pool in zip(before, (cache.k_pool, cache.v_pool)):
            now = np.asarray(pool)
            assert now.shape == was.shape and now.dtype == was.dtype
            assert np.array_equal(now[~touched], was[~touched])
            for s, (sl, _lt, _tp) in state.items():  # and the rows ARE new
                row = (slice(None), cache.page_tables[s, sl // PAGE], sl % PAGE)
                assert not np.array_equal(now[row], was[row])
        for s in state:
            want = oracle_next(arch, params, ctx[s])
            assert int(nxt[s]) == want, f"step {step} slot {s}"
            ctx[s].append(want)
            state[s] = (state[s][0] + 1, want, 0.0)


def test_prefill_pad_bucket_identity():
    """The same prompt prefilled into a LARGER padded bucket must decode
    identically — padding can only touch the scratch page and masked
    offsets."""
    arch, params = tiny_lm()
    prompt = [3, 7, 1, 9, 4]  # n_cache=4 -> minimal bucket 4, padded 16
    c1, c2 = make_cache(arch), make_cache(arch)
    out1 = greedy_generate(arch, params, c1, 0, prompt, 6, bucket=4)
    out2 = greedy_generate(arch, params, c2, 0, prompt, 6, bucket=16)
    assert out1 == out2


def test_two_slots_decode_independently():
    """Two sequences in the SAME batch must each match their solo run —
    slot isolation through the page tables."""
    arch, params = tiny_lm()
    pa = [5, 2, 8]
    pb = [11, 4, 6, 1, 13, 9, 2]
    solo_a = greedy_generate(arch, params, make_cache(arch), 0, pa, 5)
    solo_b = greedy_generate(arch, params, make_cache(arch), 0, pb, 5)

    cache = make_cache(arch)
    cache.reserve(0, len(pa) + 5)
    cache.reserve(1, len(pb) + 5)
    run_prefill(arch, params, cache, 0, pa)
    run_prefill(arch, params, cache, 1, pb)
    st = {0: [len(pa) - 1, pa[-1]], 1: [len(pb) - 1, pb[-1]]}
    got = {0: [], 1: []}
    for _ in range(5):
        nxt = decode_once(
            arch, params, cache,
            {s: (sl, lt, 0.0) for s, (sl, lt) in st.items()},
        )
        for s in (0, 1):
            tok = int(nxt[s])
            got[s].append(tok)
            st[s] = [st[s][0] + 1, tok]
    assert got[0] == solo_a
    assert got[1] == solo_b


def test_temperature_sampling_deterministic_under_key():
    arch, params = tiny_lm()

    def sample_run(key_seed):
        cache = make_cache(arch)
        cache.reserve(0, 2 + 6)
        run_prefill(arch, params, cache, 0, [3, 5])
        out, seq_len, last = [], 1, 5
        for it in range(6):
            S = cache.max_seqs
            seq_lens = np.zeros((S,), np.int32)
            lastt = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temp = np.zeros((S,), np.float32)
            seq_lens[0], lastt[0], active[0], temp[0] = seq_len, last, 1, 0.8
            key = jax.random.fold_in(jax.random.PRNGKey(key_seed), it)
            nxt, _l, cache.k_pool, cache.v_pool = arch.decode_step(
                params, cache.k_pool, cache.v_pool,
                jnp.asarray(cache.page_tables), jnp.asarray(seq_lens),
                jnp.asarray(lastt), jnp.asarray(active), jnp.asarray(temp),
                key, page_size=PAGE,
            )
            last = int(np.asarray(nxt)[0])
            assert 0 <= last < arch.vocab
            out.append(last)
            seq_len += 1
        return out

    assert sample_run(7) == sample_run(7)  # same key stream -> same tokens


def test_moe_decode_smoke():
    """MoE incremental decode runs, is deterministic, and its prefill
    matches the dense plumbing's slot isolation (the Switch FFN at
    decode is dense top-1 — see models/moe.py::moe_decode_ffn)."""
    from theanompi_tpu.models.moe import MoETransformerLM

    arch = MoETransformerLM(vocab=32, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_len=64, n_experts=4, attn="ring")
    params = arch.init(jax.random.PRNGKey(1))
    cache = make_cache(arch)
    out1 = greedy_generate(arch, params, cache, 0, [4, 9, 2], 5)
    cache.release(0)
    out2 = greedy_generate(arch, params, make_cache(arch), 0,
                           [4, 9, 2], 5)
    assert out1 == out2
    assert all(0 <= t < 32 for t in out1)
