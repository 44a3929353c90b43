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


def make_cache(arch, n_pages=16, max_seqs=2, max_pages_per_seq=8):
    page = (PAGE, arch.n_heads, arch.d_model // arch.n_heads)
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


def decode_once(arch, params, cache, slots):
    """One decode iteration; ``slots`` maps slot -> (seq_len, last_tok,
    temperature). Returns the [S] next-token array."""
    S = cache.max_seqs
    seq_lens = np.zeros((S,), np.int32)
    last = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    temp = np.zeros((S,), np.float32)
    for s, (sl, lt, tp) in slots.items():
        seq_lens[s], last[s], active[s], temp[s] = sl, lt, True, tp
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
    return int(jnp.argmax(logits[0, -1].astype(jnp.float32)))


@pytest.mark.parametrize("prompt_len", [1, 2, 5, 9])
def test_incremental_greedy_matches_full_forward(prompt_len):
    arch, params = tiny_lm()
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
