"""Top-k routing without dropped tokens over a share of the experts
(ops/moe.py route_topk, routed_experts) against the plain float32
reference's routed FFN, imported by path from
``benchmark/reference/trinity-mini.py``, at the configuration's ``tiny``
sizes: the shares add up, and nothing drops under imbalance."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.ops.moe import route_topk, routed_experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/trinity-mini.py", "ref_trinity_mini")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        c = json.load(f)
    return {**c, **c["tiny"]}


def _close(a, b, tol):
    """Norm of the difference over the reference's norm (or 1)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-6)


# -- the routed layer alone ---------------------------------------------------
def _layer_params(cfg, seed, held):
    d, fe, E = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda key, *s: 0.3 * jax.random.normal(key, s)  # noqa: E731
    return {"router": n(k[0], d, E),
            "shared": {"w1": n(k[1], d, fe), "w3": n(k[2], d, fe), "w2": n(k[3], fe, d)},
            "experts": {"w1": n(k[4], held, d, fe), "w3": n(k[5], held, d, fe),
                        "w2": n(k[6], held, fe, d)}}, jax.random.normal(k[7], (96, d))


def _program_routed(cfg, p, h, bias, first, tm=8):
    idx, w = route_topk(h, p["router"], bias, cfg["num_experts_per_tok"], cfg["route_scale"])
    ex = p["experts"]
    return routed_experts(h, idx, w, ex["w1"], ex["w3"], ex["w2"], first,
                          cfg["num_experts"], tm=tm)


# -- (c) the shares add up ------------------------------------------------------
def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer(ref, cfg):
    E = cfg["num_experts"]
    p, h = _layer_params(cfg, 11, held=E)
    bias = jnp.zeros((E,))
    op = lambda a: a  # noqa: E731
    whole, counts = ref.routed_ffn(cfg, p, h, bias, op, first=0)
    total = ref.swiglu(p["shared"], h, op)  # what every chip computes alike: once
    here = absent = 0
    for first in range(0, E, E // 4):
        share = {**p, "experts": jax.tree_util.tree_map(
            lambda a: a[first:first + E // 4], p["experts"])}
        y, stats = _program_routed(cfg, share, h, bias, first)
        y_ref, _ = ref.routed_ffn(cfg, share, h, bias, op, first=first, shared=False)
        assert _close(y, y_ref, 1e-5)
        assert np.array_equal(stats.counts, counts)
        total = total + y
        here, absent = here + int(stats.pairs_here), absent + int(stats.pairs_absent)
    assert _close(total, whole, 1e-5)
    pairs = h.shape[0] * cfg["num_experts_per_tok"]
    assert here == pairs and absent == 3 * pairs  # every pair computed on one share


# -- (d) no drop under imbalance --------------------------------------------------
@pytest.mark.parametrize("rig", ["all_on_the_same_experts", "an_expert_without_a_row",
                                 "everything_elsewhere"])
def test_nothing_drops_under_imbalance(ref, cfg, rig):
    E, k, held = cfg["num_experts"], cfg["num_experts_per_tok"], cfg["num_experts_held"]
    p, h = _layer_params(cfg, 13, held=held)
    bias = {"all_on_the_same_experts": jnp.zeros((E,)).at[:k].set(10.0),
            "an_expert_without_a_row": jnp.zeros((E,)).at[2].set(-10.0).at[E - 1].set(10.0),
            "everything_elsewhere": jnp.zeros((E,)).at[held:held + k].set(10.0)}[rig]
    op = lambda a: a  # noqa: E731

    def prog(p, h):
        y, stats = _program_routed(cfg, p, h, bias, 0)
        return jnp.sum(jnp.sin(y)), (y, stats)

    def plain(p, h):
        y, _ = ref.routed_ffn(cfg, p, h, bias, op, first=0, shared=False)
        return jnp.sum(jnp.sin(y)), y

    (_, (y, stats)), (gp, gh) = jax.value_and_grad(prog, (0, 1), has_aux=True)(p, h)
    (_, y_ref), (gp_ref, gh_ref) = jax.value_and_grad(plain, (0, 1), has_aux=True)(p, h)
    pairs = h.shape[0] * k
    if rig == "all_on_the_same_experts":
        # the worst case the buffer is sized for: every pair lands here
        assert int(stats.pairs_here) == pairs and int(stats.pairs_absent) == 0
        assert float(stats.load_max_over_mean) == held / k
    elif rig == "an_expert_without_a_row":
        assert int(stats.counts[2]) == 0 and 0 < int(stats.pairs_absent) < pairs
        assert not np.any(np.asarray(gp["experts"]["w1"][2]))
    else:
        assert int(stats.pairs_here) == 0 and not np.any(np.asarray(y))
    assert int(stats.pairs_here) + int(stats.pairs_absent) == pairs
    assert int(stats.counts.sum()) == pairs
    assert _close(y, y_ref, 1e-5) and _close(gh, gh_ref, 1e-4)
    for name in ("w1", "w3", "w2"):
        assert _close(gp["experts"][name], gp_ref["experts"][name], 1e-4), name
    assert _close(gp["router"], gp_ref["router"], 1e-4)


def test_bf16_rows_keep_their_dtype_and_the_router_its_fp32(cfg):
    p, h = _layer_params(cfg, 17, held=cfg["num_experts_held"])
    idx, w = route_topk(h.astype(jnp.bfloat16), p["router"], jnp.zeros(()), 4, 2.826)
    assert w.dtype == jnp.float32
    ex = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p["experts"])
    y, _ = routed_experts(h.astype(jnp.bfloat16), idx, w, ex["w1"], ex["w3"], ex["w2"],
                          0, cfg["num_experts"], tm=8)
    assert y.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))


