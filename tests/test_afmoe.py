"""The kinds-built LM (models/afmoe.py), its routed layer (ops/moe.py
routed_experts) and the launch path, each against the plain float32
reference of the benchmark, imported by path from
``benchmark/reference/trinity-mini.py`` at the configuration's ``tiny``
sizes: same seeded weights, float32 compute, CPU."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from theanompi_tpu.models.afmoe import COUNTERS, AfmoeLM
from theanompi_tpu.train import init_train_state, make_train_step

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
    c = {**c, **c["tiny"]}
    # one layer of each kind (dense window, routed window, routed full):
    # the rehearsal's five cost the CPU three times the compile
    kinds = [c["layers_run"][i] for i in (0, 1, 4)]
    c["layers_run"] = kinds
    c["recipe_overrides"] = {**c["recipe_overrides"], "layers": kinds}
    return c


def _model(cfg, **over):
    r = AfmoeLM.default_recipe().replace(**{**cfg["recipe_overrides"], **over})
    return AfmoeLM(r)


def _tokens(cfg, seed):
    return jax.random.randint(jax.random.PRNGKey(100 + seed),
                              (cfg["batch_size"], cfg["seq_len"]), 0, cfg["vocab"])


def _close(a, b, tol):
    """Norm of the difference over the reference's norm (or 1)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) <= tol * max(np.linalg.norm(b), 1e-6)


# -- (a) the whole model: weights, logits, loss, every gradient leaf ---------
@pytest.mark.parametrize("seed", [0, 3])
def test_model_matches_the_plain_reference(ref, cfg, seed):
    model = _model(cfg)
    params, state = model.init(jax.random.PRNGKey(seed))
    p_ref = ref.init(cfg, seed)
    assert (jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(p_ref))
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(p_ref)):
        assert np.array_equal(a, b)  # init_gap is 0

    tok = _tokens(cfg, seed)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(7), state["router_bias"].shape)
    state = {**state, "router_bias": bias}

    def loss(p):
        logits, new = model.apply(p, state, tok, train=True)
        return model.loss(logits, tok), (logits, new)

    (l, (logits, new)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    denom = tok.shape[0] * (tok.shape[1] - 1)
    (l_ref, counts), g_ref = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_sum(cfg, p, tok, bias), has_aux=True))(p_ref)
    logits_ref, _ = jax.jit(lambda p: ref.forward(cfg, p, tok, bias))(p_ref)

    assert _close(logits, logits_ref, 1e-5)
    assert abs(float(l) - float(l_ref) / denom) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), gr in zip(flat, jax.tree_util.tree_leaves(g_ref)):
        assert _close(g, gr / denom, 2e-4), jax.tree_util.keystr(path)
    # the state: the bias moved by the reference's rule, the counters count
    assert np.allclose(new["router_bias"], ref.bias_step(cfg, bias, counts), atol=1e-7)
    pairs = tok.size * cfg["num_experts_per_tok"] * ref.n_routed(cfg)
    c = new["counters"]
    assert float(c["moe_pairs_here"] + c["moe_pairs_absent"]) == pairs
    assert 0 < float(c["moe_pairs_absent"]) < pairs  # half of the experts are elsewhere
    assert float(c["moe_load_max_over_mean"]) >= 1.0


# -- (b) three optimizer steps, the bias update with them -------------------
def test_three_steps_follow_the_reference(ref, cfg):
    seed = 5
    model = _model(cfg)
    state = init_train_state(model, jax.random.PRNGKey(seed))
    p0 = state.params
    batches = [(np.asarray(_tokens(cfg, s)), None) for s in (1, 2, 3)]
    out = ref.follow(cfg, seed, batches)
    step = jax.jit(make_train_step(model))
    losses = []
    for x, _ in batches:
        state, m = step(state, jnp.asarray(x), jnp.asarray(x), jax.random.PRNGKey(0))
        losses.append(float(m["loss"]))
        assert set(COUNTERS) <= set(m)
    assert np.allclose(losses, out["losses"], rtol=1e-5)
    assert np.allclose(state.model_state["router_bias"], out["bias"], atol=1e-7)
    assert float(jnp.abs(out["bias"]).max()) > 0
    flat, _ = jax.tree_util.tree_flatten_with_path(state.params)
    for (path, a), b, z in zip(flat, jax.tree_util.tree_leaves(out["params"]),
                               jax.tree_util.tree_leaves(p0)):
        moved = np.linalg.norm(np.asarray(b) - np.asarray(z))
        assert np.linalg.norm(np.asarray(a) - np.asarray(b)) <= 0.02 * moved + 1e-7, \
            jax.tree_util.keystr(path)


# -- (g) through run_training ------------------------------------------------------
@pytest.mark.parametrize("devices", [1, 2])
def test_three_steps_through_run_training_with_counters_in_the_rows(cfg, devices):
    from theanompi_tpu.launch.worker import run_training

    s = run_training(rule="bsp", model_cls=AfmoeLM, devices=devices,
                     recipe_overrides=dict(cfg["recipe_overrides"]),
                     dataset_kwargs=dict(n_train=16, n_val=4), max_steps=3,
                     print_freq=1000, return_recorder=True)
    rows = s["recorder"].history["train"]
    assert s["steps"] == 3 and len(rows) == 3
    pairs = cfg["batch_size"] // devices * cfg["seq_len"] * cfg["num_experts_per_tok"] * 2
    for row in rows:
        assert np.isfinite(row["loss"])
        assert set(COUNTERS) <= set(row)
        assert row["moe_pairs_here"] + row["moe_pairs_absent"] == pairs
        assert row["moe_pad_rows"] >= 0 and row["moe_load_max_over_mean"] >= 1.0


def test_the_zoo_knows_both_classes_and_the_cut_keeps_the_published_widths():
    from theanompi_tpu.models import get_model
    from theanompi_tpu.models.afmoe import TrinityMini_EP8

    assert get_model("afmoe_lm") is AfmoeLM
    assert get_model("trinity_mini_ep8") is TrinityMini_EP8
    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        c = json.load(f)
    m = TrinityMini_EP8()
    r = m.recipe
    assert (r.d_model, r.n_heads, r.n_kv_heads, r.head_dim, r.d_ff, r.d_expert) == (
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        c["intermediate_size"], c["moe_intermediate_size"])
    assert (r.n_experts, r.experts_per_token, r.experts_held, r.num_classes) == (
        c["num_experts"], c["num_experts_per_tok"], c["num_experts_held"], c["vocab"])
    assert [list(k) for k in m.kinds] == c["layers_run"]
    shapes = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0))[0])
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) == 705_473_792


def test_a_share_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="not a share"):
        AfmoeLM(AfmoeLM.default_recipe().replace(first_expert=8, experts_held=16))
