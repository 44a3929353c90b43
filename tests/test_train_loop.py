"""The driver's one train loop (``launch/worker.py run_training``) under every
engine: BSP, EASGD, GoSGD, ZeRO-1 and an ND layout (tensor parallel).

Two steps in flight (the default dispatch depth) must give the recorder rows
and the checkpointed key-stream carry of the classic per-step sync, and a
``max_steps`` in the middle of an epoch must stop on exactly that step, with
the checkpoint, the rows and the key chain all at that step.
"""

import json
import os

import jax
import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.models.lm import TransformerLMModel
from theanompi_tpu.utils.checkpoint import checkpoint_step, latest_checkpoint, load_checkpoint

STEPS_PER_EPOCH = 4
_CNN = dict(
    model_cls=TinyCNN, devices=8, print_freq=0, dataset="synthetic",
    recipe_overrides={"batch_size": 32, "input_shape": (16, 16, 3),
                      "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]}},
    dataset_kwargs={"n_train": 32 * STEPS_PER_EPOCH, "n_val": 32,
                    "image_shape": (16, 16, 3)},
)
# the per-worker rules take batch_size per worker: 8 workers x 4 = 32 a step
_PER_WORKER = {**_CNN, "recipe_overrides": {**_CNN["recipe_overrides"], "batch_size": 4}}
ENGINES = {
    "bsp": dict(_CNN, rule="bsp"),
    # an elastic exchange (and its pipeline flush) every second step
    "easgd": dict(_PER_WORKER, rule="easgd", avg_freq=2),
    "gosgd": dict(_PER_WORKER, rule="gosgd"),
    "zero1": dict(_CNN, rule="bsp", zero=1),
    "nd_tp2": dict(
        model_cls=TransformerLMModel, devices=8, print_freq=0, rule="bsp", tp=2,
        recipe_overrides={"batch_size": 16, "d_model": 32, "n_heads": 4,
                          "n_layers": 1, "d_ff": 64, "input_shape": (32,),
                          "num_classes": 32, "sched_kwargs": {"lr": 3e-3}},
        dataset_kwargs={"n_train": 16 * STEPS_PER_EPOCH, "n_val": 16},
    ),
}
SEED = 5


def _bits(key):
    if jax.numpy.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key)


def _carry_after(n):
    """The key chain's carry after ``n`` steps: ``rng, _ = split(rng)``."""
    rng = jax.random.PRNGKey(SEED)
    for _ in range(n):
        rng, _ = jax.random.split(rng)
    return _bits(rng)


def _run(tmp_path, engine, tag, **kw):
    """-> (summary, recorder rows without their wall-clock fields,
    (step, carry) of the newest checkpoint)."""
    save, ckpt = str(tmp_path / tag / "log"), str(tmp_path / tag / "ckpt")
    summary = run_training(seed=SEED, save_dir=save, run_name="run", ckpt_dir=ckpt,
                           **{**ENGINES[engine], **kw})
    rows = []
    with open(os.path.join(save, "run.jsonl")) as f:
        for line in f:
            r = json.loads(line)
            r.pop("images_per_sec", None)
            if r.get("kind") == "epoch":
                r.pop("seconds", None)
            rows.append(r)
    path = latest_checkpoint(ckpt, verify=True)
    _, carry = load_checkpoint(path, None)
    return summary, rows, (checkpoint_step(path), _bits(carry))


@pytest.mark.parametrize("engine", list(ENGINES))
def test_two_steps_in_flight_give_the_rows_and_carry_of_one(engine, tmp_path):
    s1, rows1, (step1, carry1) = _run(tmp_path, engine, "depth1", n_epochs=2,
                                      dispatch_depth=1)
    s2, rows2, (step2, carry2) = _run(tmp_path, engine, "depth2", n_epochs=2,
                                      dispatch_depth=2)
    steps = 2 * STEPS_PER_EPOCH
    assert (s1["dispatch_depth"], s2["dispatch_depth"]) == (1, 2)
    assert s1["steps"] == s2["steps"] == s1["device_steps"] == s2["device_steps"] == steps
    assert [r["step"] for r in rows2 if r["kind"] == "train"] == list(range(1, steps + 1))
    assert rows1 == rows2
    assert step1 == step2 == steps
    np.testing.assert_array_equal(carry1, carry2)
    np.testing.assert_array_equal(carry2, _carry_after(steps))


@pytest.mark.parametrize("max_steps", [3, STEPS_PER_EPOCH + 2],
                         ids=["first_epoch", "second_epoch"])
@pytest.mark.parametrize("engine", list(ENGINES))
def test_max_steps_mid_epoch_stops_on_that_step(engine, max_steps, tmp_path):
    summary, rows, (step, carry) = _run(tmp_path, engine, "run", n_epochs=3,
                                        max_steps=max_steps)
    assert summary["steps"] == summary["device_steps"] == max_steps
    assert summary["epochs"] == list(range(max_steps // STEPS_PER_EPOCH + 1))
    assert [r["step"] for r in rows if r["kind"] == "train"] == list(range(1, max_steps + 1))
    assert step == max_steps
    np.testing.assert_array_equal(carry, _carry_after(max_steps))
