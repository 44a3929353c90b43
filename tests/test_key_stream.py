"""The driver's key stream (ISSUE 27, ``utils/dispatch.py KeyStream``): the
chain ``rng, sub = jax.random.split(rng)`` of one split a step, made one
step ahead by ONE jitted program. Whatever is made ahead, the keys
handed out and the committed carry are the eager chain's, bit for bit.
"""

import jax
import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.parallel.bsp import BSPEngine
from theanompi_tpu.tools.check_hot_loop import WORKER_PATH, check_source
from theanompi_tpu.utils import dispatch
from theanompi_tpu.utils.checkpoint import latest_checkpoint, load_checkpoint
from theanompi_tpu.utils.dispatch import KeyStream

STEPS = 8
_TINY = dict(
    rule="bsp", model_cls=TinyCNN, devices=1, n_epochs=1, print_freq=0,
    recipe_overrides={
        "batch_size": 32,
        "input_shape": (16, 16, 3),
        "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]},
    },
    dataset="synthetic",
    dataset_kwargs={"n_train": 32 * STEPS, "n_val": 32, "image_shape": (16, 16, 3)},
)


def _bits(key):
    if jax.numpy.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key)


def _eager_chain(rng, n):
    """-> ([sub_1 .. sub_n], [carry after each]) of the driver's old loop."""
    subs, carries = [], []
    for _ in range(n):
        rng, sub = jax.random.split(rng)
        subs.append(_bits(sub))
        carries.append(_bits(rng))
    return subs, carries


def _start(impl):
    if impl == "default":
        return jax.random.PRNGKey(7)  # raw uint32 data under the framework's rbg
    # what a checkpoint written under another implementation restores to
    return jax.random.wrap_key_data(np.array([3, 11], np.uint32), impl="threefry2x32")


@pytest.mark.parametrize("refills", [0, 1, 2], ids=["never", "after_every_take", "twice"])
def test_take_one_gives_the_eager_chain_whatever_was_made_ahead(refills):
    subs, carries = _eager_chain(jax.random.PRNGKey(7), 20)
    keys = KeyStream(jax.random.PRNGKey(7))
    for sub, carry in zip(subs, carries):
        np.testing.assert_array_equal(_bits(keys.take()), sub)
        np.testing.assert_array_equal(_bits(keys.carry), carry)  # a checkpoint's rng=
        for _ in range(refills):
            keys.refill()
    assert keys.taken == 20
    assert keys.taken_ready == (19 if refills else 0)
    assert keys.ready_share == (0.95 if refills else 0.0)
    assert KeyStream(jax.random.PRNGKey(7)).ready_share is None  # nothing taken


def test_a_refill_makes_one_key_and_leaves_the_carry_where_it_was():
    subs, carries = _eager_chain(jax.random.PRNGKey(7), 3)
    keys = KeyStream(jax.random.PRNGKey(7))
    keys.refill()
    ready = keys._ready
    keys.refill()  # already ready: nothing is made
    assert keys._ready is ready
    # made ahead, not taken: the committed carry is still the start
    np.testing.assert_array_equal(_bits(keys.carry), _bits(jax.random.PRNGKey(7)))
    for i in range(3):
        np.testing.assert_array_equal(_bits(keys.take()), subs[i])
        np.testing.assert_array_equal(_bits(keys.carry), carries[i])
    # the first key was ready, the next two were made on the spot
    assert (keys.taken, keys.taken_ready) == (3, 1)


@pytest.mark.parametrize("impl", ["default", "threefry2x32"])
def test_a_stream_rebuilt_from_a_carry_continues_and_drops_what_was_ready(impl):
    subs, carries = _eager_chain(_start(impl), 10)
    keys = KeyStream(_start(impl))
    for i in range(6):
        np.testing.assert_array_equal(_bits(keys.take()), subs[i])
        keys.refill()
    assert keys._ready is not None  # key 7, made ahead
    saved = keys.carry  # the checkpoint after step 6 ...
    for i in range(6, 9):
        keys.take()
        keys.refill()
    keys.reset(saved)  # ... restored after step 9: the rollback
    assert keys._ready is None
    for stream in (keys, KeyStream(saved)):  # and the resume in a new process
        for i in range(6, 10):
            sub = stream.take()
            np.testing.assert_array_equal(_bits(sub), subs[i])
            np.testing.assert_array_equal(_bits(stream.carry), carries[i])
            stream.refill()
        if impl != "default":
            assert str(jax.random.key_impl(sub)) == str(jax.random.key_impl(saved))


def test_the_split_is_one_compiled_program():
    dispatch._split.clear_cache()
    keys = KeyStream(jax.random.PRNGKey(3))
    for _ in range(20):
        keys.take()
        keys.refill()
    # made on the spot from PRNGKey's carry or ahead from a split's own output:
    # one signature, one program
    assert dispatch._split._cache_size() == 1


def test_no_eager_split_is_left_in_the_driver():
    with open(WORKER_PATH) as f:
        src = f.read()
    assert "KeyStream" in src and check_source(src) == []
    reborn = src.replace("keys.take()\n", "rng, _ = jax.random.split(rng)\n", 1)
    assert reborn != src
    errs = check_source(reborn)
    assert len(errs) == 1 and "eager key split" in errs[0]


@pytest.mark.parametrize("depth", [1, 2], ids=["per_step", "per_step_depth2"])
def test_a_run_takes_its_keys_ready_and_saves_the_chains_carry(depth, tmp_path):
    summary = run_training(ckpt_dir=str(tmp_path), seed=5, dispatch_depth=depth, **_TINY)
    assert summary["steps"] == STEPS and summary["dispatch_depth"] == depth
    # all but the first step's keys were waiting
    assert summary["keys_ready_share"] == (STEPS - 1) / STEPS
    path = latest_checkpoint(str(tmp_path), verify=True)
    _, saved = load_checkpoint(path, None)
    _, carries = _eager_chain(jax.random.PRNGKey(5), STEPS)
    np.testing.assert_array_equal(_bits(saved), carries[-1])


def test_a_dispatch_that_raises_leaves_the_carry_before_its_keys(tmp_path, monkeypatch):
    orig, calls = BSPEngine.train_step, []

    def train_step(engine, state, images, labels, rng, numerics=False):
        calls.append(_bits(rng))
        if len(calls) == 4:
            raise RuntimeError("the fourth dispatch fails")
        return orig(engine, state, images, labels, rng, numerics)

    monkeypatch.setattr(BSPEngine, "train_step", train_step)
    with pytest.raises(RuntimeError, match="fourth dispatch"):
        run_training(ckpt_dir=str(tmp_path), seed=5, **_TINY)
    subs, carries = _eager_chain(jax.random.PRNGKey(5), 4)
    for got, want in zip(calls, subs):
        np.testing.assert_array_equal(got, want)
    # the crash save: the state after three steps with the carry after three splits
    path = latest_checkpoint(str(tmp_path), verify=True)
    assert path is not None and path.endswith("3.npz")
    _, saved = load_checkpoint(path, None)
    np.testing.assert_array_equal(_bits(saved), carries[2])
