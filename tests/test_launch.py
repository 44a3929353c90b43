"""Launcher tests: run_training driver, session API, tmpi CLI
(reference flow: SURVEY.md §3.1)."""

import json
import os

import pytest

from theanompi_tpu import BSP
from theanompi_tpu.cli import main as tmpi_main
from theanompi_tpu.launch.session import resolve_model
from theanompi_tpu.launch.worker import run_training
from tinymodel import TinyCNN


_TINYMODEL_PY = os.path.join(os.path.dirname(__file__), "tinymodel.py")

_TINY = dict(
    recipe_overrides={
        "batch_size": 32,
        "input_shape": (16, 16, 3),
        "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]},
    },
    dataset="synthetic",
    dataset_kwargs={"n_train": 64, "n_val": 32, "image_shape": (16, 16, 3)},
    print_freq=0,
)


def test_run_training_bsp_end_to_end(tmp_path):
    summary = run_training(
        rule="bsp",
        model_cls=TinyCNN,
        devices=8,
        n_epochs=2,
        save_dir=str(tmp_path),
        ckpt_dir=str(tmp_path / "ckpt"),
        **_TINY,
    )
    assert summary["steps"] == 4  # 64/32 batches x 2 epochs
    assert summary["images_per_sec"] > 0
    assert "val" in summary and "error" in summary["val"]
    # recorder JSONL + checkpoint written
    assert (tmp_path / "tinycnn_bsp.jsonl").exists()
    assert any(f.name.startswith("ckpt_") for f in (tmp_path / "ckpt").iterdir())


@pytest.mark.slow
def test_run_training_resume(tmp_path):
    kw = dict(rule="bsp", model_cls=TinyCNN, devices=8, ckpt_dir=str(tmp_path / "c"), **_TINY)
    run_training(n_epochs=1, **kw)
    summary = run_training(n_epochs=2, resume=True, **kw)
    assert summary["steps"] == 4  # resumed at 2, trained 2 more


def test_run_training_errors():
    with pytest.raises(ValueError, match="model_cls"):
        run_training(rule="bsp")
    with pytest.raises(ValueError, match="unknown rule"):
        run_training(rule="fancy", model_cls=TinyCNN, **_TINY)
    with pytest.raises(ValueError, match="not divisible"):
        run_training(
            rule="bsp", model_cls=TinyCNN, devices=8,
            recipe_overrides={"batch_size": 12, "input_shape": (16, 16, 3)},
            dataset="synthetic", dataset_kwargs={"n_train": 24, "n_val": 12, "image_shape": (16, 16, 3)},
        )


def test_session_api_background_and_wait():
    rule = BSP()
    rule.init(
        devices=8,
        modelfile=_TINYMODEL_PY,
        modelclass="TinyCNN",
        n_epochs=1,
        **_TINY,
    )
    summary = rule.wait()
    assert summary["steps"] == 2
    # bad model class fails fast at init() (resolve happens before spawn)
    with pytest.raises(AttributeError):
        BSP().init(modelfile="theanompi_tpu.models.model_zoo.wrn", modelclass="Nope")
    # runtime failure inside the background thread surfaces at wait()
    rule2 = BSP()
    rule2.init(
        modelfile=_TINYMODEL_PY,
        modelclass="TinyCNN",
        dataset="no_such_dataset",
    )
    with pytest.raises(ValueError, match="unknown dataset"):
        rule2.wait()


def test_resolve_model_from_file(tmp_path):
    f = tmp_path / "mymodel.py"
    f.write_text(
        "from theanompi_tpu.models.model_zoo.wrn import WRN_16_4\n"
        "class Mine(WRN_16_4):\n    name = 'mine'\n"
    )
    cls = resolve_model(str(f), "Mine")
    assert cls.name == "mine"


def test_tmpi_cli(tmp_path, capsys):
    rc = tmpi_main(
        [
            "BSP", "8",
            _TINYMODEL_PY, "TinyCNN",
            "--synthetic", "--max-steps", "2", "--epochs", "1",
            "--batch-size", "32", "--print-freq", "0",
        ]
    )
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(out)
    assert summary["rule"] == "bsp" and summary["steps"] == 2


def test_resolve_model_short_name():
    assert resolve_model("wrn", "WRN_16_4").name == "wrn_16_4"
    assert resolve_model("cifar10", "Cifar10_model").name == "cifar10"


def test_profile_trace_capture(tmp_path):
    """--profile-dir must produce a real jax.profiler trace (SURVEY §5.1
    TPU equivalent: the in-step comm/compute split comes from the XLA
    trace, not host brackets)."""
    prof = tmp_path / "trace"
    # 2 steps/epoch (64/32): the capture window [2, 4) spans epochs,
    # which profile_tick must handle (global step, not per-epoch)
    run_training(
        rule="bsp", model_cls=TinyCNN, max_steps=8, n_epochs=4,
        profile_dir=str(prof), profile_steps=2, **_TINY,
    )
    produced = list(prof.rglob("*.xplane.pb")) + list(prof.rglob("*.trace.json.gz"))
    assert produced, f"no trace files under {prof}: {list(prof.rglob('*'))}"


@pytest.mark.parametrize("rule,devices,kw", [
    ("bsp", 1, {}),
    ("bsp", 4, {}),
    ("bsp", 4, {"zero": 1}),
    ("easgd", 4, {"avg_freq": 2}),
    ("gosgd", 4, {}),
])
def test_train_step_compiles_once(rule, devices, kw, caplog):
    """The state a run starts from carries the shardings (and types) of
    the state every step returns, so the second step reuses the first
    one's program. An uncommitted or weak-typed initial state made every
    engine lower and compile its step twice — on a chip, the most
    expensive half-minute of a run, paid double."""
    import logging

    import jax

    # an odd width: nothing else in this process has lowered this step
    overrides = dict(_TINY["recipe_overrides"], input_shape=(12, 12, 3))
    data = dict(_TINY["dataset_kwargs"], n_train=32 * 4 * 3, n_val=32 * 4,
                image_shape=(12, 12, 3))
    with jax.log_compiles(True), caplog.at_level(logging.WARNING, logger="jax"):
        summary = run_training(
            rule=rule, model_cls=TinyCNN, devices=devices, n_epochs=1,
            max_steps=3, **{**_TINY, "recipe_overrides": overrides,
                            "dataset_kwargs": data}, **kw,
        )
    assert summary["steps"] == summary["device_steps"] == 3
    lowered = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith(("Compiling jit(sharded_step)",
                                             "Compiling jit(single_step)",
                                             "Compiling sharded_step",
                                             "Compiling single_step"))]
    assert len(lowered) == 1, lowered
