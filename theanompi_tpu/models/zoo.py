"""Model registry: name -> (model class, single-chip global batch).
Shared by ``tmpi profile``, ``tmpi preflight``, ``tmpi chaos`` and
``tools/op_profile.py`` so the batch policy lives in one place.

Batch policy: AlexNet runs the reference workload's GLOBAL batch
(BASELINE config #2: 8 workers x 128 = 1024 — same SGD trajectory);
GoogLeNet uses 512 (config #3's global 1024 is a
32-WORKER batch — at pod scale each chip sees 32 rows, so the
single-chip batch is a free parameter). ResNet-50 uses config #4's
batch 256; VGG16/WRN use the largest power-of-two that fits one chip's
HBM comfortably. None of these has been re-measured on the current
machine."""

from __future__ import annotations


def zoo_entry(name: str):
    """``(model_cls, single_chip_global_batch)`` for the benchable zoo
    (alexnet / googlenet / resnet50 / vgg16 / wrn; ``mlp`` is the
    CPU-profileable smoke entry ``tmpi profile`` defaults exercise)."""
    if name == "mlp":
        from theanompi_tpu.models.mlp import MLP

        return MLP, 64
    if name == "alexnet":
        from theanompi_tpu.models.alex_net import AlexNet

        return AlexNet, 1024
    if name == "googlenet":
        from theanompi_tpu.models.googlenet import GoogLeNet

        return GoogLeNet, 512
    if name == "resnet50":
        from theanompi_tpu.models.model_zoo.resnet50 import ResNet50

        return ResNet50, 256
    if name == "vgg16":
        from theanompi_tpu.models.model_zoo.vgg import VGG16

        return VGG16, 128
    if name == "wrn":
        from theanompi_tpu.models.model_zoo.wrn import WRN

        return WRN, 1024
    if name == "transformer_lm":
        # beyond-parity LM row: ~136M params, T=1024, flash attention;
        # batch in SEQUENCES
        from theanompi_tpu.models.lm import TransformerLM_136M

        return TransformerLM_136M, 8
    if name == "transformer_lm_350m":
        # GPT-2-medium scale (~370M params): the caller must donate the
        # train state (two f32 states would OOM a v5e)
        from theanompi_tpu.models.lm import TransformerLM_350M

        return TransformerLM_350M, 8
    raise ValueError(f"unknown bench model {name!r}")


def infer_fn(entry):
    """The eval-mode apply closure — ``(params, model_state, x) ->
    logits`` with ``train=False``, no rng, fixed BatchNorm stats — the
    ONE definition of "run this model for inference", shared by the
    serving engine (serve/engine.py jits it per batch bucket) and the
    eval loops (train.py ``make_eval_step``), so the two paths cannot
    drift (e.g. one forgetting to freeze BN).

    ``entry`` is a constructed :class:`~theanompi_tpu.models.contract.
    Model` instance, or a bench-zoo short name (resolved through
    :func:`zoo_entry` under its default recipe)."""
    model = entry
    if isinstance(entry, str):
        model_cls, _ = zoo_entry(entry)
        model = model_cls()

    def fwd(params, model_state, x):
        logits, _ = model.apply(params, model_state, x, train=False)
        return logits

    return fwd
