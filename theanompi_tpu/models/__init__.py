"""Model zoo.

TPU-native rebuild of the reference zoo (SURVEY.md §2.1): AlexNet,
GoogLeNet, cifar10 CNN (reference: ``models/{alex_net,googlenet,cifar10}.py``)
plus the lasagne-built models VGG16, ResNet-50, Wide-ResNet
(reference: ``models/lasagne_model_zoo/{vgg,resnet50,wrn}.py`` — here
``model_zoo/`` since nothing lasagne remains).

Every model owns its training recipe (batch size, LR schedule, optimizer,
augmentation) exactly as in the reference, where hyperparams lived inside
each model file and the framework never interpreted them (SURVEY.md §5.6).
"""

from theanompi_tpu.models.contract import Model, Recipe, softmax_cross_entropy  # noqa: F401


# short name -> (module path, class name); imported lazily so one missing
# model never breaks lookups of the others
MODEL_REGISTRY = {
    "cifar10": ("theanompi_tpu.models.cifar10", "Cifar10_model"),
    "wrn": ("theanompi_tpu.models.model_zoo.wrn", "WRN"),
    "wrn_16_4": ("theanompi_tpu.models.model_zoo.wrn", "WRN_16_4"),
    "alexnet": ("theanompi_tpu.models.alex_net", "AlexNet"),
    "googlenet": ("theanompi_tpu.models.googlenet", "GoogLeNet"),
    "vgg16": ("theanompi_tpu.models.model_zoo.vgg", "VGG16"),
    "resnet50": ("theanompi_tpu.models.model_zoo.resnet50", "ResNet50"),
    "transformer_lm": ("theanompi_tpu.models.lm", "TransformerLMModel"),
    "transformer_lm_136m": ("theanompi_tpu.models.lm", "TransformerLM_136M"),
    "moe_lm": ("theanompi_tpu.models.lm", "MoELMModel"),
    "afmoe_lm": ("theanompi_tpu.models.afmoe", "AfmoeLM"),
    "trinity_mini_ep8": ("theanompi_tpu.models.afmoe", "TrinityMini_EP8"),
    "mistral4_lm": ("theanompi_tpu.models.mistral4", "Mistral4LM"),
    "mistral_small_4_ep8": ("theanompi_tpu.models.mistral4", "MistralSmall4_EP8"),
    "minicpm_sala_lm": ("theanompi_tpu.models.minicpm_sala", "MiniCPMSALA"),
    "minicpm_sala_stage8": ("theanompi_tpu.models.minicpm_sala", "MiniCPM_SALA_Stage8"),
}


def get_model(name: str) -> type:
    """Resolve a model class by zoo short name (used by
    ``launch.session.resolve_model`` for ``tmpi BSP 8 wrn WRN``-style
    invocations)."""
    import importlib

    try:
        modpath, clsname = MODEL_REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(MODEL_REGISTRY)}"
        ) from None
    return getattr(importlib.import_module(modpath), clsname)
