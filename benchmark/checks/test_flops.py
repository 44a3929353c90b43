import json
import os

from harness import manifest


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_lm136m_needs_5_41_TF_of_matmul_and_0_46_TF_of_causal_attention():
    flops = manifest.load_module("flops", "lm136m")
    c = _config("lm136m")
    assert flops.matmul_params(c) == 110_100_480
    assert round(flops.matmul_flops(c) / 1e12, 2) == 5.41
    assert round(flops.attention_flops(c) / 1e12, 2) == 0.46
    assert flops.step_flops(c) == flops.matmul_flops(c) + flops.attention_flops(c)


def test_alexnet_needs_1_45_GF_forward_per_image():
    flops = manifest.load_module("flops", "alexnet")
    c = _config("alexnet")
    per_image = dict(flops.layer_flops(c))
    assert per_image["conv1"] == 2 * 55 * 55 * 11 * 11 * 3 * 96
    assert per_image["fc6"] == 2 * 9216 * 4096
    assert round(sum(per_image.values()) / 1e9, 2) == 1.45
    assert flops.step_flops(c) == (3 * sum(per_image.values()) - per_image["conv1"]) * 1024
