"""Operations the ``alexnet`` step NEEDS, from the configuration's shapes:
convolutions and dense layers, forward and backward (the first layer needs
no input gradient), LRN, pooling and the loss not counted."""


def layer_flops(c):
    """[(name, forward FLOPs per image)]."""
    h, w, cin = c["input_shape"]
    out = []
    for conv in c["conv"]:
        k = conv["kernel"]
        h = (h + 2 * conv["pad"] - k) // conv["stride"] + 1
        w = (w + 2 * conv["pad"] - k) // conv["stride"] + 1
        out.append((conv["name"], 2 * h * w * k * k * (cin // conv["groups"]) * conv["out"]))
        cin = conv["out"]
        if conv["pool"]:
            h = (h - c["pool"]["window"]) // c["pool"]["stride"] + 1
            w = (w - c["pool"]["window"]) // c["pool"]["stride"] + 1
    width = h * w * cin
    for fc in c["fc"]:
        out.append((fc["name"], 2 * width * fc["out"]))
        width = fc["out"]
    return out


def step_flops(c):
    per_image = layer_flops(c)
    forward = sum(f for _, f in per_image)
    return (3 * forward - per_image[0][1]) * c["batch_size"]
