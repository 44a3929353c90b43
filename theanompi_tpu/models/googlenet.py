"""GoogLeNet (Inception v1) — BASELINE config #3 (32-worker BSP).

Reference: ``models/googlenet.py`` — ``GoogLeNet`` with inception-module
builders (SURVEY.md §2.1). Szegedy et al. 2015 architecture: stem
(7x7/2 conv, LRN, 1x1+3x3 convs, LRN), nine inception modules with the
paper's channel table, two auxiliary classifiers during training
(weighted 0.3), global average pool + dropout 0.4 + linear.

Recipe per the reference: batch 32/worker scaled to the 32-worker BSP
config, momentum 0.9, weight decay 1e-4(ish), polynomial LR decay.

Layout: NHWC with channel-axis concats. A channels-major trunk and a
concat-free inception were tried on an earlier backend and not adopted
(XLA:TPU assigns its own internal layouts); the zoo's single-chip batch
is 512 (models/zoo.py). No benchmark cell runs this model: its speed on
the current chip is not measured.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from theanompi_tpu import nn
from theanompi_tpu.models.contract import (
    Model,
    Recipe,
    classification_metrics,
    softmax_cross_entropy,
)
from theanompi_tpu.nn import init as initializers
from theanompi_tpu.nn.layers import Layer

_he = initializers.he_normal()


def _conv_relu(out_c, kernel, stride=1, padding="SAME", name="conv"):
    return [
        nn.Conv(out_c, kernel, stride=stride, padding=padding, w_init=_he, name=name),
        nn.Activation("relu"),
    ]


class Inception(Layer):
    """One inception module: 1x1 / 1x1-3x3 / 1x1-5x5 / pool-1x1 branches,
    channel-concatenated (reference: inception-module builders)."""

    def __init__(self, c1, c3r, c3, c5r, c5, cp, name="incept"):
        self.name = name
        self.c1, self.c3r, self.c5r = c1, c3r, c5r
        self.b1 = nn.Sequential(_conv_relu(c1, 1, name="b1"), name="b1")
        self.b3 = nn.Sequential(
            _conv_relu(c3r, 1, name="b3r") + _conv_relu(c3, 3, name="b3"), name="b3"
        )
        self.b5 = nn.Sequential(
            _conv_relu(c5r, 1, name="b5r") + _conv_relu(c5, 5, name="b5"), name="b5"
        )
        self.bp = nn.Sequential(
            [nn.Pool(3, stride=1, padding=1, mode="max")] + _conv_relu(cp, 1, name="bp"),
            name="bp",
        )
        self.branches = {"b1": self.b1, "b3": self.b3, "b5": self.b5, "bp": self.bp}
        # The fused-front apply slices each branch at the end of its
        # leading conv+relu pair; pin that structural assumption HERE so
        # a change to _conv_relu's composition fails at build time, not
        # by silently misaligning the tail slicing below.
        self._front_len = len(_conv_relu(1, 1))
        for bname in ("b1", "b3", "b5"):
            branch = self.branches[bname]
            if not isinstance(branch.layers[0], nn.Conv):
                raise AssertionError(
                    f"Inception fused front expects branch {bname!r} to "
                    f"start with a Conv; got {type(branch.layers[0]).__name__}"
                )

    def init(self, key, in_shape):
        params, state = {}, {}
        keys = jax.random.split(key, 4)
        for k, (bname, branch) in zip(keys, self.branches.items()):
            p, s = branch.init(k, in_shape)
            params[bname] = p
            if bname != "bp" and not {"w", "b"} <= set(p[branch._keys[0]]):
                raise AssertionError(
                    f"Inception fused front expects branch {bname!r}'s "
                    f"leading conv params to carry 'w'/'b'; got "
                    f"{sorted(p[branch._keys[0]])}"
                )
            if s and bname != "bp":
                # the fused apply below does not thread state through the
                # b1/b3/b5 tails — fail at build time, not silently, if a
                # stateful layer (BatchNorm) ever lands in those branches
                raise NotImplementedError(
                    f"Inception branch {bname!r} carries layer state "
                    f"({list(s)}); the fused-front apply only threads "
                    "state for the pool branch"
                )
            if s:
                state[bname] = s
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        # TPU MXU shaping: the b1 / b3-reduce / b5-reduce 1x1 convs all
        # read the SAME input, and their output channels are small
        # (16..208) — run them as ONE conv with c1+c3r+c5r outputs so
        # the matmul fills 128-wide MXU tiles instead of three
        # fragments, then split. Same math (concat of weights along
        # HWIO's O axis == concat of the three convs), same param tree.
        p1 = params["b1"][self.b1._keys[0]]
        p3r = params["b3"][self.b3._keys[0]]
        p5r = params["b5"][self.b5._keys[0]]
        w = jnp.concatenate([p1["w"], p3r["w"], p5r["w"]], axis=-1)
        b = jnp.concatenate([p1["b"], p3r["b"], p5r["b"]], axis=-1)
        y = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = jax.nn.relu(y + b.astype(y.dtype))
        y1 = y[..., : self.c1]
        y3r = y[..., self.c1 : self.c1 + self.c3r]
        y5r = y[..., self.c1 + self.c3r :]

        def _tail(branch, bname, h):
            # remaining layers of the branch (conv 3x3/5x5 + relu); the
            # split point is _conv_relu's OWN length, asserted in __init__
            fl = self._front_len
            for lname, layer in zip(branch._keys[fl:], branch.layers[fl:]):
                h, _ = layer.apply(
                    params[bname].get(lname, {}), {}, h, train=train, rng=rng
                )
            return h

        y3 = _tail(self.b3, "b3", y3r)
        y5 = _tail(self.b5, "b5", y5r)
        yp, _ = self.bp.apply(
            params["bp"], state.get("bp", {}), x, train=train, rng=rng
        )
        return jnp.concatenate([y1, y3, y5, yp], axis=-1), state

    def out_shape(self, in_shape):
        n, h, w, _ = in_shape
        c = sum(b.out_shape(in_shape)[-1] for b in self.branches.values())
        return (n, h, w, c)


class AuxHead(Layer):
    """Auxiliary classifier: 5x5/3 avg pool, 1x1 conv 128, FC 1024,
    dropout 0.7, linear (training-time only)."""

    def __init__(self, num_classes, name="aux"):
        self.name = name
        self.net = nn.Sequential(
            [
                nn.Pool(5, stride=3, mode="avg"),
                *_conv_relu(128, 1, name="proj"),
                nn.Flatten(),
                nn.Dense(1024, w_init=_he, name="fc"),
                nn.Activation("relu"),
                nn.Dropout(0.7),
                nn.Dense(num_classes, name="out"),
            ],
            name=name,
        )

    def init(self, key, in_shape):
        return self.net.init(key, in_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.net.apply(params, state, x, train=train, rng=rng)

    def out_shape(self, in_shape):
        return self.net.out_shape(in_shape)


# (name, module config or pool marker); channel table per the paper
_INCEPTION_TABLE = [
    ("3a", (64, 96, 128, 16, 32, 32)),
    ("3b", (128, 128, 192, 32, 96, 64)),
    ("pool3", None),
    ("4a", (192, 96, 208, 16, 48, 64)),
    ("4b", (160, 112, 224, 24, 64, 64)),  # aux1 taps the output of 4a
    ("4c", (128, 128, 256, 24, 64, 64)),
    ("4d", (112, 144, 288, 32, 64, 64)),
    ("4e", (256, 160, 320, 32, 128, 128)),  # aux2 taps the output of 4d
    ("pool4", None),
    ("5a", (256, 160, 320, 32, 128, 128)),
    ("5b", (384, 192, 384, 48, 128, 128)),
]


class GoogLeNet(Model):
    name = "googlenet"
    aux_weight = 0.3

    @classmethod
    def default_recipe(cls) -> Recipe:
        return Recipe(
            batch_size=1024,  # 32 workers x 32/worker, BASELINE config #3
            n_epochs=60,
            optimizer="momentum",
            opt_kwargs={"momentum": 0.9, "weight_decay": 1e-4},
            schedule="poly",
            sched_kwargs={"lr": 0.04, "total_steps": 60, "power": 0.5},
            lr_unit="epoch",
            input_shape=(224, 224, 3),
            num_classes=1000,
            compute_dtype=jnp.bfloat16,
            dataset="imagenet",
        )

    def build(self):
        ncls = self.recipe.num_classes
        self.stem = nn.Sequential(
            [
                *_conv_relu(64, 7, stride=2, name="conv1"),
                nn.Pool(3, stride=2, mode="max", padding=1),
                nn.LRN(),
                *_conv_relu(64, 1, name="conv2r"),
                *_conv_relu(192, 3, name="conv2"),
                nn.LRN(),
                nn.Pool(3, stride=2, mode="max", padding=1),
            ],
            name="stem",
        )
        self.blocks: list[tuple[str, Optional[Layer]]] = []
        for bname, cfg in _INCEPTION_TABLE:
            if cfg is None:
                self.blocks.append((bname, nn.Pool(3, stride=2, mode="max", padding=1)))
            else:
                self.blocks.append((bname, Inception(*cfg, name=bname)))
        self.head = nn.Sequential(
            [nn.GlobalAvgPool(), nn.Dropout(0.4), nn.Dense(ncls, name="out")],
            name="head",
        )
        self.aux1 = AuxHead(ncls, name="aux1")
        self.aux2 = AuxHead(ncls, name="aux2")
        return None  # custom apply below

    # -- custom init/apply (branching graph, aux heads) ---------------------
    def init(self, key):
        keys = iter(jax.random.split(key, len(self.blocks) + 4))
        params, state = {}, {}
        shape = self.input_shape
        p, s = self.stem.init(next(keys), shape)
        params["stem"], shape = p, self.stem.out_shape(shape)
        if s:
            state["stem"] = s
        aux_shapes = {}
        for bname, block in self.blocks:
            p, s = block.init(next(keys), shape)
            if p:
                params[bname] = p
            if s:
                state[bname] = s
            shape = block.out_shape(shape)
            if bname == "4a":
                aux_shapes["aux1"] = shape
            if bname == "4d":
                aux_shapes["aux2"] = shape
        p, s = self.head.init(next(keys), shape)
        params["head"] = p
        if s:
            state["head"] = s
        for aux_name, aux in (("aux1", self.aux1), ("aux2", self.aux2)):
            p, s = aux.init(next(keys), aux_shapes[aux_name])
            params[aux_name] = p
            if s:
                state[aux_name] = s
        return params, state

    def apply(self, params, state, images, *, train=False, rng=None):
        x = images.astype(self.recipe.compute_dtype)
        rngs = iter(
            jax.random.split(rng, len(self.blocks) + 4)
            if rng is not None
            else [None] * (len(self.blocks) + 4)
        )
        new_state = dict(state)
        x, s = self.stem.apply(params["stem"], state.get("stem", {}), x, train=train, rng=next(rngs))
        if s:
            new_state["stem"] = s
        aux_in = {}
        for bname, block in self.blocks:
            x, s = block.apply(
                params.get(bname, {}), state.get(bname, {}), x, train=train, rng=next(rngs)
            )
            if s:
                new_state[bname] = s
            if bname == "4a":
                aux_in["aux1"] = x
            if bname == "4d":
                aux_in["aux2"] = x
        logits, s = self.head.apply(params["head"], state.get("head", {}), x, train=train, rng=next(rngs))
        if s:
            new_state["head"] = s
        if not train:
            return logits, new_state
        aux_logits = []
        for aux_name, aux in (("aux1", self.aux1), ("aux2", self.aux2)):
            al, _ = aux.apply(
                params[aux_name], state.get(aux_name, {}), aux_in[aux_name],
                train=train, rng=next(rngs),
            )
            aux_logits.append(al)
        return (logits, *aux_logits), new_state

    def loss(self, logits, labels):
        if isinstance(logits, tuple):
            main, *aux = logits
            loss = softmax_cross_entropy(main, labels)
            for a in aux:
                loss = loss + self.aux_weight * softmax_cross_entropy(a, labels)
            return loss
        return softmax_cross_entropy(logits, labels)

    def metrics(self, logits, labels):
        if isinstance(logits, tuple):
            logits = logits[0]
        return classification_metrics(logits, labels)
