"""Functional layers: Conv / Pool / LRN / Dense / Dropout / BatchNorm / Sequential.

TPU-native equivalent of the reference's Theano layer classes
(reference: ``models/layers2.py`` — ``Conv`` (cuDNN ``dnn_conv``),
``Pool``, ``LRN``, ``FC``, ``Dropout``, ``Softmax``; anchors per
SURVEY.md §2.1, reference mount empty at build time).

Design:

- **NHWC** activations and **HWIO** kernels throughout — the layouts
  XLA:TPU tiles best onto the MXU (vs the reference's NCHW/cuDNN).
- Every layer is a lightweight config object with three pure methods::

      params, state = layer.init(key, in_shape)      # in_shape includes batch
      y, new_state  = layer.apply(params, state, x, train=..., rng=...)
      out_shape     = layer.out_shape(in_shape)

  ``params`` are trainable pytrees; ``state`` holds non-trainable
  buffers (BatchNorm running stats). Both are plain dicts, so the whole
  model is one transparent pytree — the analogue of the reference's
  list of Theano shared variables, but functional and shardable.
- No data-dependent Python control flow: everything traces once under
  ``jax.jit`` and compiles to a single XLA program.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.nn import init as initializers

Shape = tuple  # includes leading batch dim


def _pair(v) -> tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _spatial_out(h, w, kernel, stride, padding):
    """Output (h, w) for a windowed op with SAME/VALID/explicit padding."""
    kh, kw = kernel
    sh, sw = stride
    if padding == "SAME":
        return -(-h // sh), -(-w // sw)
    if padding == "VALID":
        return (h - kh) // sh + 1, (w - kw) // sw + 1
    ph, pw = _pair(padding)
    return (h + 2 * ph - kh) // sh + 1, (w + 2 * pw - kw) // sw + 1


class Layer:
    """Base class: stateless identity. Subclasses override as needed."""

    name: str = "layer"

    def init(self, key, in_shape: Shape):
        del key, in_shape
        return {}, {}

    def apply(self, params, state, x, *, train: bool = False, rng=None):
        del params, train, rng
        return x, state

    def out_shape(self, in_shape: Shape) -> Shape:
        return in_shape


class Conv(Layer):
    """2-D convolution (NHWC x HWIO -> NHWC), with AlexNet-style channel
    groups via ``feature_group_count`` (reference: ``models/layers2.py`` —
    ``Conv`` wrapping cuDNN ``dnn_conv`` with ``num_groups``).

    ``padding``: int / (int, int) explicit symmetric pad, or 'SAME'/'VALID'.
    """

    def __init__(
        self,
        out_channels: int,
        kernel: Union[int, tuple],
        stride: Union[int, tuple] = 1,
        padding: Union[int, tuple, str] = "SAME",
        groups: int = 1,
        use_bias: bool = True,
        w_init=None,
        b_init=None,
        name: str = "conv",
    ):
        self.out_channels = out_channels
        self.kernel = _pair(kernel)
        self.stride = _pair(stride)
        self.padding = padding
        self.groups = groups
        self.use_bias = use_bias
        self.w_init = w_init or initializers.he_normal()
        self.b_init = b_init or initializers.zeros
        self.name = name

    def _pad_arg(self):
        if isinstance(self.padding, str):
            return self.padding
        ph, pw = _pair(self.padding)
        return ((ph, ph), (pw, pw))

    def init(self, key, in_shape: Shape):
        cin = in_shape[-1]
        assert cin % self.groups == 0 and self.out_channels % self.groups == 0
        kh, kw = self.kernel
        wkey, bkey = jax.random.split(key)
        params = {"w": self.w_init(wkey, (kh, kw, cin // self.groups, self.out_channels))}
        if self.use_bias:
            params["b"] = self.b_init(bkey, (self.out_channels,))
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        y = lax.conv_general_dilated(
            x,
            params["w"].astype(x.dtype),
            window_strides=self.stride,
            padding=self._pad_arg(),
            feature_group_count=self.groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y, state

    def out_shape(self, in_shape: Shape) -> Shape:
        n, h, w, _ = in_shape
        oh, ow = _spatial_out(h, w, self.kernel, self.stride, self.padding)
        return (n, oh, ow, self.out_channels)


class Pool(Layer):
    """Max / average pooling (reference: ``models/layers2.py`` — ``Pool``).

    ``mode``: 'max' or 'avg'. AlexNet-style overlapping pool = 3x3 stride 2
    VALID.
    """

    def __init__(
        self,
        window: Union[int, tuple] = 2,
        stride: Optional[Union[int, tuple]] = None,
        padding: Union[int, tuple, str] = "VALID",
        mode: str = "max",
        name: str = "pool",
    ):
        self.window = _pair(window)
        self.stride = _pair(stride) if stride is not None else self.window
        self.padding = padding
        assert mode in ("max", "avg")
        self.mode = mode
        self.name = name

    def _pad_arg(self):
        if isinstance(self.padding, str):
            return self.padding
        ph, pw = _pair(self.padding)
        return ((0, 0), (ph, ph), (pw, pw), (0, 0))

    def apply(self, params, state, x, *, train=False, rng=None):
        kh, kw = self.window
        sh, sw = self.stride
        dims, strides = (1, kh, kw, 1), (1, sh, sw, 1)
        if self.mode == "max":
            # NOTE: AD of reduce_window-max lowers to select-and-scatter.
            # The Theano-style eq-mask backward was tried three ways on
            # an earlier backend and all lost: plain jnp in two
            # formulations (XLA won't fuse the 9-way accumulation), and
            # a register-resident Pallas kernel (ops/pallas_pool.py:
            # NHWC puts W on the sublane dim so shifted reads are
            # misaligned shuffles, and the custom call is a fusion
            # barrier; full analysis in that module's docstring). The
            # Pallas kernel stays as an opt-in (TMPI_PALLAS_POOL=1) with
            # Theano's all-maxima tie semantics.
            from theanompi_tpu.ops import pallas_pool

            if pallas_pool.routable(self.window, self.stride, self.padding, x):
                return pallas_pool.maxpool3x3_s1(x), state
            y = lax.reduce_window(
                x, -jnp.inf, lax.max, dims, strides, self._pad_arg()
            )
        else:
            summed = lax.reduce_window(
                x, 0.0, lax.add, dims, strides, self._pad_arg()
            )
            if isinstance(self.padding, str) and self.padding == "SAME":
                # normalize by actual window coverage at the borders
                ones = jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None]
                counts = lax.reduce_window(
                    ones, 0.0, lax.add, dims, strides, self._pad_arg()
                )
                y = summed / counts
            else:
                y = summed / (kh * kw)
        return y, state

    def out_shape(self, in_shape: Shape) -> Shape:
        n, h, w, c = in_shape
        oh, ow = _spatial_out(h, w, self.window, self.stride, self.padding)
        return (n, oh, ow, c)


class LRN(Layer):
    """Cross-channel local response normalization — the AlexNet/GoogLeNet
    normalizer (reference: ``models/layers2.py`` — ``LRN``, pylearn2-style
    ``CrossChannelNormalization(alpha=1e-4, k=2, beta=0.75, n=5)``).

    ``y = x / (k + (alpha/n) * sum_{window n} x^2)^beta`` — the
    pylearn2/Theano convention divides ``alpha`` by the window size, which
    the reference inherited; reproduce it exactly for top-1 parity.
    """

    def __init__(self, n: int = 5, alpha: float = 1e-4, beta: float = 0.75, k: float = 2.0, name: str = "lrn"):
        # the banded window sum below is the symmetric |i-j| <= n//2
        # band, which spans n channels only for odd n (even n would
        # silently widen to n+1 vs the reference's asymmetric window)
        assert n % 2 == 1, f"LRN window n must be odd, got {n}"
        self.n = n
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.name = name

    def apply(self, params, state, x, *, train=False, rng=None):
        sq = jnp.square(x)
        # Cross-channel window sum as a banded [C, C] matmul: the MXU
        # eats it (C = 96/256), XLA fuses the square into the GEMM input
        # and the rsqrt math into its output, and AD's transpose is just
        # the same band again — where the reduce_window lowering costs
        # several full HBM passes over AlexNet's 55x55 maps (measured on
        # v5e at batch 1024: 13,969 -> 18,169 img/s for the whole train
        # step). A fully fused Pallas kernel was also tried and REJECTED:
        # XLA picks batch-minor layouts for these conv activations, and
        # a lane=C kernel's layout constraint forces ~600 MB relayout
        # copies around every call that cost more than the fusion saves.
        c = x.shape[-1]
        i = jnp.arange(c)
        band = (jnp.abs(i[:, None] - i[None, :]) <= self.n // 2).astype(x.dtype)
        # output dtype follows x (bf16 on TPU): the MXU accumulates in
        # f32 internally either way, and asking for an f32 result here
        # materializes a full-precision copy of the biggest activation
        # maps in the backward residuals (~1.2 GB at AlexNet batch 1024)
        window_sum = jnp.einsum("...c,cd->...d", sq, band)
        d = self.k + (self.alpha / self.n) * window_sum
        if self.beta == 0.75:
            # d^-0.75 = rsqrt(d) * rsqrt(sqrt(d)): sqrt/rsqrt are single
            # VPU ops where pow lowers to exp(log) — measurably cheaper
            # on the big early conv maps (agrees with pow to ~1e-6 rel)
            return (x * lax.rsqrt(d) * lax.rsqrt(lax.sqrt(d))).astype(x.dtype), state
        return (x / jnp.power(d, self.beta)).astype(x.dtype), state


class Dense(Layer):
    """Fully connected layer (reference: ``models/layers2.py`` — ``FC``)."""

    def __init__(self, out_features: int, use_bias: bool = True, w_init=None, b_init=None, name: str = "fc"):
        self.out_features = out_features
        self.use_bias = use_bias
        self.w_init = w_init or initializers.glorot_uniform()
        self.b_init = b_init or initializers.zeros
        self.name = name

    def init(self, key, in_shape: Shape):
        wkey, bkey = jax.random.split(key)
        params = {"w": self.w_init(wkey, (in_shape[-1], self.out_features))}
        if self.use_bias:
            params["b"] = self.b_init(bkey, (self.out_features,))
        return params, {}

    def apply(self, params, state, x, *, train=False, rng=None):
        y = x @ params["w"].astype(x.dtype)
        if self.use_bias:
            y = y + params["b"].astype(y.dtype)
        return y, state

    def out_shape(self, in_shape: Shape) -> Shape:
        return (*in_shape[:-1], self.out_features)


class Dropout(Layer):
    """Inverted dropout (reference: ``models/layers2.py`` — ``Dropout``;
    the reference scaled at test time, we use the equivalent inverted
    form so eval is a pure pass-through)."""

    def __init__(self, rate: float = 0.5, name: str = "dropout"):
        self.rate = rate
        self.name = name

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, state
        assert rng is not None, "Dropout.apply(train=True) needs an rng"
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), state


class BatchNorm(Layer):
    """Batch normalization with running-stat state (WRN/ResNet recipes).

    ``axis_name``: if set and the layer runs inside a mapped axis
    (``shard_map``/``pmap``), batch stats are averaged across replicas
    with ``lax.pmean`` — cross-replica BN for small per-device batches.

    Design note: the statistic sweeps are one fused two-moment pass per
    BN per direction over the activations (bandwidth-bound, not
    MXU-bound), and the normalize sweep is computed in bf16 when x is
    bf16 (scale/offset still derived in fp32). Tried on an earlier
    backend and not adopted (``experiments/resnet_bn_probe.py`` repeats
    the comparison; no benchmark cell runs a BatchNorm model, so nothing
    here is measured on the current chip): ``dtype=f32`` reduction args
    instead of an explicit upcast (XLA already fuses the convert), and a
    variadic ``lax.reduce`` computing (Σx, Σx²) in one declared pass
    (XLA:TPU lowers a generic variadic reduce as scalar code; the
    moments were already sibling-fused). Removing the second pass needs
    stats fused into the producer conv's epilogue, which XLA does not
    expose (the LRN matmul above does not transfer: LRN replaced a
    bandwidth-bound op with a matmul, BN's reduce is already minimal
    traffic).
    """

    def __init__(
        self,
        momentum: float = 0.9,
        eps: float = 1e-5,
        axis_name: Optional[str] = None,
        name: str = "bn",
    ):
        self.momentum = momentum
        self.eps = eps
        self.axis_name = axis_name
        self.name = name

    def init(self, key, in_shape: Shape):
        c = in_shape[-1]
        params = {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}
        state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            xf = x.astype(jnp.float32)
            # two-moment form so cross-replica stats reduce with a single pmean
            mean = jnp.mean(xf, axis=reduce_axes)
            mean_sq = jnp.mean(jnp.square(xf), axis=reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean_sq = lax.pmean(mean_sq, self.axis_name)
            # clamp: fp32 cancellation can drive E[x^2]-E[x]^2 slightly negative
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        inv = lax.rsqrt(var + self.eps) * params["scale"]
        if x.dtype == jnp.bfloat16:
            # bf16 normalize sweep (class docstring): per-channel
            # constants derived in fp32, the big elementwise pass
            # reads/writes bf16 only
            y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + params[
                "bias"
            ].astype(x.dtype)
            return y, new_state
        y = (x.astype(jnp.float32) - mean) * inv + params["bias"]
        return y.astype(x.dtype), new_state


class Activation(Layer):
    _FNS: dict[str, Callable] = {
        "relu": jax.nn.relu,
        "gelu": jax.nn.gelu,
        "tanh": jnp.tanh,
        "sigmoid": jax.nn.sigmoid,
        "identity": lambda x: x,
    }

    def __init__(self, fn: Union[str, Callable] = "relu", name: Optional[str] = None):
        self.fn = self._FNS[fn] if isinstance(fn, str) else fn
        self.name = name or (fn if isinstance(fn, str) else "act")

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.fn(x), state


class Flatten(Layer):
    name = "flatten"

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape(x.shape[0], -1), state

    def out_shape(self, in_shape: Shape) -> Shape:
        return (in_shape[0], int(math.prod(in_shape[1:])))


class GlobalAvgPool(Layer):
    name = "gap"

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), state

    def out_shape(self, in_shape: Shape) -> Shape:
        return (in_shape[0], in_shape[-1])


class Sequential(Layer):
    """Composition of layers with per-layer namespaced params/state.

    The analogue of the reference models' layer lists, but the whole
    network is a single pytree of params + a pytree of state.
    """

    def __init__(self, layers: Sequence[Layer], name: str = "seq"):
        self.layers = list(layers)
        self.name = name
        self._keys = [f"{i:02d}_{l.name}" for i, l in enumerate(self.layers)]

    def init(self, key, in_shape: Shape):
        params, state = {}, {}
        keys = jax.random.split(key, max(1, len(self.layers)))
        shape = in_shape
        for k, lname, layer in zip(keys, self._keys, self.layers):
            if any(d <= 0 for d in shape):
                # fail with the layer name, not a ZeroDivisionError deep
                # in an initializer (e.g. GoogLeNet on an input smaller
                # than its pooling stack supports)
                raise ValueError(
                    f"{self.name}: input to layer {lname!r} has non-positive "
                    f"dims {tuple(shape)} — input_shape too small for this "
                    "architecture"
                )
            p, s = layer.init(k, shape)
            if p:
                params[lname] = p
            if s:
                state[lname] = s
            shape = layer.out_shape(shape)
        return params, state

    def apply(self, params, state, x, *, train=False, rng=None):
        new_state = dict(state)
        rngs = (
            jax.random.split(rng, max(1, len(self.layers))) if rng is not None else [None] * len(self.layers)
        )
        for r, lname, layer in zip(rngs, self._keys, self.layers):
            p = params.get(lname, {})
            s = state.get(lname, {})
            x, s2 = layer.apply(p, s, x, train=train, rng=r)
            if s2:
                new_state[lname] = s2
        return x, new_state

    def out_shape(self, in_shape: Shape) -> Shape:
        shape = in_shape
        for layer in self.layers:
            shape = layer.out_shape(shape)
        return shape
