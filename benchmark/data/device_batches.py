"""Device-fed batches: a few distinct batches made on the device from the
seed in one jitted call and handed over again and again, in another order each
epoch. The program's loader thread, queue and ``device_put`` still run, on
arrays that are already where they have to be: no host gather, no crop, no H2D
copy. Rows all differ (uniform random bytes or token ids).

``what`` in the workload's ``data`` block says what a batch is: ``images``
(uint8 ``[B, *input_shape]`` with int32 labels, carrying the recipe's device-side
normalisation, so the compiled step is the loader-fed one) or ``tokens`` (int32
``[B, seq_len]``, handed over as tokens and targets alike: the model shifts the
targets itself)."""

import jax
import jax.numpy as jnp
import numpy as np


class DeviceBatches:
    def __init__(self, seed, n_batches, what, config):
        self.seed = int(seed)
        self.batch_size = B = int(config["batch_size"])
        if what == "images":
            self.image_shape = tuple(int(s) for s in config["input_shape"])
            self.n_classes = int(config["num_classes"])
            self.device_transform = {"mean": np.float32(config["input_mean"]),
                                     "scale": float(config["input_scale"])}
        elif what == "tokens":
            self.image_shape = (int(config["seq_len"]),)
            self.n_classes = int(config["vocab"])
            self.device_transform = None
        else:
            raise ValueError(f"device_batches: 'what' is {what!r}, not 'images' or 'tokens'")

        n = int(n_batches)

        def make(key):
            if what == "tokens":
                x = jax.random.randint(key, (n, B, *self.image_shape), 0, self.n_classes, jnp.int32)
                return [(x[i], x[i]) for i in range(n)]
            kx, ky = jax.random.split(key)
            x = jax.random.bits(kx, (n, B, *self.image_shape), dtype=jnp.uint8)
            y = jax.random.randint(ky, (n, B), 0, self.n_classes, jnp.int32)
            return [(x[i], y[i]) for i in range(n)]

        # the seed may pass 32 bits: fold its high part into the key
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed % 2 ** 31), self.seed // 2 ** 31)
        self.pool = jax.jit(make)(key)

    def train_epoch(self, epoch, batch_size, seed=0, part=None):
        if int(batch_size) != self.batch_size or part is not None:
            raise ValueError("device-fed batches are made whole, at the configuration's batch size")
        for i in np.random.default_rng([self.seed, int(epoch)]).permutation(len(self.pool)):
            yield self.pool[i]


def make(seed, params, config, workdir):
    return DeviceBatches(seed, params["batches"], params["what"], config)
