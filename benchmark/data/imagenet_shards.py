"""Seeded uint8 ImageNet-shaped shards on disk, read back through the
recipe's own ``imagenet`` pipeline (mmap gather, crop, mirror).

The shard format is the one ``theanompi_tpu/data/imagenet.py`` documents:
``train_images_NNNN.npy`` uint8 [N, S, S, 3] and ``train_labels_NNNN.npy``.
Rows all differ (uniform random bytes). No val shards: the window dataset
runs no validation pass. ``reference_batches`` is the plain rebuild of what
that pipeline has to hand to the step; it imports nothing of the program.
"""

import os

import numpy as np


def rows(seed, params):
    """The shards' contents, a pure function of the seed: [(images, labels)]."""
    rng = np.random.default_rng(int(seed))
    n, shape = int(params["rows_per_shard"]), tuple(params["row_shape"])
    out = []
    for _ in range(int(params["shards"])):
        x = rng.integers(0, 256, size=(n, *shape), dtype=np.uint8)
        y = rng.integers(0, int(params.get("classes", 1000)), size=n).astype(np.int64)
        out.append((x, y))
    return out


def make(seed, params, config, workdir):
    from theanompi_tpu.data.datasets import get_dataset

    os.makedirs(workdir, exist_ok=True)
    for f in os.listdir(workdir):
        if f.endswith(".npy"):
            os.remove(os.path.join(workdir, f))
    for i, (x, y) in enumerate(rows(seed, params)):
        np.save(os.path.join(workdir, f"train_images_{i:04d}.npy"), x)
        np.save(os.path.join(workdir, f"train_labels_{i:04d}.npy"), y)
    return get_dataset("imagenet", root=workdir, crop=int(config["input_shape"][0]))


def reference_batches(seed, params, config, prog_seed, n):
    """The first ``n`` batches the recipe's pipeline makes from these shards,
    rebuilt apart from the program so that the loader's output is part of what
    ``correct`` compares: per-epoch shard order and row permutation, sorted
    gather, random crop and mirror, all from one ``RandomState``."""
    shard_rows = rows(seed, params)
    crop, batch_size = config["input_shape"][0], int(config["batch_size"])
    out, epoch = [], 0
    while len(out) < n:
        rng = np.random.RandomState(prog_seed * 100003 + epoch)
        for si in rng.permutation(len(shard_rows)):
            images, labels = shard_rows[si]
            perm = rng.permutation(len(images))
            for b in range(len(images) // batch_size):
                idx = np.sort(perm[b * batch_size:(b + 1) * batch_size])
                x, y = images[idx], labels[idx].astype(np.int32)
                _, h, w, _ = x.shape
                offs = rng.randint(0, (h - crop + 1) * (w - crop + 1), size=len(x))
                oy, ox = offs // (w - crop + 1), offs % (w - crop + 1)
                flips = rng.rand(len(x)) < 0.5
                rows_ = oy[:, None] + np.arange(crop)
                cols = ox[:, None] + np.arange(crop)
                cols = np.where(flips[:, None], cols[:, ::-1], cols)
                x = x[np.arange(len(x))[:, None, None], rows_[:, :, None], cols[:, None, :]]
                out.append((x, y))
                if len(out) == n:
                    return out
        epoch += 1
    return out
