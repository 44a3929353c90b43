"""ImageNet pipeline: memory-mapped preprocessed shards.

Reference: ``models/data/imagenet.py`` — ``ImageNet_data`` over
preprocessed hickle ``.hkl`` file-batches (256x256 uint8) with
``img_mean`` subtraction and random 227-crop + mirror done in the
spawned loader (``lib/proc_load_mpi.py``; SURVEY.md §2.1, §3.4). The
TPU-native equivalent replaces HDF5 file-batches with plain ``.npy``
shards opened via ``np.load(mmap_mode='r')`` — zero-copy reads, no
codec dependency, trivially producible from any source:

    $IMAGENET_DIR/
      train_images_0000.npy   uint8 [N, S, S, 3]   (S >= crop size, e.g. 256)
      train_labels_0000.npy   int   [N]
      ...more shards...
      val_images_0000.npy / val_labels_0000.npy
      mean.npy                float [S, S, 3] or [3]   (optional)

Shuffling follows the reference's file-batch scheme: shard order and
intra-shard order are permuted per epoch (seeded, same on every host);
batches never span shards, keeping reads sequential per shard.

``Imagenet_synthetic`` generates shape-identical fake data in memory —
the benchmarking/CI stand-in when no ImageNet is on disk.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterator, Optional

import numpy as np

from theanompi_tpu.data.datasets import Dataset, register_dataset
from theanompi_tpu import native


def shard_path(directory: str, split: str, kind: str, i: int) -> str:
    """Canonical shard filename — the ONE place the naming convention
    lives (write_shards, tools/make_shards, and the index glob agree)."""
    return os.path.join(directory, f"{split}_{kind}_{i:04d}.npy")


def shard_glob(directory: str, split: str, kind: str) -> str:
    return os.path.join(directory, f"{split}_{kind}_*.npy")


def write_shards(
    directory: str,
    split: str,
    images: np.ndarray,
    labels: np.ndarray,
    shard_size: int = 1024,
) -> int:
    """Write uint8 images/labels as the shard format above (used by tests
    and by any user conversion script). Returns the number of shards."""
    os.makedirs(directory, exist_ok=True)
    n = len(images)
    n_shards = -(-n // shard_size)
    for i in range(n_shards):
        sl = slice(i * shard_size, (i + 1) * shard_size)
        np.save(shard_path(directory, split, "images", i), images[sl])
        np.save(shard_path(directory, split, "labels", i), labels[sl])
    return n_shards


class ImageNet_data(Dataset):
    """ImageNet-1k from preprocessed mmap shards."""

    name = "imagenet"
    n_classes = 1000

    SEARCH = ("/root/data/imagenet", "/data/imagenet")

    def __init__(
        self,
        root: Optional[str] = None,
        crop: int = 227,
        train_mirror: bool = True,
        device_normalize: bool = True,
        val_crops: int = 1,
    ):
        base = self._find(root)
        self.crop = crop
        self.train_mirror = train_mirror
        if val_crops not in (1, 10):
            raise ValueError("val_crops must be 1 (center) or 10 (10-crop)")
        # 1 = center crop; 10 = the AlexNet-era protocol (4 corners +
        # center, each mirrored), logits averaged per image by the eval
        # step (train.make_eval_step(views=10)) — the published top-1
        # numbers the recipes were validated with use this
        self.val_views = val_crops
        self.image_shape = (crop, crop, 3)
        self._train = self._index(base, "train")
        self._val = self._index(base, "val")
        if not self._train:
            raise FileNotFoundError(f"no train_images_*.npy shards under {base}")
        # say which implementation feeds the trainer: the numpy fallback
        # is bit-identical but slower, and otherwise only a failed build
        # ever prints
        print(f"[data] imagenet shards under {base}: {self.n_train} train / "
              f"{self.n_val} val rows; host loader: {native.describe()}",
              flush=True)
        mean_path = os.path.join(base, "mean.npy")
        # reference: per-pixel img_mean subtracted in the loader
        self.mean = (
            np.load(mean_path).astype(np.float32)
            if os.path.exists(mean_path)
            else np.float32(127.5)
        )
        self.scale = np.float32(1.0 / 58.0)  # ~global pixel std
        # device_normalize: batches stay uint8 on the host (crop+mirror
        # only) and the driver applies (x - mean) * scale ON DEVICE —
        # 4x less H2D traffic. device_transform is the driver's contract
        # (launch/worker.py); False restores host-side float batches.
        self.device_transform = (
            {"mean": self._mean_for_crop(crop), "scale": float(self.scale)}
            if device_normalize
            else None
        )

    @classmethod
    def _find(cls, root: Optional[str]) -> str:
        env = os.environ.get("IMAGENET_DIR", "")
        for c in ([root] if root else [p for p in (env, *cls.SEARCH) if p]):
            if c and glob.glob(shard_glob(c, "train", "images")):
                return c
        raise FileNotFoundError(
            "ImageNet shards not found; set $IMAGENET_DIR to a directory of "
            "train/val_images_*.npy shards (see module docstring for the "
            "format; use dataset='imagenet_synthetic' for benchmarks without data)"
        )

    @staticmethod
    def _index(base: str, split: str) -> list[tuple[str, str, int]]:
        shards = []
        for img_path in sorted(glob.glob(shard_glob(base, split, "images"))):
            lbl_path = img_path.replace("_images_", "_labels_")
            n = len(np.load(lbl_path, mmap_mode="r"))
            shards.append((img_path, lbl_path, n))
        return shards

    # -- Dataset interface over shards --------------------------------------
    @property
    def n_train(self) -> int:
        return sum(n for _, _, n in self._train)

    @property
    def n_val(self) -> int:
        return sum(n for _, _, n in self._val)

    def n_train_batches(self, batch_size: int) -> int:
        return sum(n // batch_size for _, _, n in self._train)

    def n_val_batches(self, batch_size: int) -> int:
        return sum(n // batch_size for _, _, n in self._val)

    def train_epoch(
        self,
        epoch: int,
        batch_size: int,
        seed: int = 0,
        part: Optional[slice] = None,
    ) -> Iterator:
        """``part`` (multi-controller): this host's slice of each global
        batch — sliced from the UNSORTED permutation (a random subset),
        then sorted for sequential mmap reads."""
        rng = np.random.RandomState(seed * 100003 + epoch)
        order = rng.permutation(len(self._train))
        for si in order:
            img_path, lbl_path, n = self._train[si]
            images = np.load(img_path, mmap_mode="r")
            labels = np.load(lbl_path)
            perm = rng.permutation(n)
            for b in range(n // batch_size):
                idx = perm[b * batch_size : (b + 1) * batch_size]
                if part is not None:
                    idx = idx[part]
                idx = np.sort(idx)
                # mmap gather: multithreaded memcpy when the native lib
                # built (reference loader's hkl read), numpy otherwise
                x = native.gather_rows(images, idx)
                if x is None:
                    x = np.asarray(images[idx])
                y = labels[idx].astype(np.int32)
                yield self._preprocess(x, rng, train=True), y

    def val_epoch(self, batch_size: int, part: Optional[slice] = None) -> Iterator:
        for img_path, lbl_path, n in self._val:
            images = np.load(img_path, mmap_mode="r")
            labels = np.load(lbl_path)
            for b in range(n // batch_size):
                sl = slice(b * batch_size, (b + 1) * batch_size)
                x = np.asarray(images[sl])
                y = labels[sl].astype(np.int32)
                if part is not None:
                    x, y = x[part], y[part]
                if self.val_views == 10:
                    yield self._ten_crop(x), y
                else:
                    yield self._preprocess(x, None, train=False), y

    def _ten_crop(self, x: np.ndarray) -> np.ndarray:
        """4 corners + center, each mirrored — view-major rows per image
        ``[img0_v0..img0_v9, img1_v0, ...]``, so a batch-dim shard holds
        whole images (the eval step averages logits over the 10 views).
        uint8 when the device-normalize path is on, floats otherwise."""
        n, h, w, _ = x.shape
        c = self.crop
        oys = [0, 0, h - c, h - c, (h - c) // 2]
        oxs = [0, w - c, 0, w - c, (w - c) // 2]
        views = []
        for oy, ox in zip(oys, oxs):
            v = x[:, oy : oy + c, ox : ox + c]
            views.append(v)
            views.append(v[:, :, ::-1])
        out = np.stack(views, axis=1).reshape(n * 10, c, c, x.shape[-1])
        if self.device_transform is not None:
            return np.ascontiguousarray(out)
        return (out.astype(np.float32) - self._mean_for_crop(c)) * self.scale

    def _mean_for_crop(self, c: int) -> np.ndarray:
        """The mean as applied post-crop: scalar / per-channel pass
        through; a full-plane mean is CENTER-cropped to the crop size for
        every sample (the plane is smooth; identical to the numpy path)."""
        if np.ndim(self.mean) == 3 and self.mean.shape[0] != c:
            return self.mean[
                (self.mean.shape[0] - c) // 2 : (self.mean.shape[0] - c) // 2 + c,
                (self.mean.shape[1] - c) // 2 : (self.mean.shape[1] - c) // 2 + c,
            ]
        return np.asarray(self.mean, np.float32)

    @staticmethod
    def _numpy_crop_mirror(x, oy, ox, flips, c):
        """The fancy-index crop+mirror fallback — the single source for
        the indexing the native kernels replicate (tests compare)."""
        n = len(x)
        rows = oy[:, None] + np.arange(c)
        cols = ox[:, None] + np.arange(c)
        cols = np.where(flips[:, None], cols[:, ::-1], cols)
        return x[np.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :]]

    def _preprocess(
        self, x: np.ndarray, rng: Optional[np.random.RandomState], train: bool
    ) -> np.ndarray:
        """Random crop + mirror + mean/scale (reference:
        ``proc_load_mpi`` crop/mirror funcs). Val: center crop. The hot
        loop runs in the native C++ kernel when built (same RNG draws,
        bit-identical output — tests/test_native.py), numpy otherwise."""
        n, h, w, _ = x.shape
        c = self.crop
        if train:
            offs = rng.randint(0, (h - c + 1) * (w - c + 1), size=n)
            oy, ox = offs // (w - c + 1), offs % (w - c + 1)
            # draw even when mirroring is off: the data order downstream
            # of the RNG must not depend on the train_mirror flag
            flips = rng.rand(n) < 0.5
            if not self.train_mirror:
                flips = np.zeros(n, bool)
        else:
            oy = np.full(n, (h - c) // 2)
            ox = np.full(n, (w - c) // 2)
            flips = np.zeros(n, bool)
        if self.device_transform is not None:
            # crop/mirror only, dtype preserved; normalization happens on
            # device (worker's input_transform) — ship 4x fewer bytes.
            # Native kernel is uint8-only: any other shard dtype takes
            # the numpy path (same guard as the host path below).
            out = (
                native.crop_mirror_u8(x, oy, ox, flips, c)
                if x.dtype == np.uint8
                else None
            )
            if out is None:
                out = self._numpy_crop_mirror(x, oy, ox, flips, c)
            return out
        m = self._mean_for_crop(c)
        if x.dtype == np.uint8:
            out = native.crop_mirror_normalize(
                x, oy, ox, flips, c, m, float(self.scale)
            )
            if out is not None:
                return out
        out = self._numpy_crop_mirror(x, oy, ox, flips, c)
        return (out.astype(np.float32) - m) * self.scale


class Imagenet_synthetic(Dataset):
    """Shape-correct fake ImageNet for benchmarks/CI (no disk, seeded)."""

    name = "imagenet_synthetic"

    def __init__(
        self,
        n_train: int = 2048,
        n_val: int = 256,
        crop: int = 227,
        n_classes: int = 1000,
        seed: int = 0,
        device_normalize: bool = True,
    ):
        self.image_shape = (crop, crop, 3)
        self.n_classes = n_classes
        rng = np.random.RandomState(seed)
        # the ONE definition of the normalization constants — the
        # device_transform dict and both host-path conversions use these
        self.mean = np.float32(127.5)
        self.scale = np.float32(1.0 / 58.0)
        self.device_transform = (
            {"mean": self.mean, "scale": float(self.scale)}
            if device_normalize
            else None
        )

        def make(n, salt):
            r = np.random.RandomState(seed + salt)
            y = r.randint(0, n_classes, size=n).astype(np.int32)
            x = r.randint(0, 256, size=(n, *self.image_shape)).astype(np.uint8)
            return x, y

        self.x_train, self.y_train = make(n_train, 1)
        self.x_val, self.y_val = make(n_val, 2)

    def _normalize(self, x: np.ndarray) -> np.ndarray:
        return (x.astype(np.float32) - self.mean) * self.scale

    def augment(self, x: np.ndarray, rng: np.random.RandomState) -> np.ndarray:
        if self.device_transform is not None:
            return x  # uint8; normalized on device
        return self._normalize(x)

    def val_epoch(self, batch_size: int, part: Optional[slice] = None):
        for x, y in super().val_epoch(batch_size, part=part):
            if self.device_transform is None:
                x = self._normalize(x)
            yield x, y


register_dataset("imagenet", ImageNet_data)
register_dataset("imagenet_synthetic", Imagenet_synthetic)
