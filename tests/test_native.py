"""Native C++ loader kernels vs the numpy reference implementation
(reference hot path: ``lib/proc_load_mpi.py`` crop/mirror/mean-subtract;
SURVEY.md §3.4). The contract is bit-identical float32 output — the
native path must be a pure speedup, never a numerics change."""

import numpy as np
import pytest

from theanompi_tpu import native


def _numpy_ref(x, oy, ox, flips, c, mean, scale):
    n = len(x)
    rows = oy[:, None] + np.arange(c)
    cols = ox[:, None] + np.arange(c)
    cols = np.where(flips[:, None], cols[:, ::-1], cols)
    out = x[np.arange(n)[:, None, None], rows[:, :, None], cols[:, None, :]]
    return (out.astype(np.float32) - mean) * np.float32(scale)


needs_native = pytest.mark.skipif(
    not native.available(), reason="native lib failed to build (no g++?)"
)


@needs_native
@pytest.mark.parametrize("mean_kind", ["scalar", "channel", "plane"])
def test_crop_mirror_normalize_matches_numpy(mean_kind):
    r = np.random.RandomState(0)
    n, h, w, c = 9, 40, 36, 3
    crop = 27
    x = r.randint(0, 256, (n, h, w, c)).astype(np.uint8)
    oy = r.randint(0, h - crop + 1, n).astype(np.int64)
    ox = r.randint(0, w - crop + 1, n).astype(np.int64)
    flips = r.rand(n) < 0.5
    scale = 1.0 / 58.0
    if mean_kind == "scalar":
        mean = np.float32(127.5)
    elif mean_kind == "channel":
        mean = r.rand(c).astype(np.float32) * 255
    else:
        mean = r.rand(crop, crop, c).astype(np.float32) * 255

    got = native.crop_mirror_normalize(x, oy, ox, flips, crop, mean, scale)
    assert got is not None
    want = _numpy_ref(x, oy, ox, flips, crop, np.asarray(mean, np.float32), scale)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.float32


@needs_native
def test_crop_mirror_normalize_threads_equal_single():
    r = np.random.RandomState(1)
    x = r.randint(0, 256, (33, 32, 32, 3)).astype(np.uint8)
    oy = r.randint(0, 6, 33)
    ox = r.randint(0, 6, 33)
    flips = r.rand(33) < 0.5
    a = native.crop_mirror_normalize(
        x, oy, ox, flips, 27, np.float32(127.5), 0.02, n_threads=1
    )
    b = native.crop_mirror_normalize(
        x, oy, ox, flips, 27, np.float32(127.5), 0.02, n_threads=7
    )
    np.testing.assert_array_equal(a, b)


@needs_native
def test_gather_rows_matches_fancy_index(tmp_path):
    r = np.random.RandomState(2)
    src = r.randint(0, 256, (50, 8, 8, 3)).astype(np.uint8)
    # exercise the real use: a memory-mapped shard
    p = tmp_path / "shard.npy"
    np.save(p, src)
    mm = np.load(p, mmap_mode="r")
    idx = r.permutation(50)[:17]
    got = native.gather_rows(mm, idx)
    assert got is not None
    np.testing.assert_array_equal(got, src[idx])


@needs_native
def test_imagenet_pipeline_native_equals_numpy(tmp_path, monkeypatch):
    """The full ImageNet_data train batch stream must be bit-identical
    with the native kernels on or off (same RNG draw order)."""
    from theanompi_tpu.data.imagenet import ImageNet_data, write_shards

    r = np.random.RandomState(3)
    imgs = r.randint(0, 256, (64, 36, 36, 3)).astype(np.uint8)
    lbls = r.randint(0, 10, 64).astype(np.int64)
    write_shards(str(tmp_path), "train", imgs, lbls, shard_size=32)
    write_shards(str(tmp_path), "val", imgs[:16], lbls[:16], shard_size=16)
    np.save(tmp_path / "mean.npy", r.rand(36, 36, 3).astype(np.float32) * 255)

    ds = ImageNet_data(root=str(tmp_path), crop=27, device_normalize=False)
    native_batches = [(x.copy(), y.copy()) for x, y in ds.train_epoch(0, 16, seed=5)]

    # force the numpy fallback for an identical second pass
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    numpy_batches = [(x.copy(), y.copy()) for x, y in ds.train_epoch(0, 16, seed=5)]

    assert len(native_batches) == len(numpy_batches) == 4
    for (xa, ya), (xb, yb) in zip(native_batches, numpy_batches):
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ya, yb)


def test_default_threads_positive():
    assert native.default_threads() >= 1


def test_hostaffinity_parse_and_pin():
    """hwloc-equivalent cpuset parsing + pin (reference:
    lib/hwloc_utils.py; SURVEY.md §2.1)."""
    import os

    import pytest as _pytest

    from theanompi_tpu.utils.hostaffinity import (
        loader_cpuset,
        parse_cpuset,
        pin_thread,
    )

    assert parse_cpuset("0-3,8,10-11") == {0, 1, 2, 3, 8, 10, 11}
    assert parse_cpuset("5") == {5}
    with _pytest.raises(ValueError):
        parse_cpuset(" , ")

    if not hasattr(os, "sched_getaffinity"):
        return
    allowed = sorted(os.sched_getaffinity(0))
    os.environ["TMPI_LOADER_CPUS"] = str(allowed[0])
    try:
        assert loader_cpuset() == {allowed[0]}
        # pin from a scratch thread so the test runner's own affinity
        # is untouched
        import threading

        result = {}
        t = threading.Thread(
            target=lambda: result.setdefault("pinned", pin_thread())
        )
        t.start(); t.join()
        assert result["pinned"] is True
    finally:
        del os.environ["TMPI_LOADER_CPUS"]


def test_train_mirror_flag_disables_flips(tmp_path):
    from theanompi_tpu.data.imagenet import ImageNet_data, write_shards

    r = np.random.RandomState(4)
    imgs = r.randint(0, 256, (32, 36, 36, 3)).astype(np.uint8)
    lbls = r.randint(0, 10, 32).astype(np.int64)
    write_shards(str(tmp_path), "train", imgs, lbls, shard_size=32)
    write_shards(str(tmp_path), "val", imgs[:8], lbls[:8], shard_size=8)

    on = ImageNet_data(root=str(tmp_path), crop=27, train_mirror=True,
                       device_normalize=False)
    off = ImageNet_data(root=str(tmp_path), crop=27, train_mirror=False,
                        device_normalize=False)
    xa, _ = next(iter(on.train_epoch(0, 16, seed=7)))
    xb, _ = next(iter(off.train_epoch(0, 16, seed=7)))
    # same crops (same RNG draw order), but at least one image mirrored
    assert xa.shape == xb.shape
    assert not np.array_equal(xa, xb)
    # each no-mirror image equals either the mirrored or unmirrored one
    for i in range(len(xa)):
        assert (
            np.array_equal(xa[i], xb[i])
            or np.array_equal(xa[i], xb[i][:, ::-1])
        )


@needs_native
def test_crop_mirror_u8_matches_numpy():
    r = np.random.RandomState(5)
    n, h, w, c, crop = 11, 40, 36, 3, 27
    x = r.randint(0, 256, (n, h, w, c)).astype(np.uint8)
    oy = r.randint(0, h - crop + 1, n)
    ox = r.randint(0, w - crop + 1, n)
    flips = r.rand(n) < 0.5
    got = native.crop_mirror_u8(x, oy, ox, flips, crop)
    assert got is not None and got.dtype == np.uint8
    from theanompi_tpu.data.imagenet import ImageNet_data

    want = ImageNet_data._numpy_crop_mirror(x, oy, ox, flips, crop)
    np.testing.assert_array_equal(got, want)


def test_device_normalize_pipeline_agrees_with_host(tmp_path):
    """uint8 batches + on-device (x-mean)*scale must equal the host
    float pipeline after the transform."""
    from theanompi_tpu.data.imagenet import ImageNet_data, write_shards

    r = np.random.RandomState(6)
    imgs = r.randint(0, 256, (32, 36, 36, 3)).astype(np.uint8)
    lbls = r.randint(0, 10, 32).astype(np.int64)
    write_shards(str(tmp_path), "train", imgs, lbls, shard_size=32)
    write_shards(str(tmp_path), "val", imgs[:8], lbls[:8], shard_size=8)
    np.save(tmp_path / "mean.npy", (r.rand(36, 36, 3) * 255).astype(np.float32))

    dev = ImageNet_data(root=str(tmp_path), crop=27)  # default: device path
    host = ImageNet_data(root=str(tmp_path), crop=27, device_normalize=False)
    (xd, yd) = next(iter(dev.train_epoch(0, 16, seed=9)))
    (xh, yh) = next(iter(host.train_epoch(0, 16, seed=9)))
    assert xd.dtype == np.uint8 and xh.dtype == np.float32
    np.testing.assert_array_equal(yd, yh)
    t = dev.device_transform
    np.testing.assert_allclose(
        (xd.astype(np.float32) - t["mean"]) * t["scale"], xh, rtol=1e-5, atol=1e-5
    )


def test_artifact_is_keyed_on_source_content_not_on_the_host(tmp_path, monkeypatch):
    """A library is loaded only under the name this checkout's source,
    flags and CPU hash to: a copied tree or another machine's build can
    never be picked up by host name or mtime."""
    import os
    import shutil

    here = native._artifact()
    assert here is not None and os.path.basename(here).startswith("_tmpi_native-")
    # another CPU's features -> another artifact
    with monkeypatch.context() as m:
        m.setattr(native, "_cpu_flags", lambda: "another cpu")
        assert native._artifact() != here
    edited = tmp_path / "loader.cpp"
    shutil.copy(native._SRC, edited)
    with open(edited, "a") as f:
        f.write("\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert native._artifact() != here
    # no source: nothing on disk can be trusted
    monkeypatch.setattr(native, "_SRC", str(tmp_path / "missing.cpp"))
    assert native._artifact() is None and native._build() is None
