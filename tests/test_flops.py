"""utils/flops: XLA-cost-model FLOP accounting used by the bench/MFU
reporting (no reference equivalent — the reference only reported img/s,
``lib/recorder.py``; SURVEY.md §5.1)."""

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.utils.flops import compiled_flops, mfu, peak_flops


def test_compiled_flops_matmul():
    """A matmul's cost must be ~2*M*N*K flops (XLA counts fused muladd
    as 2)."""
    m = n = k = 256

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    flops = compiled_flops(f, a, b)
    if flops is None:  # backend without a cost model: API contract holds
        return
    assert 0.5 * 2 * m * n * k <= flops <= 4 * 2 * m * n * k


def test_peak_flops_table():
    class FakeDev:
        device_kind = "TPU v5 lite"

    assert peak_flops(FakeDev()) == 197e12

    class Unknown:
        device_kind = "cpu"

    assert peak_flops(Unknown()) is None
    assert mfu(1e12, Unknown()) is None
    assert abs(mfu(98.5e12, FakeDev()) - 0.5) < 1e-9


def test_unknown_tpu_kind_raises_instead_of_calibrating():
    """Off the TPU an unknown device reports None and consumers
    calibrate; a TPU the spec tables do not know must not quietly take
    that path — every table lookup raises."""
    import pytest

    from theanompi_tpu.obs.attribution import link_bytes_per_sec
    from theanompi_tpu.utils.flops import (
        hbm_capacity_bytes,
        peak_hbm_bytes_per_sec,
    )

    class NewChip:
        platform = "tpu"
        device_kind = "TPU v9 mega"

    for lookup in (peak_flops, peak_hbm_bytes_per_sec, hbm_capacity_bytes,
                   link_bytes_per_sec):
        with pytest.raises(ValueError, match="TPU v9 mega"):
            lookup(NewChip())

    class Cpu:
        platform = "cpu"
        device_kind = "cpu"

    assert link_bytes_per_sec(Cpu()) is None


def test_compiled_cost_swallows_a_failed_lowering_only_off_the_tpu():
    import pytest

    from theanompi_tpu.utils.flops import compiled_cost

    class Unlowerable:
        def lower(self, *a, **k):
            raise RuntimeError("does not lower")

    class Tpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert compiled_cost(Unlowerable(), device=jax.devices()[0]) is None
    with pytest.raises(RuntimeError, match="does not lower"):
        compiled_cost(Unlowerable(), device=Tpu())
