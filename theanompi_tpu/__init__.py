"""theanompi_tpu — a TPU-native rebuild of Theano-MPI.

Theano-MPI (reference: bobquest33/Theano-MPI, arXiv:1605.08325) is a
data-parallel distributed training framework for convolutional networks:
a model zoo (AlexNet, GoogLeNet, VGG16, ResNet-50, Wide-ResNet), pluggable
synchronization rules (BSP / EASGD / GoSGD), pluggable gradient-exchange
strategies, an asynchronous input pipeline, and a recorder/checkpoint layer,
all glued together with CUDA-aware MPI + NCCL.

This package provides the same behavioral contract, redesigned TPU-first:

- one SPMD program under ``jax.jit`` over a named ``jax.sharding.Mesh``
  replaces the reference's process-per-GPU ``mpirun`` model
  (reference: ``lib/base.py`` — ``MPI_GPU_Process``; empty mount, see SURVEY.md);
- gradient allreduce lowers to ``lax.psum`` over ICI instead of
  MPI/NCCL calls between steps (reference: ``lib/exchanger.py`` — ``BSP_Exchanger``);
- EASGD's center<->worker elastic averaging and GoSGD's randomized gossip
  become ``lax.ppermute`` / ``lax.psum`` collectives inside the compiled step
  (reference: ``lib/exchanger.py`` — ``EASGD_Exchanger``, ``GOSGD_Exchanger``);
- the exchanger-strategy concept survives as a swappable gradient-sync
  function (reference: ``lib/exchanger_strategy.py`` — ``Exch_allreduce``,
  ``Exch_asa32``, ``Exch_asa16``, ``Exch_nccl32``);
- Theano shared GPU params + ``lib/opt.py`` updates compile as a single
  pjit'd train step over HBM-resident ``jax.Array``s.

Session API (reference: ``launch_session.py`` / ``tmpi``)::

    from theanompi_tpu import BSP
    rule = BSP()
    rule.init(devices=8, modelfile='wrn', modelclass='WRN')  # short name or module path
    rule.wait()
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# TPU-native default PRNG: XLA's rng-bit-generator ("rbg") instead of the
# pure-JAX threefry. Threefry lowers to a long scalar-heavy program that
# costs ~1.9 ms of a 14.4 ms AlexNet-128 train step on a v5e (dropout
# masks); rbg generates the same-shaped bits in hardware for ~0.5 ms
# (measured: 8,723 -> 9,685 img/s). Streams stay deterministic per seed;
# they differ from threefry's, and split/fold_in derivations remain
# threefry-based (only bit generation changes). Opt out / override with
# TMPI_PRNG_IMPL=threefry2x32 (empty string = leave JAX's default).
# Precedence: TMPI_PRNG_IMPL > the user's own JAX_DEFAULT_PRNG_IMPL
# (never clobber an explicit JAX-level choice) > our rbg default. A
# programmatic jax.config.update made before this import is
# indistinguishable from the default and WILL be overridden — use either
# env var to pin.
_impl = _os.environ.get("TMPI_PRNG_IMPL")
if _impl is None and "JAX_DEFAULT_PRNG_IMPL" not in _os.environ:
    _impl = "rbg"
if _impl:
    _jax.config.update("jax_default_prng_impl", _impl)

from theanompi_tpu.launch.session import BSP, EASGD, GOSGD, SyncRule  # noqa: F401,E402
from theanompi_tpu.launch.supervisor import supervise_training  # noqa: F401,E402

__all__ = ["BSP", "EASGD", "GOSGD", "SyncRule", "__version__"]
