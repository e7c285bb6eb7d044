"""Pallas TPU symbols — the ONLY module that may import
``jax.experimental.pallas.tpu``.

Kernels never touch ``pallas.tpu`` directly; they import the symbols
from here, so a rename in a future JAX is one edit in one file.  The
import is unconditional: a JAX without ``pallas.tpu`` fails here, at
import, instead of quietly routing every kernel to its jnp oracle.

Policy (the ``sole-tpu-importer`` rule in ``repro.analysis.lint`` — run
in CI's ``policy`` job and delegated to by
``tests/test_dispatch.py::test_compat_sole_tpu_importer``):

    all Pallas TPU symbols go through ``repro.kernels.compat``.

Exports
-------
``pl``                      ``jax.experimental.pallas`` (re-export, so kernel
                            modules have a single import site).
``vmem(shape, dtype)``      VMEM scratch-shape factory (MemoryRef).
``smem(shape, dtype)``      SMEM scratch-shape factory.
``prefetch_scalar_grid_spec``  grid spec with leading scalar-prefetch
                            operands.
``compiler_params(vmem_limit_bytes=)``  Mosaic compiler parameters: the
                            scoped VMEM a kernel may use.
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401  (re-export)
from jax.experimental.pallas import tpu as _pltpu


def vmem(shape, dtype):
    """VMEM scratch-shape factory: ``scratch_shapes=[vmem((8, 128), f32)]``."""
    return _pltpu.MemorySpace.VMEM(shape, dtype)


def smem(shape, dtype):
    """SMEM scratch-shape factory (scalars / control flow)."""
    return _pltpu.MemorySpace.SMEM(shape, dtype)


def prefetch_scalar_grid_spec(*, num_scalar_prefetch, grid, in_specs,
                              out_specs, scratch_shapes=()):
    """Grid spec whose first ``num_scalar_prefetch`` operands are scalars
    available to every ``index_map`` (the TPU scalar-prefetch mechanism)."""
    return _pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def compiler_params(*, vmem_limit_bytes):
    """Mosaic compiler parameters for one ``pallas_call``: the scoped VMEM
    its blocks, their double buffers and its scratch may use."""
    return _pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit_bytes))
