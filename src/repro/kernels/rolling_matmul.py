"""Rolling-window matmul — the compute hot-spot of window-mode sub-model
training, as a Pallas TPU kernel.

    y[M, win] = x[M, K] @ W[K, off : off+win]

The client's sub-model only touches a contiguous column window of the full
weight; fusing the window selection into the matmul's BlockSpec index_map
(scalar-prefetch offset) means the inactive columns are never read from HBM
and no W_sub copy is materialized.  Window offset/size are aligned to the
128-lane MXU tile (``SubmodelConfig.align=128`` on TPU), so every block the
kernel visits is dense MXU work — this is the TPU-native replacement for the
paper's elementwise m ⊙ W masking.

Grid: (M/bm, win/bn, K/bk), K innermost for accumulator reuse; the offset
arrives via ``pltpu.PrefetchScalarGridSpec`` and shifts the W column-block
index.  f32 accumulation in VMEM scratch-free form (out block revisited over
k with @pl.when init).

Blocks: ``bn`` counts the offset, so it stays at the 128-lane tile the
window's alignment is certified for; ``bm`` and ``bk`` are free, and
``dispatch.autotune_blocks`` sizes them from VMEM.  With ``bk == K`` the x
block's index is constant across the window sweep and the pipeline fetches
it once per row block.  :func:`rolling_spec` builds every kernel's
``pallas_call`` arguments: it passes a VMEM limit computed from the call's
blocks and records them in :data:`LAUNCHES`.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.compat import (compiler_params, pl,
                                  prefetch_scalar_grid_spec, vmem)

#: Scoped VMEM a v5e core grants a Mosaic kernel that asks for none (16 MiB
#: of its 128 MiB).  No rolling-matmul call asks for less than this.
_DEFAULT_SCOPED_VMEM = 16 * 2**20

#: Room above a call's own blocks for Mosaic's internal scratch (the dot's
#: partial products, operands it re-tiles).
_VMEM_HEADROOM = 8 * 2**20


def tile_vmem_bytes(bm, bn, bk, itemsize):
    """VMEM one grid step of a rolling-matmul kernel holds: the two operand
    blocks (``[bm, bk]`` and ``[bk, bn]``; ``[bn, bk]`` for W in the dx
    kernels) and the output block ``[bm, bn]``, each double-buffered by
    the pipeline, plus the float32 accumulator ``[bm, bn]``."""
    return 2 * (bm * bk + bk * bn + bm * bn) * itemsize + bm * bn * 4


def vmem_limit_bytes(bm, bn, bk, itemsize):
    """The ``vmem_limit_bytes`` a call with these blocks passes to Mosaic."""
    return max(_DEFAULT_SCOPED_VMEM,
               tile_vmem_bytes(bm, bn, bk, itemsize) + _VMEM_HEADROOM)


#: Every rolling-matmul launch traced in this process, keyed by (kernel
#: name, operand shapes, output shape): its ``(bm, bn, bk)``, grid steps a
#: call and ``vmem_limit_bytes``.  Written at trace time (a jitted round
#: records once per trace); ``dispatch.block_choices()`` reports it.
LAUNCHES: dict = {}


def rolling_spec(name, *, grid, in_specs, out_specs, out_shape, blocks,
                 operands):
    """``pallas_call`` arguments of one rolling-matmul kernel: a grid spec
    with the scalar-prefetched offset blocks and a float32 ``[bm, bn]``
    accumulator, the output shape, and compiler params whose VMEM limit is
    computed from the call's own blocks.  Records the launch in
    :data:`LAUNCHES` under ``name`` and the shapes of ``operands`` (the
    activation or cotangent, and W)."""
    bm, bn, bk = blocks
    a, w = operands
    limit = vmem_limit_bytes(bm, bn, bk, jnp.dtype(a.dtype).itemsize)
    LAUNCHES[(name, tuple(a.shape), tuple(w.shape),
              tuple(out_shape.shape))] = dict(
        blocks=(bm, bn, bk), grid_steps=math.prod(grid),
        vmem_limit_bytes=limit)
    return dict(
        grid_spec=prefetch_scalar_grid_spec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[vmem((bm, bn), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=compiler_params(vmem_limit_bytes=limit))


def _rolling_mm_kernel(off_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul(x, w, offset, win, *, bm=128, bn=128, bk=128,
                   interpret=True):
    """x [M,K]; w [K,N]; offset: int32 scalar (multiple of bn); win: static.

    Returns y [M, win] = x @ w[:, offset:offset+win].
    """
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = min(bm, M), min(bn, win), min(bk, K)
    assert win % bn == 0 and M % bm == 0 and K % bk == 0
    nk = K // bk
    off_blocks = jnp.asarray(offset, jnp.int32)[None] // bn

    return pl.pallas_call(
        functools.partial(_rolling_mm_kernel, nk=nk),
        name="rolling_matmul_fwd",
        **rolling_spec(
            "rolling_matmul_fwd",
            grid=(M // bm, win // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k, off: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k, off: (k, off[0] + j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, off: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, win), x.dtype),
            blocks=(bm, bn, bk), operands=(x, w)),
        interpret=interpret,
    )(off_blocks, x, w)


# ---------------------------------------------------------------------------
# Multi-step arm: T windowed matmuls sharing one x and one window offset
# ---------------------------------------------------------------------------


def _rolling_mm_multi_kernel(off_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_multi(x, ws, offset, win, *, bm=128, bn=128, bk=128,
                         interpret=True):
    """x [M,K]; ws [T,K,N]; offset: int32 scalar (multiple of bn); win static.

    Returns ys [T, M, win] with ys[t] = x @ ws[t][:, offset:offset+win] — the
    scan-body fusion: the gated MLP's gate/up pair (and any other group of
    windowed matmuls sharing one activation and one window) runs as ONE
    Pallas call.  The grid gains a step dimension ``t`` ahead of the output
    tiles, so the automatic cross-iteration double buffering prefetches step
    ``t+1``'s first W column-block (through the same scalar-prefetch offset)
    while step ``t``'s last k-block is still on the MXU — the per-client
    window load overlaps the previous step's compute instead of serializing
    T separate kernel launches, and the x block load amortizes over steps.
    """
    T = ws.shape[0]
    M, K = x.shape
    bm, bn, bk = min(bm, M), min(bn, win), min(bk, K)
    assert win % bn == 0 and M % bm == 0 and K % bk == 0
    nk = K // bk
    off_blocks = jnp.asarray(offset, jnp.int32)[None] // bn

    return pl.pallas_call(
        functools.partial(_rolling_mm_multi_kernel, nk=nk),
        name="rolling_matmul_multi",
        **rolling_spec(
            "rolling_matmul_multi",
            grid=(T, M // bm, win // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda t, i, j, k, off: (i, k)),
                pl.BlockSpec((1, bk, bn),
                             lambda t, i, j, k, off: (t, k, off[0] + j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda t, i, j, k, off: (t, i, j)),
            out_shape=jax.ShapeDtypeStruct((T, M, win), x.dtype),
            blocks=(bm, bn, bk), operands=(x, ws)),
        interpret=interpret,
    )(off_blocks, x, ws)
