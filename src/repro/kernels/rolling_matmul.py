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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl, prefetch_scalar_grid_spec, vmem


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

    grid_spec = prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(M // bm, win // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, off: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, off: (k, off[0] + j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, off: (i, j)),
        scratch_shapes=[vmem((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_rolling_mm_kernel, nk=nk),
        name="rolling_matmul_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, win), x.dtype),
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

    grid_spec = prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(T, M // bm, win // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda t, i, j, k, off: (i, k)),
            pl.BlockSpec((1, bk, bn),
                         lambda t, i, j, k, off: (t, k, off[0] + j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda t, i, j, k, off: (t, i, j)),
        scratch_shapes=[vmem((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_rolling_mm_multi_kernel, nk=nk),
        name="rolling_matmul_multi",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, M, win), x.dtype),
        interpret=interpret,
    )(off_blocks, x, ws)
