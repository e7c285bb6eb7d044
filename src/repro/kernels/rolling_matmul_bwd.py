"""Backward Pallas kernel for the rolling-window matmul.

Forward (``rolling_matmul.py``): ``y[M, win] = x[M, K] @ W[K, off:off+win]``.
This module provides the input-gradient half of its custom VJP:

    dx[M, K] = dy[M, win] @ W[K, off : off+win]^T

as a second offset-prefetch kernel: the window offset again arrives through
``pltpu.PrefetchScalarGridSpec`` and shifts the *column*-block index of W, so
the backward pass — like the forward — reads only the active window of W
from HBM and never materializes a W_sub (or W_sub^T) copy.

The weight gradient needs no kernel: ``dW`` is a window scatter-add
(``x^T @ dy`` placed at the offset, zero elsewhere), which is a single MXU
matmul plus a ``dynamic_update_slice`` — see ``dispatch.rolling_matmul``'s
VJP, where both halves are registered with the jnp oracle as the autodiff
fallback for untileable shapes and unaligned traced offsets.

Grid: (M/bm, K/bn, win/bk), window innermost for accumulator reuse; the
contraction runs over the window axis, so the offset shifts the third grid
index of W's BlockSpec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl
from repro.kernels.rolling_matmul import rolling_spec


def _rolling_dx_kernel(off_ref, dy_ref, w_ref, o_ref, acc_ref, *, nj):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dy block [bm, bk] · W block [bn, bk] contracted on the window axis
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_dx(dy, w, offset, win, *, bm=128, bn=128, bk=128,
                      interpret=True):
    """dy [M, win]; w [K, N]; offset: int32 scalar (multiple of bk).

    Returns dx [M, K] = dy @ w[:, offset:offset+win]^T.
    """
    M = dy.shape[0]
    K = w.shape[0]
    bm, bn, bk = min(bm, M), min(bn, K), min(bk, win)
    assert M % bm == 0 and K % bn == 0 and win % bk == 0
    nj = win // bk
    off_blocks = jnp.asarray(offset, jnp.int32)[None] // bk

    return pl.pallas_call(
        functools.partial(_rolling_dx_kernel, nj=nj),
        name="rolling_matmul_dx",
        **rolling_spec(
            "rolling_matmul_dx",
            grid=(M // bm, K // bn, nj),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, k, j, off: (i, j)),
                pl.BlockSpec((bn, bk), lambda i, k, j, off: (k, off[0] + j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, k, j, off: (i, k)),
            out_shape=jax.ShapeDtypeStruct((M, K), dy.dtype),
            blocks=(bm, bn, bk), operands=(dy, w)),
        interpret=interpret,
    )(off_blocks, dy, w)


# ---------------------------------------------------------------------------
# Multi-step arm: one dx accumulated across T cotangent/weight pairs
# ---------------------------------------------------------------------------


def _rolling_dx_multi_kernel(off_ref, dy_ref, w_ref, o_ref, acc_ref, *,
                             nt, nj):
    t = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(jnp.logical_and(t == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dy_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(t == nt - 1, j == nj - 1))
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_dx_multi(dys, ws, offset, win, *, bm=128, bn=128, bk=128,
                            interpret=True):
    """dys [T,M,win]; ws [T,K,N]; offset: int32 scalar (multiple of bk).

    Returns dx [M, K] = sum_t dys[t] @ ws[t][:, offset:offset+win]^T — the
    backward half of the multi-step forward (``rolling_matmul_multi``): the
    T per-step input gradients accumulate in the SAME VMEM scratch across
    the step grid dimension, so the fused pair's dx needs one kernel call
    and no intermediate [T, M, K] stack.  Step/window blocks stream through
    the usual cross-iteration double buffering (the next (t, j) W fetch
    overlaps the current dot).
    """
    T, M = dys.shape[0], dys.shape[1]
    K = ws.shape[1]
    bm, bn, bk = min(bm, M), min(bn, K), min(bk, win)
    assert M % bm == 0 and K % bn == 0 and win % bk == 0
    nj = win // bk
    off_blocks = jnp.asarray(offset, jnp.int32)[None] // bk

    return pl.pallas_call(
        functools.partial(_rolling_dx_multi_kernel, nt=T, nj=nj),
        name="rolling_matmul_dx_multi",
        **rolling_spec(
            "rolling_matmul_dx_multi",
            grid=(M // bm, K // bn, T, nj),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda i, k, t, j, off: (t, i, j)),
                pl.BlockSpec((1, bn, bk),
                             lambda i, k, t, j, off: (t, k, off[0] + j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, k, t, j, off: (i, k)),
            out_shape=jax.ShapeDtypeStruct((M, K), dys.dtype),
            blocks=(bm, bn, bk), operands=(dys, ws)),
        interpret=interpret,
    )(off_blocks, dys, ws)
