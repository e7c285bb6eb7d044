"""Batched-offset rolling-window matmul — the staggered-scheme hot spot.

The shared-window kernels (``rolling_matmul.py`` / ``rolling_matmul_bwd.py``)
take ONE scalar window offset: every client trains the same contiguous
column window of W, which is exactly the non-staggered rolling/static/
importance schemes.  The *staggered* rolling scheme (and the random
structured scheme) give every client its OWN window, so a client-vmapped
window-aware forward on full weights needs the batched form

    y[b, M, win] = x[b, M, K] @ W[b, K, off[b] : off[b]+win]      b = 0..B-1

with a *vector* of per-client offsets.  This module provides that pair:

* :func:`rolling_matmul_batched`     — the forward;
* :func:`rolling_matmul_batched_dx`  — the input-gradient backward half
  (``dx[b] = dy[b] @ W[b, :, off[b]:off[b]+win]^T``).

Both kernels prefetch the whole ``off_blocks`` vector through
``pltpu.PrefetchScalarGridSpec`` and index it with the leading (batch) grid
coordinate — one scalar-prefetch row per client — so each client's kernel
instance reads only its active window of W from HBM and no per-client
W_sub stack is ever materialized, as in the shared-window arm.  (The fed
round no longer runs these kernels: it cuts each client's windows once a
round and carries the cut tree, ``core/fedavg.py``.)

The weight gradient needs no kernel (per-row window scatter-add of
``x[b]^T @ dy[b]``); see ``dispatch.rolling_matmul_batched``'s custom VJP,
which mirrors the shared-offset VJP in ``dispatch.rolling_matmul`` and
falls back to the vmapped jnp oracle for untileable shapes and unaligned
traced offsets.

Grids: forward (B, M/bm, win/bn, K/bk) with K innermost for accumulator
reuse; backward (B, M/bm, K/bn, win/bk) with the window innermost — the
same shapes as the unbatched kernels plus the leading batch dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl
from repro.kernels.rolling_matmul import rolling_spec


def _batched_mm_kernel(off_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_batched(x, w, offsets, win, *, bm=128, bn=128, bk=128,
                           interpret=True):
    """x [B,M,K]; w [B,K,N]; offsets: int32 [B] (multiples of bn); win static.

    Returns y [B, M, win] with y[b] = x[b] @ w[b][:, offsets[b] :
    offsets[b]+win].
    """
    B, M, K = x.shape
    bm, bn, bk = min(bm, M), min(bn, win), min(bk, K)
    assert win % bn == 0 and M % bm == 0 and K % bk == 0
    nk = K // bk
    off_blocks = jnp.asarray(offsets, jnp.int32) // bn

    return pl.pallas_call(
        functools.partial(_batched_mm_kernel, nk=nk),
        name="rolling_matmul_batched_fwd",
        **rolling_spec(
            "rolling_matmul_batched_fwd",
            grid=(B, M // bm, win // bn, nk),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda b, i, j, k, off: (b, i, k)),
                pl.BlockSpec((1, bk, bn),
                             lambda b, i, j, k, off: (b, k, off[b] + j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, j, k, off: (b, i, j)),
            out_shape=jax.ShapeDtypeStruct((B, M, win), x.dtype),
            blocks=(bm, bn, bk), operands=(x, w)),
        interpret=interpret,
    )(off_blocks, x, w)


def _batched_dx_kernel(off_ref, dy_ref, w_ref, o_ref, acc_ref, *, nj):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dy block [bm, bk] · W block [bn, bk] contracted on the window axis
    acc_ref[...] += jax.lax.dot_general(
        dy_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_batched_dx(dy, w, offsets, win, *, bm=128, bn=128,
                              bk=128, interpret=True):
    """dy [B,M,win]; w [B,K,N]; offsets: int32 [B] (multiples of bk).

    Returns dx [B, M, K] with dx[b] = dy[b] @ w[b][:, offsets[b] :
    offsets[b]+win]^T.
    """
    B, M = dy.shape[0], dy.shape[1]
    K = w.shape[1]
    bm, bn, bk = min(bm, M), min(bn, K), min(bk, win)
    assert M % bm == 0 and K % bn == 0 and win % bk == 0
    nj = win // bk
    off_blocks = jnp.asarray(offsets, jnp.int32) // bk

    return pl.pallas_call(
        functools.partial(_batched_dx_kernel, nj=nj),
        name="rolling_matmul_batched_dx",
        **rolling_spec(
            "rolling_matmul_batched_dx",
            grid=(B, M // bm, K // bn, nj),
            in_specs=[
                pl.BlockSpec((1, bm, bk), lambda b, i, k, j, off: (b, i, j)),
                pl.BlockSpec((1, bn, bk),
                             lambda b, i, k, j, off: (b, k, off[b] + j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, k, j, off: (b, i, k)),
            out_shape=jax.ShapeDtypeStruct((B, M, K), dy.dtype),
            blocks=(bm, bn, bk), operands=(dy, w)),
        interpret=interpret,
    )(off_blocks, dy, w)


# ---------------------------------------------------------------------------
# Multi-step arms: T windowed matmuls per client, per-client offsets
# ---------------------------------------------------------------------------


def _batched_mm_multi_kernel(off_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(4)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0, 0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_batched_multi(x, ws, offsets, win, *, bm=128, bn=128,
                                 bk=128, interpret=True):
    """x [B,M,K]; ws [T,B,K,N]; offsets: int32 [B] (multiples of bn).

    Returns ys [B, T, M, win] with ys[b, t] = x[b] @ ws[t, b][:, offsets[b] :
    offsets[b]+win] — the batched-offset form of ``rolling_matmul_multi``:
    each client runs its T-step group (gate/up pair) as one kernel instance
    against its own window, keeping the staggered fused round single-call
    per weight group.
    """
    T = ws.shape[0]
    B, M, K = x.shape
    bm, bn, bk = min(bm, M), min(bn, win), min(bk, K)
    assert win % bn == 0 and M % bm == 0 and K % bk == 0
    nk = K // bk
    off_blocks = jnp.asarray(offsets, jnp.int32) // bn

    return pl.pallas_call(
        functools.partial(_batched_mm_multi_kernel, nk=nk),
        name="rolling_matmul_batched_multi",
        **rolling_spec(
            "rolling_matmul_batched_multi",
            grid=(B, T, M // bm, win // bn, nk),
            in_specs=[
                pl.BlockSpec((1, bm, bk),
                             lambda b, t, i, j, k, off: (b, i, k)),
                pl.BlockSpec((1, 1, bk, bn),
                             lambda b, t, i, j, k, off: (t, b, k, off[b] + j)),
            ],
            out_specs=pl.BlockSpec((1, 1, bm, bn),
                                   lambda b, t, i, j, k, off: (b, t, i, j)),
            out_shape=jax.ShapeDtypeStruct((B, T, M, win), x.dtype),
            blocks=(bm, bn, bk), operands=(x, ws)),
        interpret=interpret,
    )(off_blocks, x, ws)


def _batched_dx_multi_kernel(off_ref, dy_ref, w_ref, o_ref, acc_ref, *,
                             nt, nj):
    t = pl.program_id(3)
    j = pl.program_id(4)

    @pl.when(jnp.logical_and(t == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dy_ref[0, 0], w_ref[0, 0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(t == nt - 1, j == nj - 1))
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def rolling_matmul_batched_dx_multi(dys, ws, offsets, win, *, bm=128,
                                    bn=128, bk=128, interpret=True):
    """dys [B,T,M,win]; ws [T,B,K,N]; offsets: int32 [B] (multiples of bk).

    Returns dx [B, M, K] with dx[b] = sum_t dys[b, t] @ ws[t, b][:,
    offsets[b] : offsets[b]+win]^T — the step-accumulated backward of
    ``rolling_matmul_batched_multi``, mirroring ``rolling_matmul_dx_multi``
    with the leading batch dimension and a per-client prefetched offset row.
    """
    B, T, M = dys.shape[0], dys.shape[1], dys.shape[2]
    K = ws.shape[2]
    bm, bn, bk = min(bm, M), min(bn, K), min(bk, win)
    assert M % bm == 0 and K % bn == 0 and win % bk == 0
    nj = win // bk
    off_blocks = jnp.asarray(offsets, jnp.int32) // bk

    return pl.pallas_call(
        functools.partial(_batched_dx_multi_kernel, nt=T, nj=nj),
        name="rolling_matmul_batched_dx_multi",
        **rolling_spec(
            "rolling_matmul_batched_dx_multi",
            grid=(B, M // bm, K // bn, T, nj),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda b, i, k, t, j, off: (b, t, i, j)),
                pl.BlockSpec((1, 1, bn, bk),
                             lambda b, i, k, t, j, off: (t, b, k, off[b] + j)),
            ],
            out_specs=pl.BlockSpec((1, bm, bn),
                                   lambda b, i, k, t, j, off: (b, i, k)),
            out_shape=jax.ShapeDtypeStruct((B, M, K), dys.dtype),
            blocks=(bm, bn, bk), operands=(dys, ws)),
        interpret=interpret,
    )(off_blocks, dys, ws)
