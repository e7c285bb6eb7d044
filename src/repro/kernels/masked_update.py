"""Pallas TPU kernels for the two memory-bound hot loops of sub-model
training:

* ``masked_sgd``  — w ← w − η·(m ⊙ g): the paper's local update, one fused
  read-modify-write instead of three HBM round-trips.
* ``fillin_agg``  — w ← w + (s/C)·Σ_c m_c ⊙ (w_c − w): the server fill-in
  average (delta form) fused across the client axis.

Both kernels operate on 2-D tiles (rows × 128-lane multiples, 8-sublane
aligned) — ``ops.py`` flattens/pads arbitrary parameter leaves into this
layout.  On a TPU that flatten is a physical relayout of each leaf, not a
bitcast, so the hot unmasked update of window mode (w ← w − η·g) has no
kernel here: ``dispatch.sgd_step`` leaves it to XLA's fusion on each leaf
as it lies.  Validated against ``ref.py`` in interpret mode on CPU; TPU is
the compile target (VMEM-resident tiles, VPU elementwise).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl

LANE = 128
SUBLANE = 8


def _masked_sgd_kernel(p_ref, m_ref, g_ref, o_ref, *, lr):
    o_ref[...] = (p_ref[...].astype(jnp.float32)
                  - lr * m_ref[...].astype(jnp.float32)
                  * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def masked_sgd_2d(p, m, g, lr, block_rows=256, interpret=True):
    """p,m,g: [R, 128k] identical shapes; lr static float."""
    R, C = p.shape
    br = min(block_rows, R)
    spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_masked_sgd_kernel, lr=float(lr)),
        name="masked_sgd",
        grid=(pl.cdiv(R, br),),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        interpret=interpret,
    )(p, m, g)


def _fillin_kernel(w_ref, wc_ref, mc_ref, o_ref, *, scale, n_clients):
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.zeros_like(w)
    for c in range(n_clients):  # static unroll over the client axis
        acc += mc_ref[c].astype(jnp.float32) * (
            wc_ref[c].astype(jnp.float32) - w)
    o_ref[...] = (w + scale * acc).astype(o_ref.dtype)


def fillin_agg_2d(w, w_clients, m_clients, scale, block_rows=256,
                  interpret=True):
    """w [R,Cols]; w_clients,m_clients [Cl,R,Cols]; scale = server_lr / Cl."""
    R, Cols = w.shape
    Cl = w_clients.shape[0]
    br = min(block_rows, R)
    wspec = pl.BlockSpec((br, Cols), lambda i: (i, 0))
    cspec = pl.BlockSpec((Cl, br, Cols), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_fillin_kernel, scale=float(scale), n_clients=Cl),
        name="fillin_agg",
        grid=(pl.cdiv(R, br),),
        in_specs=[wspec, cspec, cspec],
        out_specs=wspec,
        out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
        interpret=interpret,
    )(w, w_clients, m_clients)
