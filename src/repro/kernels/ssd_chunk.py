"""Pallas TPU kernel for the intra-chunk SSD block (Mamba-2 hot spot).

One grid cell = (batch b, chunk c, head-block h): computes, entirely in VMEM,

    dA   = dt ⊙ A,     L = cumsum(dA)
    Y    = ((C Bᵀ) ⊙ exp(L_q − L_t) ⊙ 1[q≥t] ⊙ dt_t) X        (MXU dots)
    S    = Σ_t exp(L_last − L_t)·dt_t · X_t ⊗ B_t              (chunk state)

i.e. the quadratic-intra-chunk term and the chunk-exit state of the SSD
block decomposition.  The O(S) inter-chunk recurrence (a tiny [nh,hd,N]
scan) stays outside in jnp — see ``ops.ssd_chunk_scan``.

Head-blocked so the [nh_b, Q, Q] decay tensor stays VMEM-resident
(nh_b·Q²·4B ≤ ~4 MB at Q=128, nh_b=64).

Runs in interpret mode only: the TPU compiler refuses both forms.  The
full-head form needs a ``cumsum``, which Mosaic cannot lower; the
head-window form takes ``(…, nh_b, hd)`` blocks of a ``(…, nh, hd)``
array (e.g. 12 of 24 heads), which breaks the rule that a block's last
two dims be multiples of 8×128 or the whole dims.  No caller on the
training path uses it; only the kernel tests do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl, prefetch_scalar_grid_spec


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0, 0].astype(jnp.float32)       # [Q, nhb, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)     # [Q, nhb]
    A = a_ref[...].astype(jnp.float32)        # [nhb]
    B = b_ref[0, 0].astype(jnp.float32)       # [Q, N]
    C = c_ref[0, 0].astype(jnp.float32)       # [Q, N]
    Q = x.shape[0]

    dA = dt * A                                # [Q, nhb]
    L = jnp.cumsum(dA, axis=0)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # [Q, Q]
    Lh = L.T                                   # [nhb, Q]
    diff = Lh[:, :, None] - Lh[:, None, :]     # [nhb, Q, Q]
    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    decay = jnp.where(causal[None], jnp.exp(diff), 0.0)
    M = CB[None] * decay * dt.T[:, None, :]    # [nhb, Q, Q]
    y = jnp.einsum("hqt,thp->qhp", M, x,
                   preferred_element_type=jnp.float32)
    sdecay = jnp.exp(Lh[:, -1:] - Lh) * dt.T   # [nhb, Q]
    state = jnp.einsum("thp,tn,ht->hpn", x, B, sdecay,
                       preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    s_ref[0, 0] = state


def _ssd_chunk_kernel_offset(off_ref, *refs):
    # head-window variant: the prefetched offset is consumed by the
    # BlockSpec index maps only — the kernel body is unchanged.
    del off_ref
    _ssd_chunk_kernel(*refs)


def ssd_chunk_intra(x, dt, A, B, C, *, nh_block=0, interpret=True,
                    head_offset=None, head_win=0):
    """x [Bt,nc,Q,nh,hd]; dt [Bt,nc,Q,nh]; A [nh]; B,C [Bt,nc,Q,N].

    Returns (y_intra [Bt,nc,Q,nh,hd], states [Bt,nc,nh,hd,N] f32).

    ``head_offset``/``head_win`` window the SSD over a contiguous
    ``ssm_heads`` range of FULL-width inputs (the sub-model training
    window): the offset arrives via scalar prefetch and shifts the
    head-block grid index of x/dt/A, so inactive heads are never read from
    HBM and the outputs are compact ``[..., head_win, ...]`` — the
    kernel-level form of the windowed SSD projection in
    ``repro.models.ssm``.  ``head_offset`` must be a multiple of the head
    block; ``head_win`` a multiple too.
    """
    Bt, nc, Q, nh, hd = x.shape
    N = B.shape[-1]
    win = head_win or nh
    nhb = nh_block or win
    assert win % nhb == 0
    out_shapes = (
        jax.ShapeDtypeStruct((Bt, nc, Q, win, hd), x.dtype),
        jax.ShapeDtypeStruct((Bt, nc, win, hd, N), jnp.float32),
    )
    if head_offset is None:
        assert nh % nhb == 0
        return pl.pallas_call(
            _ssd_chunk_kernel,
            name="ssd_chunk",
            grid=(Bt, nc, nh // nhb),
            in_specs=[
                pl.BlockSpec((1, 1, Q, nhb, hd),
                             lambda b, c, h: (b, c, 0, h, 0)),
                pl.BlockSpec((1, 1, Q, nhb), lambda b, c, h: (b, c, 0, h)),
                pl.BlockSpec((nhb,), lambda b, c, h: (h,)),
                pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
                pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            ],
            out_specs=(
                pl.BlockSpec((1, 1, Q, nhb, hd),
                             lambda b, c, h: (b, c, 0, h, 0)),
                pl.BlockSpec((1, 1, nhb, hd, N),
                             lambda b, c, h: (b, c, h, 0, 0)),
            ),
            out_shape=out_shapes,
            interpret=interpret,
        )(x, dt, A, B, C)

    off_blocks = jnp.asarray(head_offset, jnp.int32)[None] // nhb
    grid_spec = prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(Bt, nc, win // nhb),
        in_specs=[
            pl.BlockSpec((1, 1, Q, nhb, hd),
                         lambda b, c, h, off: (b, c, 0, off[0] + h, 0)),
            pl.BlockSpec((1, 1, Q, nhb),
                         lambda b, c, h, off: (b, c, 0, off[0] + h)),
            pl.BlockSpec((nhb,), lambda b, c, h, off: (off[0] + h,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h, off: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h, off: (b, c, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, Q, nhb, hd),
                         lambda b, c, h, off: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, nhb, hd, N),
                         lambda b, c, h, off: (b, c, h, 0, 0)),
        ),
    )
    return pl.pallas_call(
        _ssd_chunk_kernel_offset,
        name="ssd_chunk_offset",
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(off_blocks, x, dt, A, B, C)
