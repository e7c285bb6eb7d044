"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

The §Perf analysis of qwen3-14b train_4k shows the memory roofline term is
dominated by materialized [Qc, KVc] score tensors in the scan-based jnp
attention (~670 MB per block pair at mb=16): XLA cannot keep the online-
softmax state in registers across scan steps.  This kernel is the TPU-native
fix — m/l/acc live in VMEM scratch across the kv-block grid dimension and
scores never touch HBM:

  HBM traffic = read(q,k,v) + write(out)        (vs ~50x that for the scan)

Grid: (batch x kv_head, q_blocks, kv_blocks); kv innermost so the VMEM
accumulator is revisited.  Causality skips fully-masked kv blocks via
@pl.when (the block is still visited but performs no work — on TPU the
bandwidth win comes from never spilling the softmax state).

Validated in interpret mode against the jnp blockwise oracle
(``repro.models.attention.blockwise_attention``) over shape/dtype sweeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.compat import pl, vmem

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, window, bq, bkv, nkv):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = kj * bkv
    # skip kv blocks entirely above the causal diagonal / outside the window
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window:
        run = jnp.logical_and(run, k_start + bkv - 1 >= q_start - window + 1) \
            if not isinstance(run, bool) else (k_start + bkv - 1
                                               >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)        # [bq, G, hd]
        k = k_ref[0].astype(jnp.float32)        # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)        # [bkv, hd]
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # s: [bq, G, bkv]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        valid = jnp.ones(s.shape, jnp.bool_)
        if causal:
            valid &= qpos >= kpos
        if window:
            valid &= (qpos - kpos) < window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]                     # [bq, G]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        pv = jnp.einsum("qgs,sd->qgd", p, v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(kj == nkv - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, bq=512, bkv=512,
                    softmax_scale=None, interpret=True):
    """q [B,Sq,H,hd]; k,v [B,Skv,KV,hd] -> [B,Sq,H,hd].  GQA via grouping."""
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    bq = min(bq, Sq)
    bkv = min(bkv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0
    nkv = Skv // bkv
    # layout: fold (B, KV) into the leading grid dim
    qg = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B * KV, Sq, G, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    grid = (B * KV, Sq // bq, nkv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, nkv=nkv),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, Sq, G, hd), q.dtype),
        scratch_shapes=[
            vmem((bq, G), jnp.float32),
            vmem((bq, G), jnp.float32),
            vmem((bq, G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kg, vg)
    return out.reshape(B, KV, Sq, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Sq, H, hd)


def flash_hbm_bytes(B, Sq, Skv, H, KV, hd, dtype_bytes=2):
    """Ideal HBM traffic of the kernel (roofline projection)."""
    q = B * Sq * H * hd
    kv = 2 * B * Skv * KV * hd * (Sq // 512)   # k,v re-read per q block
    out = B * Sq * H * hd
    return (q + kv + out) * dtype_bytes
