"""Attention: GQA (full / sliding-window / qk-norm) and MLA, training path.

Both use *blockwise online-softmax attention* (flash-style in pure jnp, scan
over kv chunks), so a long sequence never materializes an SxS score matrix
and the HLO stays small.  Sliding-window attention only visits the kv chunks
inside the window (sub-quadratic).

The attention core (scores, softmax, weighted values; not the q/k/v/o
projections) runs under the ``model.attention`` named scope.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import (ParamBuilder, apply_rope, head_proj,
                                 rms_norm, yarn_freqs, yarn_get_mscale)


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attn_params(b: ParamBuilder, prefix, cfg, layers=0):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b.dense(f"{prefix}/wq", (D, H, hd), ("d_model", "heads", "head_dim"),
            layers=layers)
    b.dense(f"{prefix}/wk", (D, KV, hd), ("d_model", "kv_heads", "head_dim"),
            layers=layers)
    b.dense(f"{prefix}/wv", (D, KV, hd), ("d_model", "kv_heads", "head_dim"),
            layers=layers)
    b.dense(f"{prefix}/wo", (H, hd, D), ("heads", "head_dim", "d_model"),
            layers=layers, scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1)))
    if cfg.qk_norm:
        b.const(f"{prefix}/q_norm", (hd,), ("head_dim",), 1.0, layers=layers)
        b.const(f"{prefix}/k_norm", (hd,), ("head_dim",), 1.0, layers=layers)


def mla_params(b: ParamBuilder, prefix, cfg, layers=0):
    m, D, H = cfg.mla, cfg.d_model, cfg.n_heads
    qh = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank:
        b.dense(f"{prefix}/w_dq", (D, m.q_lora_rank),
                ("d_model", "mla_q_rank"), layers=layers)
        b.const(f"{prefix}/q_norm", (m.q_lora_rank,), ("mla_q_rank",), 1.0,
                layers=layers)
        b.dense(f"{prefix}/w_uq", (m.q_lora_rank, H, qh),
                ("mla_q_rank", "heads", "head_dim"), layers=layers)
    else:  # no q compression (DeepSeek-V2-Lite): one direct projection
        b.dense(f"{prefix}/wq", (D, H, qh), ("d_model", "heads", "head_dim"),
                layers=layers)
    b.dense(f"{prefix}/w_dkv", (D, m.kv_lora_rank), ("d_model", "mla_kv_rank"),
            layers=layers)
    b.const(f"{prefix}/kv_norm", (m.kv_lora_rank,), ("mla_kv_rank",), 1.0,
            layers=layers)
    b.dense(f"{prefix}/w_kr", (D, m.rope_head_dim), ("d_model", "rope_dim"),
            layers=layers)
    b.dense(f"{prefix}/w_uk", (m.kv_lora_rank, H, m.nope_head_dim),
            ("mla_kv_rank", "heads", "head_dim"), layers=layers)
    b.dense(f"{prefix}/w_uv", (m.kv_lora_rank, H, m.v_head_dim),
            ("mla_kv_rank", "heads", "v_head_dim"), layers=layers)
    b.dense(f"{prefix}/wo", (H, m.v_head_dim, D),
            ("heads", "v_head_dim", "d_model"), layers=layers,
            scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1)))


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention
# ---------------------------------------------------------------------------


def _chunk(x, n, axis):
    """Split ``axis`` into [n, axis_len // n] (chunk index first)."""
    shape = list(x.shape)
    shape[axis:axis + 1] = [n, shape[axis] // n]
    return x.reshape(shape)


_Q_CHUNK = int(os.environ.get("REPRO_ATTN_Q_CHUNK", "512"))
_KV_CHUNK = int(os.environ.get("REPRO_ATTN_KV_CHUNK", "512"))


@tracing.scoped(tracing.ATTENTION)
def blockwise_attention(q, k, v, *, causal=True, window=0, q_chunk=0,
                        kv_chunk=0, softmax_scale=None):
    """q [B,Sq,H,hd]; k,v [B,Sk,KV,hd]; H % KV == 0.  Returns [B,Sq,H,hd].

    Online-softmax over kv chunks.  With ``window`` > 0 only the kv chunks
    intersecting [q_pos - window + 1, q_pos] are visited (static trip count),
    giving sub-quadratic cost.  Chunk sizes default to the
    REPRO_ATTN_{Q,KV}_CHUNK env knobs (perf iteration) or 512.
    """
    q_chunk = q_chunk or _Q_CHUNK
    kv_chunk = kv_chunk or _KV_CHUNK
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    qs = _chunk(q.reshape(B, Sq, KV, G, hd), nq, 1)   # [B,nq,Qc,KV,G,hd]
    q_off = Sk - Sq  # q positions = q_off + [0..Sq)

    def one_q_chunk(qi, qc):
        # qc: [B,Qc,KV,G,hd]
        qpos = q_off + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk, kv_chunk, 1)
            vc = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk, kv_chunk, 1)
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            valid = qpos[:, None] >= kpos[None, :] if causal else \
                jnp.ones((q_chunk, kv_chunk), bool)
            if window:
                valid &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        if causal or window:
            # static kv-chunk range for this q chunk
            last = (q_off + (qi + 1) * q_chunk - 1) // kv_chunk  # inclusive
            first = 0
            if window:
                first = max(0, (q_off + qi * q_chunk - window + 1)
                            // kv_chunk)
            idxs = jnp.arange(first, last + 1)
        else:
            idxs = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), idxs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # [B,KV,G,Qc,hd] -> [B,Qc,H,hd]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, q_chunk, H, hd)

    outs = [one_q_chunk(i, qs[:, i]) for i in range(nq)]
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------


# the windowed head projection now lives in models.layers (shared with the
# MLA and SSM head windows); keep the old name for callers and tests.
_head_proj = head_proj


def _qkv(p, x, cfg, positions, window=None):
    """q/k/v projections; ``window`` (a ``WindowMap`` or None) windows the
    q/o heads and the k/v kv-heads independently — GQA coupling (derived
    ``heads = kv_heads * group`` offsets) is the scheme's job upstream."""
    hspec = window.get("heads", p["wq"].shape[1]) if window else None
    kvspec = window.get("kv_heads", p["wk"].shape[1]) if window else None
    bk = window.backend if window else None
    q = _head_proj(x, p["wq"], hspec, bk)
    k = _head_proj(x, p["wk"], kvspec, bk)
    v = _head_proj(x, p["wv"], kvspec, bk)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


_USE_FLASH = bool(os.environ.get("REPRO_USE_FLASH"))


def gqa_train(p, x, cfg, positions, q_chunk=0, kv_chunk=0, window=None):
    q, k, v = _qkv(p, x, cfg, positions, window=window)
    if _USE_FLASH:
        # Pallas flash kernel (VMEM-resident online softmax) — the TPU
        # deployment path; interpret-mode on CPU hosts (see §Perf C3).
        from repro.kernels.dispatch import interpret_mode
        from repro.kernels.flash_attention import flash_attention
        with jax.named_scope(tracing.ATTENTION):
            out = flash_attention(
                q, k, v, causal=True, window=cfg.sliding_window,
                bq=min(512, q.shape[1]), bkv=min(512, k.shape[1]),
                interpret=interpret_mode())
    else:
        out = blockwise_attention(q, k, v, causal=True,
                                  window=cfg.sliding_window,
                                  q_chunk=q_chunk, kv_chunk=kv_chunk)
    wo = p["wo"]
    hspec = window.get("heads", wo.shape[0]) if window else None
    if hspec is not None:
        # the contraction runs over the active heads only: slice the output
        # projection rows to the window (grads scatter back as exact zeros
        # outside — the dynamic_slice transpose)
        wo = jax.lax.dynamic_slice_in_dim(wo, hspec.offset, hspec.win, 0)
    return jnp.einsum("bshe,hed->bsd", out, wo)


# ---------------------------------------------------------------------------
# MLA module
# ---------------------------------------------------------------------------


def _mla_rope(x, positions, cfg):
    """Rope of the decoupled rope dims, YaRN-scaled where the config says
    so (the cos/sin factor mscale / mscale_all_dim; the softmax scale's
    factor is :func:`_mla_scale`'s)."""
    m = cfg.mla
    if not m.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = yarn_freqs(m.rope_head_dim, cfg.rope_theta, m.yarn_factor,
                       m.yarn_beta_fast, m.yarn_beta_slow,
                       m.yarn_original_max)
    scale = (yarn_get_mscale(m.yarn_factor, m.yarn_mscale)
             / yarn_get_mscale(m.yarn_factor, m.yarn_mscale_all_dim))
    return apply_rope(x, positions, cfg.rope_theta, freqs=freqs, scale=scale)


def _mla_scale(cfg):
    """Softmax scale: 1/sqrt(q head dim), times mscale_all_dim's YaRN
    factor squared when that is set."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.nope_head_dim + m.rope_head_dim)
    if m.yarn_factor and m.yarn_mscale_all_dim:
        scale *= yarn_get_mscale(m.yarn_factor, m.yarn_mscale_all_dim) ** 2
    return scale


def _mla_q(p, x, cfg, positions, hspec=None, backend=None):
    m = cfg.mla
    if m.q_lora_rank:
        cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
        q = head_proj(cq, p["w_uq"], hspec, backend)
    else:
        q = head_proj(x, p["wq"], hspec, backend)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    return q_nope, _mla_rope(q_rope, positions, cfg)


def _mla_ckv(p, x, cfg, positions):
    c = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    kr = _mla_rope((x @ p["w_kr"])[:, :, None, :], positions, cfg)[:, :, 0]
    return c, kr


def mla_train(p, x, cfg, positions, q_chunk=0, kv_chunk=0, window=None):
    """Decompressed path: materialize per-head k,v; blockwise attention.

    ``window`` (a ``WindowMap`` or None) applies a *standalone* ``heads``
    window: unlike GQA there is no kv grouping to couple to — every head
    draws its k/v from the shared compressed ``c`` — so the per-head
    projections (``w_uq``, or ``wq`` without q compression, and
    ``w_uk``/``w_uv``) window independently via
    :func:`repro.models.layers.head_proj` and ``wo`` contracts over the
    active heads only.  The shared low-rank down-projections and the
    decoupled rope key stay full (they carry no ``heads`` axis)."""
    m = cfg.mla
    hspec = window.get("heads", p["wo"].shape[0]) if window else None
    bk = window.backend if window else None
    q_nope, q_rope = _mla_q(p, x, cfg, positions, hspec, bk)
    c, kr = _mla_ckv(p, x, cfg, positions)
    k_nope = head_proj(c, p["w_uk"], hspec, bk)
    v = head_proj(c, p["w_uv"], hspec, bk)
    k_rope = jnp.broadcast_to(kr[:, :, None, :], k_nope.shape[:3]
                              + (m.rope_head_dim,))
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, k_rope], -1)
    scale = _mla_scale(cfg)
    # pad v to k's head_dim so blockwise_attention can share hd, then slice
    pad = k.shape[-1] - v.shape[-1]
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    out = blockwise_attention(q, k, vp, causal=True, q_chunk=q_chunk,
                              kv_chunk=kv_chunk, softmax_scale=scale)
    out = out[..., :m.v_head_dim]
    wo = p["wo"]
    if hspec is not None:
        # contraction over the active heads only; grads scatter back as
        # exact zeros outside (the dynamic_slice transpose)
        wo = jax.lax.dynamic_slice_in_dim(wo, hspec.offset, hspec.win, 0)
    return jnp.einsum("bshe,hed->bsd", out, wo)
