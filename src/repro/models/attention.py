"""Attention: GQA (full / sliding-window / qk-norm), MLA, decode paths.

Training / prefill use *blockwise online-softmax attention* (flash-style in
pure jnp, scan over kv chunks) so the 32k-prefill never materializes an SxS
score matrix and the HLO stays small for the dry-run.  Sliding-window
attention only visits the kv chunks inside the window (sub-quadratic).

Decode is one-token attention against a KV cache.  For `long_500k` the cache
is sharded along the sequence dim over the mesh `data` axis and combined with
an exact log-sum-exp psum (`cp_decode_attention`) — context-parallel decode.

The attention core (scores, softmax, weighted values; not the q/k/v/o
projections) runs under the ``model.attention`` named scope in every path.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.models.layers import (ParamBuilder, apply_rope, head_proj,
                                 rms_norm, yarn_freqs, yarn_get_mscale)
from repro.sharding.spmd import shard_map


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
# Blockwise (flash-style) attention — train / prefill
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
# Decode attention (one token vs cache)
# ---------------------------------------------------------------------------


@tracing.scoped(tracing.ATTENTION)
def decode_attention(q, k, v, valid, softmax_scale=None):
    """q [B,H,hd]; k,v [B,Sc,KV,hd]; valid [B,Sc] bool.  -> [B,H,vdim]."""
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, -1)


@tracing.scoped(tracing.ATTENTION)
def cp_decode_attention(mesh, q, k, v, valid, axis="data", softmax_scale=None):
    """Context-parallel exact decode attention.

    k/v/valid are sharded along their sequence dim over ``axis``; q is
    replicated on ``axis``.  Heads stay sharded on `model` (manual there too).
    One psum_max + two psums — linear in local S.
    """
    B, H, hd = q.shape
    KV = k.shape[2]
    scale = softmax_scale or 1.0 / math.sqrt(hd)
    msize = mesh.shape.get("model", 1)
    # shard heads over `model` only when the GQA grouping survives the split
    if KV % msize == 0 and H % msize == 0:
        qh_spec = kvh_spec = "model"
    else:
        qh_spec = kvh_spec = None

    def local(qh, kh, vh, validh):
        G = qh.shape[1] // kh.shape[2]
        qg = qh.reshape(B, kh.shape[2], G, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kh,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(validh[:, None, None, :], s, NEG_INF)
        m = s.max(-1)
        gm = jax.lax.pmax(m, axis)
        p = jnp.exp(s - gm[..., None])
        l = jax.lax.psum(p.sum(-1), axis)
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(vh.dtype), vh,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, axis)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, qh.shape[1], -1)

    fn = shard_map(
        local, mesh,
        in_specs=(P(None, qh_spec, None), P(None, axis, kvh_spec, None),
                  P(None, axis, kvh_spec, None), P(None, axis)),
        out_specs=P(None, qh_spec, None))
    return fn(q, k, v, valid)


# ---------------------------------------------------------------------------
# GQA module (train / prefill / decode)
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


def gqa_prefill(p, x, cfg, positions, cache_len):
    q, k, v = _qkv(p, x, cfg, positions)
    out = blockwise_attention(q, k, v, causal=True,
                              window=cfg.sliding_window)
    S = x.shape[1]
    if cache_len < S:  # ring (sliding-window) cache holds the last cache_len
        shift = (S - cache_len) % cache_len if cache_len else 0
        kc = jnp.roll(k[:, -cache_len:], shift, axis=1)
        vc = jnp.roll(v[:, -cache_len:], shift, axis=1)
    else:
        kc, vc = k, v
    return jnp.einsum("bshe,hed->bsd", out, p["wo"]), {"k": kc, "v": vc}


def gqa_decode(p, x, cfg, cache, pos, mesh=None, cp=False,
               valid_override=None, rope_pos=None):
    """x [B,1,D]; cache {k,v: [B,Sc,KV,hd]}; pos scalar int (cache write
    slot / causal horizon).

    valid_override [B,Sc] bool: per-slot cache validity; rope_pos [B]: per-
    slot logical positions (continuous batching timelines with gaps)."""
    positions = rope_pos[:, None] if rope_pos is not None \
        else jnp.full((x.shape[0], 1), pos)
    q, k, v = _qkv(p, x, cfg, positions)
    Sc = cache["k"].shape[1]
    slot = pos % Sc
    kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
    vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
    idx = jnp.arange(Sc)
    if valid_override is not None:
        valid = valid_override
    elif cfg.sliding_window and Sc <= cfg.sliding_window:
        valid = (idx <= pos) | (pos + 1 >= Sc)     # ring fully valid once wrapped
        valid = jnp.broadcast_to(valid, (x.shape[0], Sc))
    else:
        valid = jnp.broadcast_to(idx <= pos, (x.shape[0], Sc))
    if cp and mesh is not None:
        out = cp_decode_attention(mesh, q[:, 0], kc, vc, valid)
    else:
        out = decode_attention(q[:, 0], kc, vc, valid)
    out = jnp.einsum("bhe,hed->bd", out.astype(x.dtype), p["wo"])
    return out[:, None, :], {"k": kc, "v": vc}


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


def mla_prefill(p, x, cfg, positions):
    out = mla_train(p, x, cfg, positions)
    c, kr = _mla_ckv(p, x, cfg, positions)
    return out, {"c": c, "kr": kr}


def mla_decode(p, x, cfg, cache, pos, mesh=None, cp=False,
               valid_override=None, rope_pos=None):
    """Absorbed path — attends in compressed space; cache {c:[B,S,r], kr}."""
    B = x.shape[0]
    posv = rope_pos[:, None] if rope_pos is not None \
        else jnp.full((B, 1), pos)
    q_nope, q_rope = _mla_q(p, x, cfg, posv)          # [B,1,H,*]
    c_t, kr_t = _mla_ckv(p, x, cfg, posv)             # [B,1,r],[B,1,rd]
    cc = jax.lax.dynamic_update_slice_in_dim(cache["c"], c_t, pos, 1)
    krc = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_t, pos, 1)
    # absorb W_uk into q:  q_c [B,H,r]
    q_c = jnp.einsum("bhe,rhe->bhr", q_nope[:, 0], p["w_uk"])
    q_cat = jnp.concatenate([q_c, q_rope[:, 0]], -1)  # [B,H,r+rd]
    k_cat = jnp.concatenate([cc, krc], -1)[:, :, None, :]  # [B,S,1,r+rd]
    v = cc[:, :, None, :]                              # [B,S,1,r]
    S = cc.shape[1]
    valid = valid_override if valid_override is not None else \
        jnp.broadcast_to(jnp.arange(S) <= pos, (B, S))
    scale = _mla_scale(cfg)
    if cp and mesh is not None:
        ctx = cp_decode_attention(mesh, q_cat, k_cat, v, valid,
                                  softmax_scale=scale)
    else:
        ctx = decode_attention(q_cat, k_cat, v, valid, softmax_scale=scale)
    out = jnp.einsum("bhr,rhe->bhe", ctx.astype(x.dtype), p["w_uv"])
    out = jnp.einsum("bhe,hed->bd", out, p["wo"])
    return out[:, None, :], {"c": cc, "kr": krc}
