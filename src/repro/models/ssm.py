"""Mamba-2 (SSD, state-space duality) mixer — TPU-native chunked form.

Training uses the *chunked SSD block decomposition* [arXiv:2405.21060]:
intra-chunk quadratic (attention-like, MXU matmuls) + inter-chunk state
recurrence via ``lax.scan`` over chunks — O(S) with matmul-dominated compute,
which is the right adaptation of the selective-scan to the MXU (no
warp-shuffle scan tricks needed on TPU).

Projections are kept *split* (w_z/w_x/w_B/w_C/w_dt instead of one fused
in_proj) so each carries clean semantic axis tags for sub-model windowing —
``ssm_heads`` is the windowed unit; B/C (ngroups=1, shared across heads) and
d_state stay full.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamBuilder, head_proj, rms_norm


def ssm_params(b: ParamBuilder, prefix, cfg, layers=0):
    s, D = cfg.ssm, cfg.d_model
    nh = s.n_heads or (s.expand * D) // s.head_dim
    hd, N, cw = s.head_dim, s.d_state, s.conv_width
    b.dense(f"{prefix}/w_z", (D, nh, hd), ("d_model", "ssm_heads",
                                           "ssm_head_dim"), layers=layers)
    b.dense(f"{prefix}/w_x", (D, nh, hd), ("d_model", "ssm_heads",
                                           "ssm_head_dim"), layers=layers)
    b.dense(f"{prefix}/w_B", (D, N), ("d_model", "ssm_state"), layers=layers)
    b.dense(f"{prefix}/w_C", (D, N), ("d_model", "ssm_state"), layers=layers)
    b.dense(f"{prefix}/w_dt", (D, nh), ("d_model", "ssm_heads"), layers=layers)
    b.const(f"{prefix}/dt_bias", (nh,), ("ssm_heads",), 0.0, layers=layers)
    b.const(f"{prefix}/A_log", (nh,), ("ssm_heads",), 0.0, layers=layers)
    b.const(f"{prefix}/D_skip", (nh,), ("ssm_heads",), 1.0, layers=layers)
    b.dense(f"{prefix}/conv_x", (cw, nh, hd), ("conv_w", "ssm_heads",
                                               "ssm_head_dim"), layers=layers)
    b.dense(f"{prefix}/conv_B", (cw, N), ("conv_w", "ssm_state"),
            layers=layers)
    b.dense(f"{prefix}/conv_C", (cw, N), ("conv_w", "ssm_state"),
            layers=layers)
    b.const(f"{prefix}/y_norm", (nh, hd), ("ssm_heads", "ssm_head_dim"), 1.0,
            layers=layers)
    b.dense(f"{prefix}/w_out", (nh, hd, D), ("ssm_heads", "ssm_head_dim",
                                             "d_model"), layers=layers)


def _causal_conv(x, w):
    """x [B,S,ch]; w [cw,ch] depthwise causal conv."""
    cw, ch = w.shape
    out = jax.lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(cw - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=ch)
    return out


def _projections(p, x):
    z = jnp.einsum("bsd,dhe->bshe", x, p["w_z"])
    xr = jnp.einsum("bsd,dhe->bshe", x, p["w_x"])
    Br = x @ p["w_B"]
    Cr = x @ p["w_C"]
    dt_raw = x @ p["w_dt"] + p["dt_bias"]
    return z, xr, Br, Cr, dt_raw


def _projections_windowed(p, x, spec, backend=None):
    """Windowed SSD projections: the ``ssm_heads`` window restricted to the
    FULL weights.  z/x run through the head-flattened rolling matmul
    (:func:`repro.models.layers.head_proj`); dt is the same window on the
    2-D ``[D, nh]`` layout (``dispatch.rolling_matmul``); B/C/state are
    shared across heads (ngroups=1) and stay full.  Inactive heads' columns
    are never read from HBM, and the custom VJP scatters their gradients
    back as exact zeros — the fused-round fill-in contract."""
    from repro.kernels.dispatch import rolling_matmul  # lazy: no import cycle
    z = head_proj(x, p["w_z"], spec, backend)
    xr = head_proj(x, p["w_x"], spec, backend)
    Br = x @ p["w_B"]
    Cr = x @ p["w_C"]
    lead = x.shape[:-1]
    dt_win = rolling_matmul(
        x.reshape(-1, x.shape[-1]), p["w_dt"], spec.offset, spec.win,
        backend=backend, assume_aligned=spec.aligned(min(128, spec.win)))
    dt_bias = jax.lax.dynamic_slice_in_dim(p["dt_bias"], spec.offset,
                                           spec.win, 0)
    dt_raw = dt_win.reshape(*lead, spec.win) + dt_bias
    return z, xr, Br, Cr, dt_raw


def ssd_chunked(xr, dt, A, Br, Cr, chunk):
    """Chunked SSD.  xr [B,S,nh,hd]; dt [B,S,nh]; A [nh]; Br/Cr [B,S,N].

    Returns y [B,S,nh,hd] and final state [B,nh,hd,N].
    """
    B, S, nh, hd = xr.shape
    N = Br.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    xs = xr.reshape(B, nc, Q, nh, hd)
    dts = dt.reshape(B, nc, Q, nh)
    Bs = Br.reshape(B, nc, Q, N)
    Cs = Cr.reshape(B, nc, Q, N)
    dA = dts * A                                         # [B,nc,Q,nh] (<=0)
    L = jnp.cumsum(dA, axis=2)                           # inclusive
    # ---- intra-chunk (quadratic within chunk) ----
    CB = jnp.einsum("bcqn,bctn->bcqt", Cs, Bs,
                    preferred_element_type=jnp.float32)  # [B,nc,Q,Q]
    # decay[b,c,h,q,t] = exp(L[q,h]-L[t,h]) for q>=t
    Lh = L.transpose(0, 1, 3, 2)                         # [B,nc,nh,Q]
    diff = Lh[..., :, None] - Lh[..., None, :]           # [B,nc,nh,Q,Q]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.where(causal, jnp.exp(diff), 0.0)
    M = CB[:, :, None] * decay * dts.transpose(0, 1, 3, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchqt,bcthp->bcqhp", M.astype(xs.dtype), xs,
                         preferred_element_type=jnp.float32)
    # ---- chunk states ----
    Llast = Lh[..., -1:]                                 # [B,nc,nh,1]
    sdecay = jnp.exp(Llast - Lh) * dts.transpose(0, 1, 3, 2)  # [B,nc,nh,Q]
    states = jnp.einsum("bcthp,bctn,bcht->bchpn", xs, Bs,
                        sdecay.astype(xs.dtype),
                        preferred_element_type=jnp.float32)

    # ---- inter-chunk recurrence ----
    def step(h, inp):
        st, dtot = inp                                   # [B,nh,hd,N],[B,nh]
        h_new = h * jnp.exp(dtot)[:, :, None, None] + st
        return h_new, h                                  # emit state at entry

    dtot = dA.sum(2)                                     # [B,nc,nh]
    h0 = jnp.zeros((B, nh, hd, N), jnp.float32)
    hT, h_entry = jax.lax.scan(
        step, h0, (states.transpose(1, 0, 2, 3, 4),
                   dtot.transpose(1, 0, 2)))
    h_entry = h_entry.transpose(1, 0, 2, 3, 4)           # [B,nc,nh,hd,N]
    y_inter = jnp.einsum("bcqn,bchpn->bcqhp", Cs, h_entry.astype(Cs.dtype),
                         preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(L)[..., None].astype(y_inter.dtype)
    y = (y_intra + y_inter).reshape(B, S, nh, hd)
    return y.astype(xr.dtype), hT


def ssm_train(p, x, cfg, window=None):
    """x [B,S,D] -> [B,S,D].

    ``window`` (a ``WindowMap`` or None) applies an ``ssm_heads`` window on
    the FULL weights: the windowed SSD projections
    (:func:`_projections_windowed` — only the active heads' activations
    are ever computed), the per-head conv / gate / skip / norm / A
    parameters sliced to the active head range, and ``w_out`` contracting
    over the active heads only.  The chunked SSD then runs on ``win``
    heads — identical ops to the extracted compact model, so fused ==
    extract stays bitwise.  (``kernels.ssd_chunk.ssd_chunk_intra`` also
    offers a ``head_offset``-prefetch window for callers that keep
    FULL-width activations and window only the mixer; this training path
    deliberately windows the projections instead, which never computes
    the inactive heads at all.)"""
    s = cfg.ssm
    nh_full = p["A_log"].shape[-1]
    spec = window.get("ssm_heads", nh_full) if window else None
    if spec is None:
        z, xr, Br, Cr, dt_raw = _projections(p, x)
        conv_x, A_log = p["conv_x"], p["A_log"]
        D_skip, y_norm, w_out = p["D_skip"], p["y_norm"], p["w_out"]
    else:
        z, xr, Br, Cr, dt_raw = _projections_windowed(
            p, x, spec, backend=window.backend)
        sl = lambda w, d: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, spec.offset, spec.win, d)
        conv_x, A_log = sl(p["conv_x"], 1), sl(p["A_log"], 0)
        D_skip, y_norm, w_out = (sl(p["D_skip"], 0), sl(p["y_norm"], 0),
                                 sl(p["w_out"], 0))
    B, S, nh, hd = xr.shape
    xr = jax.nn.silu(_causal_conv(xr.reshape(B, S, nh * hd),
                                  conv_x.reshape(s.conv_width, nh * hd))
                     ).reshape(B, S, nh, hd)
    Brc = jax.nn.silu(_causal_conv(Br, p["conv_B"]))
    Crc = jax.nn.silu(_causal_conv(Cr, p["conv_C"]))
    dt = jax.nn.softplus(dt_raw)
    A = -jnp.exp(A_log.astype(jnp.float32))
    y, _ = ssd_chunked(xr, dt, A, Brc, Crc, s.chunk)
    y = y + D_skip[:, None] * xr
    y = rms_norm(y * jax.nn.silu(z), y_norm, cfg.norm_eps)
    return jnp.einsum("bshe,hed->bsd", y, w_out)
