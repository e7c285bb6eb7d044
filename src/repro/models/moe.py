"""Mixture-of-Experts: top-k routing, then one of two dispatch paths.

* ``dense``    — every expert processes every token, outputs weighted by the
                 router.  Exact and dropless; used for reduced/smoke
                 configs, for a share (below) and as the test oracle.
* ``dropping`` — tokens are routed via ``lax.sort`` into per-expert
                 capacity buckets ([E, C, D] batched matmuls, MXU friendly,
                 expert dim shardable), tokens over capacity are dropped
                 (standard Switch-style).  FLOPs ≈ active-expert FLOPs x
                 capacity_factor.

Routing styles: ``softmax`` (Mixtral: softmax over the top-k logits;
DeepSeek-V2 with ``norm_topk_prob=False``: the top-k of the softmax over
every expert, not renormalised) and ``sigmoid`` (DeepSeek-V3: sigmoid
scores, top-k, weights normalized over the selected k).  The balance loss
is Switch-style (top-1 fractions) or DeepSeek-V2's sequence-wise one.

A *share* (``MoEConfig.router_experts`` wider than ``n_experts``) holds
experts ``0 .. n_experts-1`` of a wider layer whose router it keeps whole:
routing runs over every expert, and a choice of an expert held elsewhere
adds nothing here.  A share always runs ``dense``.  Scopes: ``model.moe``
(the layer), ``model.moe.dispatch`` (routing, the balance loss, the dense
path's combine), ``model.moe.experts`` (the expert matmuls).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.models.layers import (ParamBuilder, act_fn, mlp_apply_windowed)
from repro.sharding.ctx import constrain

PATHS = ("dense", "dropping")
AUX_LOSSES = ("switch", "seq")


def moe_params(b: ParamBuilder, prefix, cfg, layers=0):
    mo, D = cfg.moe, cfg.d_model
    E, F = mo.n_experts, mo.d_ff
    # a share's router spans the whole layer under an axis no window takes:
    # an experts window narrows the held experts, never the routing
    b.dense(f"{prefix}/router", (D, mo.n_router),
            ("d_model", "router_experts" if mo.is_share else "experts"),
            layers=layers)
    for w, sh, ax in (("w_gate", (E, D, F), ("experts", "d_model", "moe_d_ff")),
                      ("w_up", (E, D, F), ("experts", "d_model", "moe_d_ff")),
                      ("w_down", (E, F, D), ("experts", "moe_d_ff", "d_model"))):
        b.dense(f"{prefix}/{w}", sh, ax, layers=layers)
    if mo.n_shared:
        Fs = mo.n_shared * F
        b.dense(f"{prefix}/shared/w_gate", (D, Fs), ("d_model", "moe_d_ff"),
                layers=layers)
        b.dense(f"{prefix}/shared/w_up", (D, Fs), ("d_model", "moe_d_ff"),
                layers=layers)
        b.dense(f"{prefix}/shared/w_down", (Fs, D), ("moe_d_ff", "d_model"),
                layers=layers)


def _route(router, x, cfg):
    """x [T,D] -> (weights [T,k], idx [T,k], logits [T,E] f32).  The
    logits are taken at the highest matmul precision, so that a one-pass
    bfloat16 rounding cannot flip near-tied top-k choices."""
    mo = cfg.moe
    E = router.shape[-1]               # may be a sub-model window of experts
    k = min(mo.top_k, E)
    logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST
                     ).astype(jnp.float32)              # [T,E]
    if mo.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, k)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    elif mo.norm_topk_prob:
        w, idx = jax.lax.top_k(logits, k)
        w = jax.nn.softmax(w, axis=-1)
    else:
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return w.astype(x.dtype), idx, logits


def _balance_loss(logits, idx, mo, n_seqs):
    """``switch``: E * sum_e f_e * p_e, f_e the share of tokens whose first
    choice is e.  ``seq`` (DeepSeek-V2): per sequence of S tokens,
    sum_i f_i * P_i with f_i = count_i / (S k / E) over all k choices and
    P_i the mean softmax score, averaged over the sequences."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    if mo.aux_loss == "seq":
        T, k = idx.shape
        S = T // n_seqs
        counts = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)
        f = counts.reshape(n_seqs, S, E).sum(1) / (S * k / E)
        P = probs.reshape(n_seqs, S, E).mean(1)
        return jnp.mean(jnp.sum(f * P, axis=-1))
    frac = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    return E * jnp.sum(frac * probs.mean(0))


def _held(idx, w, lo, win):
    """Each choice as an index into the held experts ``[lo, lo + win)``
    (``win`` for a choice outside them) and its weight (0 outside)."""
    e = idx - lo
    inside = (e >= 0) & (e < win)
    return jnp.where(inside, e, win), jnp.where(inside, w, 0.0)


def _expert_ffn(wg, wu, wd, x, act, fspec=None, backend=None):
    """Per-expert gated MLPs.  ``fspec`` (an ``AxisWindow`` over the
    per-expert hidden width ``moe_d_ff``) routes every expert through the
    fused rolling-window MLP on the FULL weights — only the active window's
    columns are read, grads outside it are exactly zero."""
    if fspec is None:
        g = act_fn(act)(jnp.einsum("ecd,edf->ecf", x, wg))
        u = jnp.einsum("ecd,edf->ecf", x, wu)
        return jnp.einsum("ecf,efd->ecd", g * u, wd)
    return jax.vmap(lambda wg_e, wu_e, wd_e, x_e: mlp_apply_windowed(
        {"w_gate": wg_e, "w_up": wu_e, "w_down": wd_e}, x_e, fspec, act,
        backend=backend))(wg, wu, wd, x)


def _ff_window(wg, wu, wd, fspec):
    """The expert stacks cut to the ``moe_d_ff`` window (a dynamic slice:
    the gradient outside it is exactly zero)."""
    if fspec is None:
        return wg, wu, wd
    sl = jax.lax.dynamic_slice_in_dim
    return (sl(wg, fspec.offset, fspec.win, 2),
            sl(wu, fspec.offset, fspec.win, 2),
            sl(wd, fspec.offset, fspec.win, 1))


@tracing.scoped(tracing.MOE)
def moe_apply(p, x, cfg, path="dropping", window=None):
    """x [B,S,D] -> (out [B,S,D], aux_loss scalar).  ``path`` is one of
    :data:`PATHS`; a share runs ``dense`` whatever ``path`` says, since
    ``dropping`` would drop its tokens.

    ``window`` (a ``WindowMap``, or None) applies the fused sub-model
    windows on the FULL weights.  An ``experts`` window cuts the expert
    stacks to the active contiguous expert range.  On a whole layer it also
    slices the router columns (routing then runs over that sub-zoo, exactly
    like the extracted compact model); on a share the routing stays over
    every expert and a choice outside the window adds nothing, as a choice
    of an expert on another chip does.  A ``moe_d_ff`` window routes the
    per-expert and shared MLPs through their window."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    mo = cfg.moe
    if path not in PATHS:
        raise ValueError(f"unknown MoE path {path!r}; expected one of {PATHS}")
    if mo.aux_loss not in AUX_LOSSES:
        raise ValueError(f"unknown MoE aux_loss {mo.aux_loss!r}; expected "
                         f"one of {AUX_LOSSES}")
    router, wg, wu, wd = p["router"], p["w_gate"], p["w_up"], p["w_down"]
    espec = window.get("experts", wg.shape[0]) if window else None
    if mo.is_share and wg.shape[0] != mo.n_experts and espec is None:
        raise ValueError(
            f"an expert stack of {wg.shape[0]} experts on a share of "
            f"{mo.n_experts} of {mo.n_router}: an extracted experts window "
            "carries no expert ids, so a share trains through the fused "
            "round (fused_forward 'auto' or 'on'), not extract")
    if mo.is_share:
        path = "dense"
    lo, win = 0, wg.shape[0]
    if espec is not None:
        if mo.is_share:
            lo = espec.offset
        else:
            router = jax.lax.dynamic_slice_in_dim(router, espec.offset,
                                                  espec.win, 1)
        win = espec.win
        wg = jax.lax.dynamic_slice_in_dim(wg, espec.offset, espec.win, 0)
        wu = jax.lax.dynamic_slice_in_dim(wu, espec.offset, espec.win, 0)
        wd = jax.lax.dynamic_slice_in_dim(wd, espec.offset, espec.win, 0)
    fspec = window.get("moe_d_ff", wg.shape[-1]) if window else None
    backend = window.backend if window else None
    with jax.named_scope(tracing.MOE_DISPATCH):
        w, idx, logits = _route(router, xt, cfg)
        aux = _balance_loss(logits, idx, mo, B)
    k = idx.shape[-1]
    T = xt.shape[0]

    # a share's choices outside the window go to a column past it
    e, w = _held(idx, w, lo, win) if mo.is_share else (idx, w)
    if path == "dense":
        with jax.named_scope(tracing.MOE_EXPERTS):
            wg, wu, wd = _ff_window(wg, wu, wd, fspec)
            g = act_fn(cfg.act)(jnp.einsum("td,edf->tef", xt, wg))
            u = jnp.einsum("td,edf->tef", xt, wu)
            y_all = jnp.einsum("tef,efd->ted", g * u, wd)       # [T,E,D]
        with jax.named_scope(tracing.MOE_DISPATCH):
            gate = jnp.zeros((T, win + mo.is_share), xt.dtype)
            gate = jax.vmap(lambda gt, it, wt: gt.at[it].add(wt))(gate, e, w)
            out = jnp.einsum("ted,te->td", y_all, gate[:, :win])
    else:
        E = win
        C = max(int(T * k / E * mo.capacity_factor), 1)
        C = min(C, T)
        # flatten (token, expert-choice) pairs and sort by expert id
        flat_e = idx.reshape(-1)                       # [T*k]
        flat_t = jnp.repeat(jnp.arange(T), k)
        flat_w = w.reshape(-1)
        order = jnp.argsort(flat_e)
        se, st, sw = flat_e[order], flat_t[order], flat_w[order]
        # rank within expert = position - start offset of that expert
        counts = jnp.bincount(se, length=E)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(T * k) - starts[se]
        keep = rank < C
        slot = se * C + jnp.where(keep, rank, 0)       # [T*k] in [0, E*C)
        # dispatch: gather token rows into [E*C, D]
        xin = jnp.zeros((E * C, D), xt.dtype).at[slot].set(
            jnp.where(keep[:, None], xt[st], 0.0))
        # pin dispatch/combine to expert-parallel layout so the partitioner
        # routes tokens with one all-to-all-ish exchange instead of
        # re-gathering the token matrix per expert shard
        xin = constrain(xin.reshape(E, C, D), "experts", None, None)
        with jax.named_scope(tracing.MOE_EXPERTS):
            y = _expert_ffn(wg, wu, wd, xin, cfg.act, fspec=fspec,
                            backend=backend)
        y = constrain(y, "experts", None, None)
        # combine: weighted scatter-add back to tokens
        y_flat = y.reshape(E * C, D)[slot]             # [T*k, D]
        contrib = jnp.where(keep[:, None], y_flat * sw[:, None], 0.0)
        out = jnp.zeros((T, D), y_flat.dtype).at[st].add(contrib)

    if mo.n_shared:
        sp = p["shared"]
        sspec = (window.get("moe_d_ff", sp["w_gate"].shape[-1])
                 if window else None)
        if sspec is not None:  # shared width n_shared*F windows separately
            out = out + mlp_apply_windowed(sp, xt, sspec, cfg.act,
                                           backend=backend)
        else:
            g = act_fn(cfg.act)(xt @ sp["w_gate"])
            out = out + (g * (xt @ sp["w_up"])) @ sp["w_down"]
    return out.reshape(B, S, D).astype(x.dtype), aux * mo.aux_loss_weight
