"""Model assembly: one composable decoder framework for all 10 assigned
architectures (dense / MoE / SSM / hybrid / audio / VLM).

* Parameters are stacked over layers (leading ``layers`` dim) and the stack is
  executed with ``lax.scan`` — small HLO, fast compiles, remat-able.
* Each leaf carries semantic axis tags (see ``repro.models.layers``), which
  drive sub-model windowing, masking, and sharding.
* One entry point per model: ``forward``, and ``loss`` on top of it — the
  training path every federated round runs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (AxisWindow, ParamBuilder, WindowMap,
                                 mlp_apply, mlp_apply_windowed, mlp_params,
                                 rms_norm, sinusoidal_positions, softmax_xent)
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


def _layer_kind(cfg: ModelConfig) -> Tuple[str, ...]:
    """Stack names in execution order."""
    if cfg.family == "ssm":
        return ("ssm_layers",)
    if cfg.moe is not None and cfg.n_dense_layers:
        return ("dense_layers", "moe_layers")
    if cfg.moe is not None:
        return ("moe_layers",)
    return ("layers",)


def _block_params(b: ParamBuilder, stack: str, cfg: ModelConfig, n: int):
    pre = stack
    b.const(f"{pre}/ln1", (cfg.d_model,), ("d_model",), 1.0, layers=n)
    if cfg.family == "ssm":
        ssm_mod.ssm_params(b, f"{pre}/ssm", cfg, layers=n)
        return
    if cfg.mla is not None:
        attn.mla_params(b, f"{pre}/attn", cfg, layers=n)
    else:
        attn.attn_params(b, f"{pre}/attn", cfg, layers=n)
    if cfg.hybrid:
        ssm_mod.ssm_params(b, f"{pre}/ssm", cfg, layers=n)
        b.const(f"{pre}/fuse_a", (cfg.d_model,), ("d_model",), 1.0, layers=n)
        b.const(f"{pre}/fuse_s", (cfg.d_model,), ("d_model",), 1.0, layers=n)
    b.const(f"{pre}/ln2", (cfg.d_model,), ("d_model",), 1.0, layers=n)
    if stack == "moe_layers":
        moe_mod.moe_params(b, f"{pre}/moe", cfg, layers=n)
    else:
        mlp_params(b, f"{pre}/mlp", cfg.d_model, cfg.d_ff, layers=n)


def build_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Tuple[Dict, Dict]:
    b = ParamBuilder(key, dtype=dtype)
    D, V = cfg.d_model, cfg.vocab
    if cfg.n_codebooks:
        b.dense("embed", (cfg.n_codebooks, V, D), ("codebooks", "vocab",
                                                   "d_model"), scale=0.02)
        b.dense("head", (cfg.n_codebooks, D, V), ("codebooks", "d_model",
                                                  "vocab"))
    else:
        b.dense("embed", (V, D), ("vocab", "d_model"), scale=0.02)
        if not cfg.tie_embeddings:
            b.dense("head", (D, V), ("d_model", "vocab"))
    if cfg.vision_stub:
        b.dense("vision_proj/w1", (cfg.vision_d, D), ("vision_d", "d_model"))
        b.dense("vision_proj/w2", (D, D), ("d_model", "d_model"))
    stacks = _layer_kind(cfg)
    for s in stacks:
        if s == "dense_layers":
            n = cfg.n_dense_layers
        elif s == "moe_layers":
            n = cfg.n_layers - cfg.n_dense_layers
        else:
            n = cfg.n_layers
        _block_params(b, s, cfg, n)
    b.const("final_norm", (D,), ("d_model",), 1.0)
    if cfg.mtp:
        b.const("mtp/ln1", (D,), ("d_model",), 1.0)
        attn.attn_params(b, "mtp/attn", cfg, layers=0) if cfg.mla is None \
            else attn.mla_params(b, "mtp/attn", cfg, layers=0)
        b.const("mtp/ln2", (D,), ("d_model",), 1.0)
        mlp_params(b, "mtp/mlp", D, cfg.d_ff, layers=0)
        b.const("mtp/final", (D,), ("d_model",), 1.0)
    return b.params, b.axes


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _attn_any(p, x, cfg, positions, window=None):
    if cfg.mla is not None:
        if window is not None and \
                window.get("kv_heads", cfg.n_kv_heads) is not None:
            # MLA has no kv_heads axis (all heads share the compressed
            # kv) — refuse rather than silently ignore the window.
            raise ValueError(
                "MLA attention has no kv_heads axis to window; window the "
                "standalone heads axis instead (windowed per-head "
                "up-projections)")
        return attn.mla_train(p, x, cfg, positions, window=window)
    return attn.gqa_train(p, x, cfg, positions, window=window)


def block_apply(p, h, cfg, stack, positions, moe_path="dropping",
                window=None):
    """One layer.  Returns (h, aux_loss).

    ``window`` (a :class:`WindowMap`, or None) routes every windowed
    matmul through the fused sub-model forward on the FULL weights — MLP
    ``d_ff`` columns, attention ``heads``/``kv_heads`` projections, MoE
    ``experts``/``moe_d_ff`` — so only the active windows are read from
    HBM and no compact W_sub copy exists."""
    aux = jnp.zeros((), jnp.float32)
    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        out = ssm_mod.ssm_train(p["ssm"], x, cfg, window=window)
        return h + out, aux
    a = _attn_any(p["attn"], x, cfg, positions, window)
    if cfg.hybrid:
        s_out = ssm_mod.ssm_train(p["ssm"], x, cfg, window=window)
        a = 0.5 * (rms_norm(a, p["fuse_a"], cfg.norm_eps)
                   + rms_norm(s_out, p["fuse_s"], cfg.norm_eps))
    h = h + constrain(a, "batch", "seq", "d_model")
    x2 = rms_norm(h, p["ln2"], cfg.norm_eps)
    if stack == "moe_layers":
        out, aux = moe_mod.moe_apply(p["moe"], x2, cfg, path=moe_path,
                                     window=window)
    else:
        spec = (window.get("d_ff", p["mlp"]["w_gate"].shape[-1])
                if window is not None else None)
        if spec is not None:
            out = mlp_apply_windowed(p["mlp"], x2, spec, cfg.act,
                                     backend=window.backend)
        else:
            out = mlp_apply(p["mlp"], x2, cfg.act)
    h = h + constrain(out, "batch", "seq", "d_model")
    return h, aux


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


@dataclass
class Model:
    cfg: ModelConfig
    moe_path: str = "dropping"
    remat: bool = True
    param_dtype: Any = jnp.float32
    #: ``unroll`` for the layer scan in :meth:`_run_stacks`.  ``True``
    #: fully inlines the loop, eliminating the per-layer carry copies and
    #: weight-stack layout round-trips of a rolled scan — the decisive
    #: lever for the fused window round on CPU (see benchmarks/run.py
    #: ``fed_round_fused``).  Default rolled: inlining perturbs XLA's dot
    #: fusion enough to move MoE outputs by ~1 ulp between program
    #: variants (see test_fused_forward's mixtral bitwise pin), and at
    #: paper scale a rolled scan keeps HLO small and compiles fast — so
    #: callers opt in per run, applying the same setting to every arm
    #: they compare.
    layer_unroll: Any = 1
    _axes_cache: Any = None

    # -- params ------------------------------------------------------------
    def init(self, key):
        params, _ = build_params(self.cfg, key, self.param_dtype)
        return params

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def axes(self):
        if self._axes_cache is None:
            box = {}

            def f(key):
                p, a = build_params(self.cfg, key)
                box["axes"] = a
                return p

            jax.eval_shape(f, jax.random.PRNGKey(0))
            object.__setattr__(self, "_axes_cache", box["axes"])
        return self._axes_cache

    # -- embedding / head ----------------------------------------------------
    def _embed(self, params, tokens, extra):
        cfg = self.cfg
        if cfg.n_codebooks:
            # tokens [B,S,CB]
            h = 0.0
            for cb in range(cfg.n_codebooks):
                h = h + params["embed"][cb][tokens[..., cb]]
        else:
            h = params["embed"][tokens]
        if cfg.pos_embed == "sinusoidal":
            B, S = tokens.shape[:2]
            pos = jnp.arange(S)[None]
            h = h + sinusoidal_positions(pos, h.shape[-1]).astype(h.dtype)
        if cfg.vision_stub and extra is not None and "patches" in extra:
            vp = extra["patches"] @ params["vision_proj"]["w1"]
            vp = jax.nn.gelu(vp) @ params["vision_proj"]["w2"]
            h = jnp.concatenate([vp.astype(h.dtype), h], axis=1)
        return constrain(h, "batch", "seq", "d_model")

    def _head(self, params, h):
        cfg = self.cfg
        if cfg.n_codebooks:
            logits = jnp.einsum("bsd,cdv->bscv", h, params["head"])
        elif cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
        else:
            logits = h @ params["head"]
        return constrain(logits, "batch", "seq", None, "vocab") \
            if cfg.n_codebooks else constrain(logits, "batch", "seq", "vocab")

    # -- stacks ---------------------------------------------------------------
    def _run_stacks(self, params, h, positions, window=None):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        for stack in _layer_kind(cfg):
            def body(carry, lp, stack=stack):
                h, aux = carry
                h, a = block_apply(lp, h, cfg, stack, positions,
                                   self.moe_path, window)
                return (h, aux + a), None

            fn = jax.checkpoint(body) if self.remat else body
            (h, aux_total), _ = jax.lax.scan(fn, (h, aux_total),
                                             params[stack],
                                             unroll=self.layer_unroll)
        return h, aux_total

    def _norm_window(self, window):
        """Normalize ``window`` to a :class:`WindowMap` (or None).

        Accepted forms: a ``WindowMap``; a ``{(axis_name, full_size):
        (offset, win) | AxisWindow}`` mapping; or the legacy single-axis
        ``(offset, win)`` tuple, meaning a bare ``d_ff`` window."""
        if window is None or isinstance(window, WindowMap):
            return window
        if isinstance(window, dict):
            return WindowMap(window)
        offset, win = window
        return WindowMap({("d_ff", self.cfg.d_ff): AxisWindow(offset, win)})

    # -- entry points ---------------------------------------------------------
    def forward(self, params, tokens, extra=None, window=None):
        """``window`` (see :meth:`_norm_window`) runs every windowed block
        — MLP ``d_ff``, attention ``heads``/``kv_heads``, MoE
        ``experts``/``moe_d_ff`` — through the fused sub-model forward on
        the full weights: the window-mode training path without compact
        extraction."""
        cfg = self.cfg
        window = self._norm_window(window)
        h = self._embed(params, tokens, extra)
        B, S = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        h, aux = self._run_stacks(params, h, positions, window=window)
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return self._head(params, h), aux, h

    def loss(self, params, batch, window=None):
        """batch: tokens [B,S] (or [B,S,CB]); optional patches, mask.
        ``window``: see :meth:`forward` (threaded to the MTP block too)."""
        cfg = self.cfg
        window = self._norm_window(window)
        tokens = batch["tokens"]
        logits, aux, h = self.forward(params, tokens, batch, window=window)
        P = cfg.vision_patches if (cfg.vision_stub and "patches" in batch) \
            else 0
        if P:
            logits = logits[:, P:]
        if cfg.n_codebooks:
            lm = softmax_xent(logits[:, :-1].reshape(-1, cfg.vocab),
                              tokens[:, 1:].reshape(-1))
        else:
            lm = softmax_xent(logits[:, :-1], tokens[:, 1:])
        total = lm + aux
        metrics = {"lm_loss": lm, "aux_loss": aux}
        if cfg.mtp and not cfg.n_codebooks:
            hp = h[:, P:] if P else h
            B, S = tokens.shape[:2]
            positions = jnp.broadcast_to(jnp.arange(hp.shape[1]),
                                         (B, hp.shape[1]))
            hmtp, _ = block_apply(params["mtp"], hp, cfg, "layers",
                                  positions, moe_path=self.moe_path,
                                  window=window)
            hmtp = rms_norm(hmtp, params["mtp"]["final"], cfg.norm_eps)
            mtp_logits = self._head(params, hmtp)
            mtp = softmax_xent(mtp_logits[:, :-2], tokens[:, 2:])
            total = total + 0.3 * mtp
            metrics["mtp_loss"] = mtp
        metrics["loss"] = total
        return total, metrics


def build_model(cfg: ModelConfig, **kw) -> Model:
    return Model(cfg, **kw)
