"""Shared layer primitives + axis-tagged parameter construction.

Every parameter is created through :class:`ParamBuilder` with a tuple of
*semantic axis names* (one per array dim).  The resulting axis-tag tree is the
single source of truth consumed by

  * ``repro.core.extract``  — sub-model window extraction / scatter,
  * ``repro.sharding.policy`` — mesh PartitionSpecs,
  * ``repro.core.masking``  — dense structured masks.

Axis names used across the zoo::

  layers vocab d_model d_ff heads kv_heads head_dim experts router_experts
  moe_d_ff ssm_heads ssm_head_dim ssm_state conv_w mla_q_rank mla_kv_rank
  rope_dim v_head_dim codebooks vision_d none
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AxisWindow(NamedTuple):
    """Active window of ONE windowed semantic axis, in axis units.

    ``offset`` may be traced (per-round), ``win`` is static (SPMD shapes).
    ``mult`` is a static alignment certificate: every offset the window
    scheme can produce is a multiple of it (``0`` means the offset is
    always 0; ``1`` — the conservative default — promises nothing).  Sites
    that flatten the axis (head windows become column windows of width
    ``win * head_dim``) scale it via :meth:`aligned` to decide whether a
    *traced* offset may take the fused Pallas arm of
    ``dispatch.rolling_matmul``."""

    offset: Any
    win: int
    mult: int = 1

    def aligned(self, block: int, scale: int = 1) -> bool:
        """True when every producible offset (scaled by ``scale``) provably
        lands on a ``block`` boundary — the ``assume_aligned`` contract."""
        m = self.mult * scale
        return True if self.mult == 0 else (m % block == 0)


class WindowMap:
    """Per-axis windows for the fused multi-axis forward.

    Maps ``(axis_name, full_dim_size)`` — the same :data:`AxisKey` the
    window scheme uses — to an :class:`AxisWindow`, plus the kernel-dispatch
    ``backend`` shared by every windowed matmul.  Keyed by *(name, size)*
    rather than name alone because one semantic axis can appear at several
    sizes (MoE ``moe_d_ff``: per-expert width vs ``n_shared * width``), each
    with its own window plan.  Model code resolves windows from the actual
    weight shapes (``window.get(name, w.shape[d])``), mirroring how
    ``core.extract`` matches windowed dims."""

    SUPPORTED = ("d_ff", "heads", "kv_heads", "experts", "moe_d_ff",
                 "ssm_heads")

    def __init__(self, windows, backend: Optional[str] = None):
        self.windows = {}
        for key, spec in dict(windows).items():
            name, size = key
            if name not in self.SUPPORTED:
                raise ValueError(
                    f"axis {name!r} has no window-aware forward; fused "
                    f"windows support {self.SUPPORTED}")
            if not isinstance(spec, AxisWindow):
                spec = AxisWindow(*spec)
            self.windows[(name, int(size))] = spec
        self.backend = backend

    def get(self, name: str, size) -> Optional[AxisWindow]:
        """Window for axis ``name`` at full size ``size`` (None = no
        window: the site runs its plain full-width path)."""
        return self.windows.get((name, int(size)))


# ---------------------------------------------------------------------------
# Axis-tagged parameter building
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Collects a params pytree and a parallel axis-tag pytree."""

    def __init__(self, key: jax.Array, dtype=jnp.float32):
        self._key = key
        self.dtype = dtype
        self.params: Dict = {}
        self.axes: Dict = {}

    def _next(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _put(self, path: str, value, axes: Tuple[str, ...]):
        assert value.ndim == len(axes), (path, value.shape, axes)
        parts = path.split("/")
        p, a = self.params, self.axes
        for q in parts[:-1]:
            p = p.setdefault(q, {})
            a = a.setdefault(q, {})
        assert parts[-1] not in p, f"duplicate param {path}"
        p[parts[-1]] = value
        a[parts[-1]] = axes

    def dense(self, path, shape, axes, scale=None, layers=0):
        """Normal(0, scale) weight.  ``layers`` prepends a stacked-layer dim."""
        if scale is None:
            fan_in = int(np.prod([s for s, ax in zip(shape, axes)
                                  if ax not in ("heads", "kv_heads")][:-1]) or shape[0])
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        if layers:
            shape = (layers,) + tuple(shape)
            axes = ("layers",) + tuple(axes)
        w = jax.random.normal(self._next(), shape, self.dtype) * scale
        self._put(path, w, axes)

    def const(self, path, shape, axes, value=0.0, layers=0):
        if layers:
            shape = (layers,) + tuple(shape)
            axes = ("layers",) + tuple(axes)
        self._put(path, jnp.full(shape, value, self.dtype), axes)

    def custom(self, path, value, axes, layers_dim=False):
        axes = (("layers",) + tuple(axes)) if layers_dim else tuple(axes)
        self._put(path, value.astype(self.dtype), axes)


def tree_paths(tree, prefix=""):
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            yield from tree_paths(v, p)
        else:
            yield p, v


# ---------------------------------------------------------------------------
# Norms / activations / positions
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def act_fn(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_get_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor ``0.1 * mscale * ln(factor) + 1``
    (1 for a factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim, theta, beta_fast, beta_slow, original_max):
    """``(low, high)`` rope-frequency indices between which YaRN blends
    interpolated into extrapolated frequencies: the index at which a
    frequency turns ``beta`` times over the original context,
    ``d(beta) = dim * ln(original_max / (2 pi beta)) / (2 ln theta)``,
    floored for ``beta_fast`` and ceiled for ``beta_slow``."""
    def d(beta):
        return (dim * math.log(original_max / (beta * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(d(beta_fast)), 0),
            min(math.ceil(d(beta_slow)), dim - 1))


def yarn_freqs(head_dim, theta, factor, beta_fast, beta_slow, original_max):
    """DeepSeek-V2's YaRN inverse frequencies: the plain ones (``extra``)
    below the correction range, ``extra / factor`` above it, and a linear
    blend across it."""
    extra = rope_freqs(head_dim, theta)
    low, high = yarn_correction_range(head_dim, theta, beta_fast, beta_slow,
                                      original_max)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope(x, positions, theta, freqs=None, scale=1.0):
    """x: [..., S, H, hd]; positions: [..., S] (broadcastable).  ``freqs``
    ([hd/2], default the plain ``rope_freqs``) and ``scale`` (cos/sin
    factor) carry a rope scaling such as YaRN."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(angles)[..., None, :]                 # [..., S, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d_model):
    """[..., S] int -> [..., S, D] float."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------


def mlp_params(b: ParamBuilder, prefix, d_model, d_ff, layers=0,
               ff_axis="d_ff"):
    b.dense(f"{prefix}/w_gate", (d_model, d_ff), ("d_model", ff_axis),
            layers=layers)
    b.dense(f"{prefix}/w_up", (d_model, d_ff), ("d_model", ff_axis),
            layers=layers)
    b.dense(f"{prefix}/w_down", (d_ff, d_model), (ff_axis, "d_model"),
            layers=layers)


def mlp_apply(p, x, act="silu"):
    g = act_fn(act)(x @ p["w_gate"])
    return (g * (x @ p["w_up"])) @ p["w_down"]


def mlp_apply_rolling(p, x, offset, win, act="silu", backend=None,
                      assume_aligned=False):
    """Window-mode gated MLP on FULL weights reading only the active d_ff
    window: equivalent to ``mlp_apply`` on the extracted sub-model, but the
    window selection is fused into the matmul (``dispatch.rolling_matmul``
    scalar-prefetch offset on TPU) instead of materializing W_sub copies —
    the inactive columns never leave HBM.

    p: full-shaped mlp params; offset: int32 (align-multiple); win: static.
    ``assume_aligned=True`` lets *traced* offsets take the fused arm — only
    set it when the window scheme aligns offsets to the 128-lane block.

    The gate/up pair shares one x and one window, so it routes through the
    multi-step arm (``dispatch.rolling_matmul_multi``): one Pallas call for
    both matmuls (the step grid dimension overlaps step t+1's W-column DMA
    with step t's compute), and on the jnp arm a literal loop of the
    single-weight oracle — bitwise identical to two separate calls.
    """
    from repro.kernels.dispatch import \
        rolling_matmul_multi  # lazy: no import cycle
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    gy, u = rolling_matmul_multi(x2, (p["w_gate"], p["w_up"]), offset, win,
                                 backend=backend,
                                 assume_aligned=assume_aligned)
    g = act_fn(act)(gy)
    w_down = jax.lax.dynamic_slice_in_dim(p["w_down"], offset, win, axis=0)
    out = (g * u) @ w_down
    return out.reshape(*lead, out.shape[-1])


def mlp_apply_windowed(p, x, spec: AxisWindow, act="silu", backend=None):
    """:func:`mlp_apply_rolling` driven by an :class:`AxisWindow` spec (the
    alignment certificate decides the traced-offset Pallas arm)."""
    return mlp_apply_rolling(p, x, spec.offset, spec.win, act,
                             backend=backend,
                             assume_aligned=spec.aligned(min(128, spec.win)))


def head_proj(x, w, spec, backend=None):
    """``x [..., D] @ w [D, H, hd]`` restricted to the contiguous head
    window ``spec`` (an :class:`AxisWindow` in head units) —
    ``dispatch.rolling_matmul`` on the head-flattened ``[D, H*hd]`` layout,
    so the inactive heads' columns are never read from HBM and the custom
    VJP scatter-adds ``dW`` back into the full layout (exact zeros outside
    the window).  Shared by GQA q/k/v (``models.attention``), MLA's
    per-head up-projections, and the SSM head projections
    (``models.ssm``)."""
    if spec is None:
        return jnp.einsum("...d,dhe->...he", x, w)
    from repro.kernels.dispatch import rolling_matmul  # lazy: no import cycle
    D, H, hd = w.shape
    lead = x.shape[:-1]
    win = spec.win * hd
    y = rolling_matmul(x.reshape(-1, D), w.reshape(D, H * hd),
                       spec.offset * hd, win, backend=backend,
                       assume_aligned=spec.aligned(min(128, win), hd))
    return y.reshape(*lead, spec.win, hd)


# ---------------------------------------------------------------------------
# Cross-entropy (vocab possibly sharded on `model`)
# ---------------------------------------------------------------------------


def softmax_xent(logits, labels, mask=None):
    """logits [..., V] f32-upcast stable xent; labels int [...]."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - picked
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)
