"""Distributed sub-model training rounds — Algorithms 1 & 2 of the paper.

Two executable forms of one algorithm family, one code path each:

* **window mode** (`WindowFedAvg`) — the production TPU path.  Clients
  live on the mesh `data` axis; each round every client group
  extracts a *compact* sub-model (contiguous windows per semantic axis),
  runs K local optimizer steps (`lax.scan`), and the server applies the
  fill-in average in delta form (shared-window scatter or sequential
  scatter-add) followed by the optional l2 projection.  The whole round is
  one jitted SPMD program — what the chip benchmark times.

* **mask mode** (`MaskFedAvg`) — the paper's literal formulation with
  dense masks (supports unstructured Bernoulli masks of Algorithm 1 and
  per-client heterogeneous capacities).  Used for the faithful experiments
  and as the oracle for property tests (window mode == mask mode when the
  masks are the window indicators).

Both rounds share the same internal phases — client offsets/masks →
``_client_phase`` (extract → K-step scan → delta) → aggregation → server
step — each under its ``jax.named_scope`` (``fed.offsets``,
``fed.client_phase``, ``fed.aggregate``, ``fed.server_step``; see
:mod:`repro.tracing`), so a device trace splits the round by phase.  Both
take a pluggable :class:`repro.optim.client.ClientOpt` for the local steps
and an optional stateful server optimizer (`round_with_server_opt`) that
treats the mean delta as a pseudo-gradient.

Construct rounds through :func:`repro.api.fed_round` (the public facade);
``make_window_fed_round`` / ``make_mask_fed_round`` remain as deprecated
shims.  Batch layout: every batch leaf is [K, C, ...] — local-step major,
then client.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace as _replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.configs.base import SubmodelConfig
from repro.core import extract as ex
from repro.core import submodel as sm
from repro.core.masking import WindowScheme, collect_axis_dims, make_scheme
from repro.kernels import dispatch
from repro.optim.client import ClientOpt, client_sgd, resolve_client_opt
from repro.sharding import spmd
from repro.sharding.policy import constrain_tree

MESH_AGGS = ("gather", "psum")

_SHARED_WINDOW_SCHEMES = ("rolling", "static", "importance")


def resolve_shared_window(scfg: SubmodelConfig) -> bool:
    """Resolve ``SubmodelConfig.shared_window`` once, at construction.

    ``None`` (the default) means "derive from the scheme": rolling/static/
    importance without stagger put every client on the SAME window, so the
    aggregation can average sub-model deltas first and scatter once.  An
    explicit ``False`` forces the per-client scatter path (the old
    ``REPRO_NO_SHARED_WINDOW`` baseline knob); an explicit ``True`` is only
    valid when the scheme actually shares windows.
    """
    derived = (scfg.scheme in _SHARED_WINDOW_SCHEMES and not scfg.stagger)
    if scfg.shared_window is None:
        return derived
    if scfg.shared_window and not derived:
        raise ValueError(
            f"shared_window=True requires a shared-window scheme "
            f"({'/'.join(_SHARED_WINDOW_SCHEMES)}, stagger=False); got "
            f"scheme={scfg.scheme!r} stagger={scfg.stagger}")
    return scfg.shared_window


# ---------------------------------------------------------------------------
# Window (compact) mode — production path
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CapacityBucket:
    """One width class of a heterogeneous-capacity round.

    Clients whose capacity fraction beta rounds to the same window plan
    share a bucket: ``idx`` are their lanes in the round's client axis
    (batch dim 1), and ``fed`` is a homogeneous :class:`WindowFedAvg`
    clone at ``scfg.capacity = beta`` with ``clients_per_round =
    len(idx)``.  The batched-offset kernels take ONE static window width
    per call, so the bucket loop — not a per-row width — is how
    heterogeneous widths ride the existing fused/extract client phases,
    and each bucket's computation is bitwise-identical to an
    independently built homogeneous round at that beta (pinned in
    ``tests/test_hetero.py``)."""

    beta: float
    idx: Any            # tuple of C_b client lanes, ascending
    fed: Any            # homogeneous WindowFedAvg at this beta


@dataclass
class WindowFedAvg:
    loss_fn: Callable                   # loss_fn(params, batch) -> (loss, aux)
    scfg: SubmodelConfig
    abstract: Any                       # full-model ShapeDtypeStruct tree
    axes_tree: Any
    scheme: WindowScheme
    spmd_axis: Any = None               # mesh axis pinning the client vmap
    # Mesh scale-out: with a Mesh attached the round runs under shard_map —
    # the per-client leading axis (offsets, batch streams, deltas) is split
    # over the `spmd_axis` mesh axis, each shard runs the client phase on
    # its own clients, and the aggregation crosses shards per `mesh_agg`:
    #   "gather" (default) — all_gather the per-client deltas (byte-moving,
    #     no arithmetic) and replay the exact single-device aggregation, so
    #     the sharded round is bitwise-equal to the mesh=None round;
    #   "psum"   — shard-local f32 scatter-add partials psum'd over the
    #     client axis (O(model) comm instead of O(C·sub); fp-reassociated,
    #     so equal to the single-device round only to roundoff).
    mesh: Any = None                    # jax.sharding.Mesh (None = vmap only)
    mesh_agg: str = "gather"            # gather (exact) | psum (scalable)
    kernel_backend: Optional[str] = None  # pallas | jnp | auto (None = env)
    client_opt: Optional[ClientOpt] = None  # None = the paper's plain SGD
    server_opt: Any = None              # ServerOpt used by Trainer (optional)
    shared_window: Optional[bool] = None  # None = resolve from scfg
    # Fused multi-axis window forward: clients skip extract/scatter
    # entirely and run K steps on the FULL tree through a window-aware
    # model forward (loss_fn(params, batch, window={axis: (offset, win)})).
    # "auto" takes the fused arm whenever a windowed loss is attached and
    # every properly-windowed axis has a fused forward (d_ff, GQA-coupled
    # heads/kv_heads, MLA standalone heads, experts, moe_d_ff, ssm_heads).
    # Shared-window schemes close one WindowMap over the client vmap;
    # per-client schemes (staggered rolling / random / staggered
    # importance) vmap clients over their own WindowMaps — the batched-
    # offset rolling-matmul arm (kernels.rolling_matmul_batched).
    windowed_loss_fn: Optional[Callable] = None
    fused_forward: Any = "auto"         # "auto" | True/"on" | False/"off"
    # Heterogeneous per-client capacities: a [clients_per_round] vector of
    # window fractions beta_c in (0, 1].  None (the default) keeps the
    # homogeneous round (every client at scfg.capacity).  When set, the
    # round buckets clients by beta (see CapacityBucket) and runs one
    # fused/extract client phase per bucket, accumulating the f32
    # scatter-add delta sum in bucket order before the single /C mean —
    # so a heterogeneous round composes bitwise from per-bucket
    # homogeneous rounds.
    capacities: Any = None
    # Uplink-delta compression for the fused aggregation path: "bf16"
    # simulates clients shipping their round delta in bfloat16 (half the
    # uplink bytes), decompressed to f32 at the server BEFORE the client
    # mean — f32 accumulation, one final rounding into the param dtype, per
    # the PR 3 fill-in pipeline.  None (default) keeps the exact f32 uplink
    # and with it the fused == extract bitwise guarantee; "bf16" trades
    # that for comm volume (agreement to bf16 rounding of the deltas).
    uplink_compression: Optional[str] = None

    def __post_init__(self):
        self.hetero = None
        if self.uplink_compression not in (None, "bf16"):
            raise ValueError(
                "uplink_compression must be None (exact f32 uplink) or "
                f"'bf16'; got {self.uplink_compression!r}")
        if self.capacities is not None:
            self._resolve_hetero()
        if self.shared_window is None:
            self.shared_window = resolve_shared_window(self.scfg)
        self.client_opt = resolve_client_opt(self.client_opt)
        self.use_fused = self._resolve_fused()

    def _resolve_hetero(self):
        """Validate ``capacities`` and build the width buckets (once, at
        construction — window sizes are static SPMD shapes)."""
        c = self.scfg
        caps = np.asarray(self.capacities, np.float64).reshape(-1)
        if caps.shape[0] != c.clients_per_round:
            raise ValueError(
                f"capacities must have length clients_per_round="
                f"{c.clients_per_round}; got {caps.shape[0]}")
        if np.any(caps <= 0.0) or np.any(caps > 1.0):
            raise ValueError(
                "window-mode capacities are per-client window fractions "
                f"in (0, 1]; got {np.asarray(self.capacities)}")
        if self.mesh is not None:
            raise ValueError(
                "capacities= (heterogeneous windows) and mesh= are "
                "mutually exclusive: bucket batch slices break the static "
                "per-shard client count; drive heterogeneous fleets "
                "through AsyncTrainer/FleetSimulator instead")
        if c.scheme == "full":
            raise ValueError(
                "capacities have no effect under scheme='full' (every "
                "client trains the full model); drop capacities= or pick "
                "a windowed scheme")
        # construction-time host numpy, not a device sync
        # repro-lint: disable=host-sync
        self.capacities = tuple(float(b) for b in caps)
        if np.all(caps == c.capacity):
            return  # uniform at the configured beta: plain homogeneous round
        if self.shared_window or c.shared_window:
            raise ValueError(
                "shared_window=True is incompatible with heterogeneous "
                "capacities (clients train different window *sizes*, so "
                "no single window is shared); leave shared_window unset")
        self.shared_window = False  # per-client scatter aggregation only
        dims = collect_axis_dims(self.abstract, self.axes_tree)
        buckets = []
        for beta in sorted(set(self.capacities), reverse=True):
            idx = tuple(int(i) for i in np.nonzero(caps == beta)[0])
            # repro-lint: disable=host-sync
            bscfg = _replace(c, capacity=float(beta),
                             clients_per_round=len(idx),
                             shared_window=False)
            # beta = 1.0 buckets window nothing — fused_forward="on" would
            # (rightly) refuse, so they resolve with "auto" instead.
            bfed = _replace(
                self, scfg=bscfg, scheme=make_scheme(bscfg, dims),
                shared_window=False, capacities=None,
                fused_forward=(self.fused_forward if beta < 1.0 else "auto"))
            # repro-lint: disable=host-sync
            buckets.append(CapacityBucket(beta=float(beta), idx=idx,
                                          fed=bfed))
        self.hetero = buckets

    def _resolve_fused(self) -> bool:
        want = self.fused_forward
        if want in (False, "off"):
            return False
        if want not in (True, "on", "auto", None):
            raise ValueError(
                f"fused_forward must be 'auto', 'on'/True or 'off'/False; "
                f"got {want!r}")
        # axes the fused window-aware forward can express; everything else
        # falls back to extract/scatter (lazy import, like _fused_window)
        from repro.models.layers import WindowMap
        supported = WindowMap.SUPPORTED
        # proper windows only (size < full dim): improper ones are no-ops
        # for extract and must be no-ops for the fused forward too.
        proper = {k: w for k, w in self.scheme.sizes.items() if w < k[1]}
        reasons = []
        if self.windowed_loss_fn is None:
            reasons.append("the model exposes no windowed forward "
                           "(loss(params, batch, window=...))")
        if not proper:
            reasons.append("no axis is actually windowed (nothing to fuse)")
        unsupported = [k for k in proper if k[0] not in supported]
        if unsupported:
            reasons.append(f"axes {sorted(unsupported)} have no fused "
                           f"window-aware forward (supported: "
                           f"{supported})")
        # GQA coupling: on models with a kv_heads axis (GQA attention), a
        # heads window must be derived from kv_heads so the windowed q
        # heads keep grouping onto the windowed kv heads.  Models without
        # kv_heads dims (MLA: per-head up-projections from a shared
        # compressed kv) window heads standalone.
        uncoupled = [k for k in proper
                     if k[0] == "heads" and k not in self.scheme.derived]
        if uncoupled and any(
                name == "kv_heads"
                for (name, _) in collect_axis_dims(self.abstract,
                                                   self.axes_tree)):
            reasons.append(f"heads windows {sorted(uncoupled)} are not "
                           "GQA-derived from a kv_heads window")
        if reasons:
            if want in (True, "on"):
                raise ValueError("fused_forward=True requires: "
                                 + "; ".join(reasons))
            return False
        # Per-axis static alignment certificates: a traced offset may take
        # the fused Pallas arm only when every offset the scheme can
        # produce lands on the kernel block boundary (the exact-tail grid
        # entry breaks this when (n - w) % block != 0) — threaded through
        # AxisWindow.mult and checked per use site (head windows scale by
        # head_dim before the check).
        self._fused_keys = proper
        self._fused_mults = {k: self.scheme.grid_multiple(k) for k in proper}
        return True

    def _fused_window(self, off_scalars):
        """The per-axis WindowMap for one client's scalar offsets."""
        from repro.models.layers import AxisWindow, WindowMap
        return WindowMap(
            {k: AxisWindow(off_scalars[k], w, self._fused_mults[k])
             for k, w in self._fused_keys.items()},
            backend=self.kernel_backend)

    def _vmap(self, f, **kw):
        # under shard_map (mesh path) the client axis is shard-local and
        # manual — annotating the vmap with a mesh axis name would rebind it
        if self.spmd_axis is not None and self.mesh is None:
            return jax.vmap(f, spmd_axis_name=self.spmd_axis, **kw)
        return jax.vmap(f, **kw)

    # -- composable round phases ---------------------------------------------

    @tracing.scoped(tracing.OFFSETS)
    def _client_offsets(self, params, round_idx, rng):
        C = self.scfg.clients_per_round
        if self.hetero is not None:
            return self._hetero_offsets(params, round_idx, rng)
        if self.scfg.scheme == "importance":
            return self.scheme.importance_offsets(params, self.axes_tree, C)
        return self.scheme.offsets(rng, round_idx, C)

    # -- heterogeneous capacities: the bucket loop ----------------------------

    def _hetero_offsets(self, params, round_idx, rng):
        """Union per-axis offset vectors [C] across the width buckets.

        Each client lane carries its OWN bucket's offset draw (window
        *sizes* differ per bucket and stay static on the bucket feds);
        lanes of buckets that don't window an axis (beta = 1.0) stay 0.
        Offset draws are seed-keyed (``WindowScheme.offsets`` ignores the
        passed rng), so a bucket's slice of this union equals the draw an
        independently built homogeneous round at that beta would make."""
        C = self.scfg.clients_per_round
        out = {}
        for b in self.hetero:
            boff = b.fed._client_offsets(params, round_idx, rng)
            lanes = jnp.asarray(b.idx, jnp.int32)
            for k, v in boff.items():
                base = out.get(k, jnp.zeros((C,), jnp.int32))
                out[k] = base.at[lanes].set(v.astype(jnp.int32))
        return out

    def _hetero_delta_sum(self, params, batch, round_idx, rng):
        """Bucket-ordered f32 scatter-add sum of ALL client deltas (no
        /C), plus the [K, C] losses reassembled in client order.

        Each bucket slices its clients' batch lanes, runs its OWN
        homogeneous fused/extract client phase, and contributes its
        :meth:`_local_delta_sum` — so the total is a sum of per-bucket
        homogeneous-round delta sums, accumulated in descending-beta
        bucket order (the composition pinned bitwise in
        ``tests/test_hetero.py``)."""
        with jax.named_scope(tracing.AGGREGATE):
            acc = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)
        parts, order = [], []
        for b in self.hetero:
            with jax.named_scope(tracing.CLIENT_PHASE):
                lanes = jnp.asarray(b.idx, jnp.int32)
                bb = jax.tree_util.tree_map(
                    lambda x: jnp.take(jnp.asarray(x), lanes, axis=1), batch)
            boff = b.fed._client_offsets(params, round_idx, rng)
            bfused = b.fed.use_fused and bool(boff)
            phase = (b.fed._client_phase_fused if bfused
                     else b.fed._client_phase)
            _, delta, bl = phase(params, bb, boff)
            part = b.fed._local_delta_sum(delta, boff, bfused)
            with jax.named_scope(tracing.AGGREGATE):
                acc = jax.tree_util.tree_map(lambda a, d: a + d, acc, part)
            parts.append(bl)
            # b.idx is a static tuple of python ints, host-only
            # repro-lint: disable=host-sync
            order.append(np.asarray(b.idx))
        inv = jnp.asarray(np.argsort(np.concatenate(order)), jnp.int32)
        losses = jnp.concatenate(parts, axis=1)[:, inv]
        return acc, losses

    def _round_hetero(self, params, batch, round_idx, rng):
        """One heterogeneous-capacity round: bucket loop, then the same
        final update formula as the per-client scatter arm —
        ``w + server_lr · (Σ_c scattered delta_c) / C``."""
        c = self.scfg
        acc, losses = self._hetero_delta_sum(params, batch, round_idx, rng)
        with jax.named_scope(tracing.AGGREGATE):
            new = jax.tree_util.tree_map(
                lambda w, d: (w + c.server_lr * d / c.clients_per_round
                              ).astype(w.dtype), params, acc)
        with jax.named_scope(tracing.SERVER_STEP):
            new = sm.project_l2(new, c.proj_radius)
        return new, {"loss": losses.mean(), "client_loss": losses}

    def _hetero_phase_for(self, slots):
        """Client phase over an arbitrary lane subset of a heterogeneous
        cohort (the ``AsyncTrainer`` dispatch path).

        ``slots`` is a static tuple of client lanes; the returned
        ``phase(params, batch, offsets)`` takes batch leaves
        ``[K, m, ...]`` and cohort-sliced union offsets ``{axis: [m]}``
        (both in slot order) and returns FULL-shaped per-client f32
        deltas ``[m, ...]`` — exact zeros outside each client's window,
        extract buckets scattered per client — plus losses ``[K, m]``,
        reassembled in slot order.  Full-shaped deltas make buffered
        aggregation width-agnostic: they ride the ``*_fused`` arms'
        scan-of-adds regardless of which buckets reported."""
        slots = tuple(int(s) for s in slots)
        pos = {s: j for j, s in enumerate(slots)}
        plan = []
        for b in self.hetero:
            # static slot bookkeeping over python ints, host-only
            # repro-lint: disable=host-sync
            cols = np.asarray([pos[int(l)] for l in b.idx if int(l) in pos],
                              np.int32)
            if cols.size:
                plan.append((b, cols))

        @tracing.scoped(tracing.CLIENT_PHASE)
        def phase(params, batch, offsets):
            dparts, lparts, order = [], [], []
            for b, cols in plan:
                colsj = jnp.asarray(cols, jnp.int32)
                bb = jax.tree_util.tree_map(
                    lambda x: jnp.take(x, colsj, axis=1), batch)
                boff = {k: jnp.take(offsets[k], colsj, axis=0)
                        for k in b.fed.scheme.sizes}
                bfused = b.fed.use_fused and bool(boff)
                if bfused:
                    _, dfull, bl = b.fed._client_phase_fused(params, bb,
                                                             boff)
                else:
                    _, dsub, bl = b.fed._client_phase(params, bb, boff)
                    if boff:
                        dfull = jax.vmap(
                            lambda d, off: ex.scatter_delta(
                                d, self.abstract, self.axes_tree, off,
                                b.fed.scheme.sizes))(dsub, boff)
                    else:  # beta = 1.0: deltas are already full-shaped
                        dfull = dsub
                dparts.append(dfull)
                lparts.append(bl)
                order.append(cols)
            inv = jnp.asarray(np.argsort(np.concatenate(order)), jnp.int32)
            delta = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=0)[inv], *dparts)
            losses = jnp.concatenate(lparts, axis=1)[:, inv]
            return delta, losses

        return phase

    def _extract_clients(self, params, offsets, count=None):
        """Per-client compact sub-models, stacked on a leading C axis.

        ``count`` overrides the stacked-axis length (the shard-LOCAL client
        count under the mesh round); None keeps the global ``C``."""
        C = self.scfg.clients_per_round if count is None else count
        if offsets:
            sub0 = self._vmap(
                lambda off: ex.extract(params, self.axes_tree, off,
                                       self.scheme.sizes)
            )(offsets)
        else:  # full-model training: every client gets a replica
            sub0 = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), params)
        return constrain_tree(sub0, self.axes_tree)

    @tracing.scoped(tracing.CLIENT_PHASE)
    def _client_phase(self, params, batch, offsets):
        """extract → K local-optimizer steps (scan) → delta."""
        c = self.scfg
        # client count from the batch layout [K, C, ...]: the global C, or
        # the shard-local C/S inside the mesh round's shard_map body
        C = jax.tree_util.tree_leaves(batch)[0].shape[1]
        sub0 = self._extract_clients(params, offsets, count=C)
        grad_fn = jax.value_and_grad(self.loss_fn, has_aux=True)
        opt = self.client_opt

        def kstep(carry, mb):
            subp, ost = carry
            (loss, metrics), g = self._vmap(grad_fn)(subp, mb)
            subp, ost = opt.update(subp, g, ost, c.client_lr,
                                   backend=self.kernel_backend)
            subp = constrain_tree(subp, self.axes_tree)
            return (subp, ost), loss

        # The K-step scan stays rolled: unrolling it on top of the model's
        # inlined layer scan perturbs XLA's dot fusion enough to break the
        # bitwise fused == extract equality (~1 ulp), for no round-level win.
        (subK, _), losses = jax.lax.scan(kstep, (sub0, opt.init(sub0)), batch)
        # delta in f32: a bf16 subtraction would quantize small K-step
        # updates to 0 and starve the server pseudo-gradient.
        delta = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            subK, sub0)
        return sub0, delta, losses

    @tracing.scoped(tracing.CLIENT_PHASE)
    def _client_phase_fused(self, params, batch, offsets):
        """Fused multi-axis window client phase: K steps on the FULL tree.

        No ``extract``/``scatter_delta`` and no compact W_sub copy: the
        model's window-aware forward (``mlp_apply_rolling`` /
        ``head_proj`` through the ``dispatch.rolling_matmul`` custom VJP,
        windowed expert slices in the MoE block) reads only the active
        windows from HBM, and out-of-window coordinates of every windowed
        axis see an exactly-zero gradient, so their K-step delta is
        exactly 0.  Returns the FULL-shaped f32 delta (consumed by the
        ``*_fused`` aggregations, which slice/scatter the multi-axis
        window like the extract path does).

        Shared-window schemes close ONE WindowMap over the client vmap;
        per-client schemes (staggered rolling / random / staggered
        importance) additionally vmap the per-client offset scalars, so
        each client trains its own window — the windowed matmuls then
        lower to the batched-offset Pallas arm
        (``kernels.rolling_matmul_batched``: one grid row per client, each
        prefetching its own offset).
        """
        c = self.scfg
        # batch layout [K, C, ...]: global C, or shard-local C/S on the mesh
        C = jax.tree_util.tree_leaves(batch)[0].shape[1]
        full0 = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), params)
        full0 = constrain_tree(full0, self.axes_tree)
        wloss = self.windowed_loss_fn
        opt = self.client_opt

        if self.shared_window:
            window = self._fused_window(
                {k: offsets[k][0] for k in self._fused_keys})
            grad_fn = jax.value_and_grad(
                lambda p, mb: wloss(p, mb, window=window), has_aux=True)

            def vgrad(p, mb):
                return self._vmap(grad_fn)(p, mb)
        else:
            per_client = {k: offsets[k] for k in self._fused_keys}  # [C]

            def grad_one(p, mb, off):
                window = self._fused_window(off)
                return jax.value_and_grad(
                    lambda p, mb: wloss(p, mb, window=window),
                    has_aux=True)(p, mb)

            def vgrad(p, mb):
                return self._vmap(grad_one)(p, mb, per_client)

        def kstep(carry, mb):
            p, ost = carry
            (loss, metrics), g = vgrad(p, mb)
            p, ost = opt.update(p, g, ost, c.client_lr,
                                backend=self.kernel_backend)
            p = constrain_tree(p, self.axes_tree)
            return (p, ost), loss

        (fullK, _), losses = jax.lax.scan(kstep, (full0, opt.init(full0)),
                                          batch)
        delta_full = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            fullK, full0)
        return full0, delta_full, losses

    @tracing.scoped(tracing.AGGREGATE)
    def _apply_mean_delta(self, params, delta, offsets):
        """Plain averaging (the paper's fill-in update, delta form)."""
        c = self.scfg
        C = c.clients_per_round
        if self.shared_window and offsets:
            # Rolling/static without stagger: every client trains the SAME
            # window (Algorithm 2), so average client deltas first (one
            # sub-model-sized reduction over the client/data axis), then a
            # single in-place scatter — instead of C full-model scatters.
            off0 = {k: v[0] for k, v in offsets.items()}
            dbar = jax.tree_util.tree_map(
                lambda d: jnp.mean(d.astype(jnp.float32), axis=0), delta)
            return _scatter_update(params, dbar, self.abstract,
                                   self.axes_tree, off0, self.scheme.sizes,
                                   c.server_lr)

        def acc_step(acc, xs):
            d_c, off_c = xs
            full_d = ex.scatter_delta(d_c, self.abstract, self.axes_tree,
                                      off_c, self.scheme.sizes)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), acc, full_d)
            return constrain_tree(acc, self.axes_tree, leading=()), None

        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)
        acc, _ = jax.lax.scan(acc_step, acc0, (delta, offsets))
        return jax.tree_util.tree_map(
            lambda w, d: (w + c.server_lr * d.astype(jnp.float32) / C
                          ).astype(w.dtype), params, acc)

    def _uplink(self, tree):
        """Simulated client→server uplink of a delta tree (leaves may carry
        a leading client axis): identity under the exact f32 uplink;
        ``uplink_compression='bf16'`` rounds each delta to bfloat16 (the
        wire format, half the bytes) and immediately decompresses to f32 so
        every downstream accumulation stays f32 — one rounding per delta,
        never a bf16 reduction."""
        if self.uplink_compression is None:
            return tree
        f32 = jnp.float32
        return jax.tree_util.tree_map(
            lambda d: d.astype(jnp.bfloat16).astype(f32), tree)

    @tracing.scoped(tracing.AGGREGATE)
    def _apply_mean_delta_fused(self, params, delta_full, offsets):
        """Aggregation for the fused client phase's FULL-shaped delta.

        Shared window: out-of-window coordinates of the fused delta are
        exactly 0, so the window slice commutes with the per-coordinate
        client mean — extract each client's compact window FIRST, mean the
        [C, sub] stack, then the same single in-place scatter as the
        extract path.  Extract-then-mean is bitwise-identical to the
        mean-then-extract order (same elements, same reduction order) but
        does O(C·sub) aggregation arithmetic instead of O(C·full) — the
        shared-window wall-clock win.

        Per-client windows (staggered/random): each client's full-shaped
        delta already IS its scattered form (exact zeros outside its own
        window), so the extract path's per-client scatter-add collapses to
        a scan of plain adds — op-for-op the same accumulation order, which
        keeps the round bitwise-equal to extract on f32."""
        c = self.scfg
        C = c.clients_per_round
        if self.shared_window:
            off0 = {k: v[0] for k, v in offsets.items()}
            delta_sub = self._vmap(
                lambda d: ex.extract(d, self.axes_tree, off0,
                                     self.scheme.sizes))(delta_full)
            dbar = jax.tree_util.tree_map(
                lambda d: jnp.mean(d.astype(jnp.float32), axis=0),
                self._uplink(delta_sub))
            return _scatter_update(params, dbar, self.abstract,
                                   self.axes_tree, off0, self.scheme.sizes,
                                   c.server_lr)

        def acc_step(acc, d_c):
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), acc, d_c)
            return constrain_tree(acc, self.axes_tree, leading=()), None

        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)
        acc, _ = jax.lax.scan(acc_step, acc0, self._uplink(delta_full))
        return jax.tree_util.tree_map(
            lambda w, d: (w + c.server_lr * d.astype(jnp.float32) / C
                          ).astype(w.dtype), params, acc)

    @tracing.scoped(tracing.AGGREGATE)
    def _mean_delta_full_fused(self, delta_full):
        """Server pseudo-gradient from the fused phase: already full-shaped
        with exact zeros outside each client's window — the shared-window
        mean IS the scattered mean of the extract path; per-client windows
        mirror the extract path's scatter-average scan (same accumulation
        order, bitwise).  ``uplink_compression`` rounds each client delta
        through the simulated uplink before the f32 mean."""
        delta_full = self._uplink(delta_full)
        if self.shared_window:
            return jax.tree_util.tree_map(
                lambda d: jnp.mean(d.astype(jnp.float32), axis=0),
                delta_full)
        C = self.scfg.clients_per_round

        def acc_step(acc, d_c):
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype) / C, acc, d_c)
            return constrain_tree(acc, self.axes_tree, leading=()), None

        z = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)
        full, _ = jax.lax.scan(acc_step, z, delta_full)
        return full

    @tracing.scoped(tracing.AGGREGATE)
    def _mean_delta_full(self, params, delta, offsets):
        """Full-shaped f32 mean client delta (the server pseudo-gradient).

        Deliberately separate from :meth:`_apply_mean_delta`: stateful
        server optimizers need the delta materialized full-shaped (their
        state covers every coordinate), while the plain path's shared-window
        arm updates only the window slice in place — collapsing the two
        would force full-model traffic on the fast path.  Keep changes to
        the scatter logic mirrored between both helpers.
        """
        C = self.scfg.clients_per_round
        dbar = jax.tree_util.tree_map(
            lambda d: jnp.mean(d.astype(jnp.float32), axis=0), delta)
        if not offsets:
            return dbar
        if self.shared_window:
            off0 = {k: v[0] for k, v in offsets.items()}
            return ex.scatter_delta(dbar, self.abstract, self.axes_tree,
                                    off0, self.scheme.sizes)

        # staggered/random windows: average the per-client scatters
        def acc_step(acc, xs):
            d_c, off_c = xs
            fd = ex.scatter_delta(d_c, self.abstract, self.axes_tree,
                                  off_c, self.scheme.sizes)
            acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype) / C, acc, fd)
            return constrain_tree(acc, self.axes_tree, leading=()), None

        z = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)
        full, _ = jax.lax.scan(acc_step, z, (delta, offsets))
        return full

    # -- mesh scale-out: the client axis under shard_map -----------------------

    @tracing.scoped(tracing.AGGREGATE)
    def _local_delta_sum(self, delta, offsets, fused):
        """Shard-local f32 scatter-add of client deltas (no /C) — the
        summand of the client-axis ``psum``.  Mirrors the per-client scan
        arms of :meth:`_apply_mean_delta` / ``*_fused`` so that
        ``psum(local_sum) / C`` is the sharded mean delta."""
        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), self.abstract)

        if fused:  # delta already full-shaped, exact 0 outside each window
            def acc_step(acc, d_c):
                return jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), acc, d_c), None
            acc, _ = jax.lax.scan(acc_step, acc0, delta)
            return acc

        def acc_step(acc, xs):
            d_c, off_c = xs
            fd = ex.scatter_delta(d_c, self.abstract, self.axes_tree,
                                  off_c, self.scheme.sizes)
            return jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), acc, fd), None

        acc, _ = jax.lax.scan(acc_step, acc0, (delta, offsets))
        return acc

    def _client_phase_sharded(self, params, batch, offsets):
        """The client phase under ``shard_map`` on ``self.mesh``.

        Inputs are split over the client mesh axis — batch leaves
        ``[K, C, ...]`` on dim 1, offset vectors ``[C]`` on dim 0; server
        params ride replicated.  Each shard runs the ordinary (fused or
        extract) client phase on its own C/S clients, so per-shard the
        fused == extract bitwise contract is exactly the single-device
        one.  Crossing shards:

        * ``mesh_agg="gather"`` returns the per-client deltas all_gather'd
          back to the full client axis in client order — pure data
          movement, so the caller can replay the UNCHANGED single-device
          aggregation bitwise;
        * ``mesh_agg="psum"`` returns the f32 scatter-add partial sums
          psum'd over the client axis (the scalable arm: O(model) comm,
          fp-reassociated).

        Per-client losses are always gathered exactly ([K, C]).  The
        exchange runs under the ``fed.aggregate`` scope, the rest of the
        body under the phase's own.
        """
        axis = self.spmd_axis
        fused = self.use_fused and bool(offsets)
        psum = self.mesh_agg == "psum"

        def body(p, b, off):
            phase = self._client_phase_fused if fused else self._client_phase
            _, delta, losses = phase(p, b, off)
            if psum:
                delta = self._local_delta_sum(delta, off, fused)
            with jax.named_scope(tracing.AGGREGATE):
                losses = jax.lax.all_gather(losses, axis, axis=1, tiled=True)
                if psum:
                    return jax.lax.psum(delta, axis), losses
                delta = jax.tree_util.tree_map(
                    lambda d: jax.lax.all_gather(d, axis, axis=0, tiled=True),
                    delta)
            return delta, losses

        fn = spmd.shard_map(
            body, self.mesh,
            in_specs=(P(), P(None, axis), P(axis)),
            out_specs=P())
        return fn(params, batch, offsets)

    def _round_mesh(self, params, batch, offsets):
        """One round with the client axis sharded over ``self.mesh``."""
        c = self.scfg
        out, losses = self._client_phase_sharded(params, batch, offsets)
        if self.mesh_agg == "psum":
            # out = sum_c scattered delta_c (f32, full-shaped): the same
            # final update formula as the per-client scan arm
            with jax.named_scope(tracing.AGGREGATE):
                new = jax.tree_util.tree_map(
                    lambda w, d: (w + c.server_lr * d / c.clients_per_round
                                  ).astype(w.dtype), params, out)
        elif self.use_fused and offsets:
            new = self._apply_mean_delta_fused(params, out, offsets)
        else:
            new = self._apply_mean_delta(params, out, offsets)
        with jax.named_scope(tracing.SERVER_STEP):
            new = sm.project_l2(new, c.proj_radius)
        return new, {"loss": losses.mean(), "client_loss": losses}

    def _mean_delta_full_mesh(self, params, batch, offsets):
        """Sharded client phase + full-shaped mean delta (server-opt path)."""
        out, losses = self._client_phase_sharded(params, batch, offsets)
        if self.mesh_agg == "psum":
            with jax.named_scope(tracing.AGGREGATE):
                full_delta = jax.tree_util.tree_map(
                    lambda d: d / self.scfg.clients_per_round, out)
        elif self.use_fused and offsets:
            full_delta = self._mean_delta_full_fused(out)
        else:
            full_delta = self._mean_delta_full(params, out, offsets)
        return full_delta, losses

    # -- public rounds (both delegate to the phases above) ---------------------

    def round(self, params, batch, round_idx, rng=None):
        """One communication round.  batch leaves: [K, C, ...]."""
        if self.hetero is not None:
            return self._round_hetero(params, batch, round_idx, rng)
        offsets = self._client_offsets(params, round_idx, rng)
        if self.mesh is not None:
            return self._round_mesh(params, batch, offsets)
        if self.use_fused and offsets:
            _, delta_full, losses = self._client_phase_fused(params, batch,
                                                             offsets)
            new = self._apply_mean_delta_fused(params, delta_full, offsets)
        else:
            _, delta, losses = self._client_phase(params, batch, offsets)
            new = self._apply_mean_delta(params, delta, offsets)
        with jax.named_scope(tracing.SERVER_STEP):
            new = sm.project_l2(new, self.scfg.proj_radius)
        return new, {"loss": losses.mean(), "client_loss": losses}

    def round_with_server_opt(self, params, opt_state, batch, round_idx,
                              server_opt=None, rng=None):
        """Beyond-paper: treat the averaged client delta as a pseudo-gradient
        for a stateful server optimizer (FedAvgM / FedAdam).

        Same client phase as :meth:`round`; the aggregation applies
        ``server_opt.update`` on the full-shaped mean delta (momentum /
        second-moment state is full-shaped; out-of-window coordinates see
        delta 0, so their momentum decays — fill-in semantics preserved).
        """
        server_opt = server_opt if server_opt is not None else self.server_opt
        if server_opt is None:
            raise ValueError(
                "no server optimizer attached; pass server_opt= or build "
                "the round with api.fed_round(..., server_opt=...)")
        if self.hetero is not None:
            acc, losses = self._hetero_delta_sum(params, batch, round_idx,
                                                 rng)
            with jax.named_scope(tracing.AGGREGATE):
                full_delta = jax.tree_util.tree_map(
                    lambda d: d / self.scfg.clients_per_round, acc)
        else:
            full_delta, losses = self._mean_delta(params, batch, round_idx,
                                                  rng)
        with jax.named_scope(tracing.SERVER_STEP):
            new, opt_state = server_opt.update(params, full_delta, opt_state)
            new = sm.project_l2(new, self.scfg.proj_radius)
        return new, opt_state, {"loss": losses.mean(), "client_loss": losses}

    def _mean_delta(self, params, batch, round_idx, rng):
        """Client phase and full-shaped mean delta of a homogeneous round
        (the server optimizer's pseudo-gradient) with the client losses."""
        offsets = self._client_offsets(params, round_idx, rng)
        if self.mesh is not None:
            return self._mean_delta_full_mesh(params, batch, offsets)
        if self.use_fused and offsets:
            _, delta_full, losses = self._client_phase_fused(params, batch,
                                                             offsets)
            return self._mean_delta_full_fused(delta_full), losses
        _, delta, losses = self._client_phase(params, batch, offsets)
        return self._mean_delta_full(params, delta, offsets), losses


def _scatter_update(params, dbar, abstract, axes_tree, off0, sizes,
                    server_lr):
    """w[window] += lr * dbar, in place (single-window fast path)."""

    def f(w, d, full, axes):
        starts = [0] * w.ndim
        for dim, key in ex._windowed_dims(full.shape, axes, sizes):
            starts[dim] = off0[key]
        cur = jax.lax.dynamic_slice(w, tuple(starts), d.shape)
        upd = (cur.astype(jnp.float32)
               + server_lr * d.astype(jnp.float32)).astype(w.dtype)
        return jax.lax.dynamic_update_slice(w, upd, tuple(starts))

    return ex._tree_map_with_axes2(
        lambda pair, full, axes: f(pair[0], pair[1], full, axes),
        jax.tree_util.tree_map(lambda a, b: (a, b), params, dbar,
                               is_leaf=lambda x: not isinstance(x, dict)),
        abstract, axes_tree)


# ---------------------------------------------------------------------------
# Mask (dense) mode — paper-faithful path
# ---------------------------------------------------------------------------


def dense_client_masks(rng, abstract, axes_tree, scfg: SubmodelConfig,
                       capacities, round_idx, windowed_dims=None):
    """Masks [per-client pytrees stacked on leading C dim].

    capacities: [C] float (per-client p_i / beta_i — heterogeneous OK).
    """
    C = capacities.shape[0]
    if scfg.scheme == "full":
        return jax.tree_util.tree_map(
            lambda x: jnp.ones((C,) + x.shape, jnp.float32), abstract)
    if scfg.scheme == "bernoulli":
        keys = jax.random.split(jax.random.fold_in(rng, round_idx), C)
        return jax.vmap(
            lambda k, p: sm.bernoulli_masks(k, abstract, p)
        )(keys, capacities)

    # structured (rolling / static / random): windows per semantic axis with
    # per-client traced offsets *and sizes* (dense masks allow ragged sizes).
    if scfg.scheme not in ("static", "rolling", "random"):
        # e.g. "importance" needs live params, which dense masks never see —
        # refuse rather than silently training random windows.
        raise ValueError(
            f"scheme {scfg.scheme!r} is not supported in dense-mask mode; "
            "use window mode (repro.api.fed_round(..., mode='window')) "
            "instead")
    dims = windowed_dims or collect_axis_dims(abstract, axes_tree)
    keys = {k: i for i, k in enumerate(sorted(
        [d for d in dims if d[0] in scfg.axes]))}
    # Rolling offsets come from the very same WindowScheme grid window mode
    # uses (aligned-down interior entries + the exact-tail entry), so the
    # dense-mask oracle and the production compact path agree for align > 1.
    # The old frac-scaled offsets disagreed with the grid whenever align
    # rounded the window plan.
    roll_offsets = (make_scheme(scfg, dims).offsets(rng, round_idx, C)
                    if scfg.scheme == "rolling" else {})

    def client_mask(cap, ci):
        def leaf(full, axes):
            m = jnp.ones(full.shape, jnp.float32)
            for d, name in enumerate(axes):
                key = (name, int(full.shape[d]))
                if key not in keys:
                    continue
                n = full.shape[d]
                a = min(scfg.align, n)
                # align the per-client size exactly like make_scheme does
                # (identical to the old max(1, round(cap*n)) when align=1)
                size = jnp.clip(
                    (jnp.round(cap * n).astype(jnp.int32) // a) * a, a, n)
                if scfg.scheme == "static":
                    off = jnp.zeros((), jnp.int32)
                elif scfg.scheme == "rolling":
                    off = (roll_offsets[key][ci] if key in roll_offsets
                           else jnp.zeros((), jnp.int32))
                else:  # random structured
                    kk = jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(scfg.seed),
                                           round_idx), ci), keys[key])
                    off = jax.random.randint(kk, (), 0, n)
                idx = jnp.arange(n)
                if scfg.wrap:
                    sel = ((idx - off) % n) < size
                else:
                    off = jnp.minimum(off, n - size)
                    sel = (idx >= off) & (idx < off + size)
                shape = [1] * full.ndim
                shape[d] = n
                m = m * sel.reshape(shape).astype(jnp.float32)
            return m

        return ex._tree_map_with_axes(leaf, abstract, axes_tree)

    return jax.vmap(client_mask)(capacities, jnp.arange(C))


@dataclass
class MaskFedAvg:
    loss_fn: Callable
    scfg: SubmodelConfig
    abstract: Any
    axes_tree: Any
    capacities: jnp.ndarray            # [C]
    kernel_backend: Optional[str] = None  # pallas | jnp | auto (None = env)
    client_opt: Optional[ClientOpt] = None  # None = the paper's plain SGD
    server_opt: Any = None              # ServerOpt used by Trainer (optional)

    def __post_init__(self):
        self.client_opt = resolve_client_opt(self.client_opt)

    # -- composable round phases ---------------------------------------------

    def _client_phase(self, params, batch, round_idx, rng, capacities=None):
        """masks → m ⊙ w → K masked local-optimizer steps (scan)."""
        c = self.scfg
        capacities = self.capacities if capacities is None else capacities
        with jax.named_scope(tracing.OFFSETS):
            masks = dense_client_masks(rng, self.abstract, self.axes_tree, c,
                                       capacities, round_idx)
        mvg = sm.masked_value_and_grad(self.loss_fn)
        opt = self.client_opt

        def kstep(carry, mb):
            wc, ost = carry
            (loss, metrics), g = jax.vmap(mvg)(wc, masks, mb)
            # masked updates are elementwise, so the stacked [C, ...] leaves
            # go straight through the dispatched kernel — no client vmap.
            wc, ost = opt.update(wc, g, ost, c.client_lr, masks=masks,
                                 backend=self.kernel_backend)
            return (wc, ost), loss

        with jax.named_scope(tracing.CLIENT_PHASE):
            w_c = jax.tree_util.tree_map(
                lambda w, m: w[None] * m.astype(w.dtype), params, masks)
            (w_cK, _), losses = jax.lax.scan(kstep, (w_c, opt.init(w_c)),
                                             batch)
        return w_cK, masks, losses

    # -- public rounds ---------------------------------------------------------

    def round(self, params, batch, round_idx, rng, capacities=None):
        """batch leaves [K, C, ...].  capacities: optional per-round [C]
        (heterogeneous participation — the paper's 10%-of-100-clients)."""
        w_cK, masks, losses = self._client_phase(params, batch, round_idx,
                                                 rng, capacities)
        with jax.named_scope(tracing.AGGREGATE):
            new = dispatch.fillin_agg(params, w_cK, masks,
                                      server_lr=self.scfg.server_lr,
                                      backend=self.kernel_backend)
        with jax.named_scope(tracing.SERVER_STEP):
            new = sm.project_l2(new, self.scfg.proj_radius)
        return new, {"loss": losses.mean(), "client_loss": losses}

    def round_with_server_opt(self, params, opt_state, batch, round_idx,
                              server_opt=None, rng=None, capacities=None):
        """Stateful server step on the masked mean delta (pseudo-gradient),
        mirroring :meth:`WindowFedAvg.round_with_server_opt`."""
        server_opt = server_opt if server_opt is not None else self.server_opt
        if server_opt is None:
            raise ValueError(
                "no server optimizer attached; pass server_opt= or build "
                "the round with api.fed_round(..., server_opt=...)")
        w_cK, masks, losses = self._client_phase(params, batch, round_idx,
                                                 rng, capacities)
        with jax.named_scope(tracing.AGGREGATE):
            dbar = jax.tree_util.tree_map(
                lambda w, ws, ms: (ms * (ws.astype(jnp.float32)
                                         - w[None].astype(jnp.float32))
                                   ).mean(0),
                params, w_cK, masks)
        with jax.named_scope(tracing.SERVER_STEP):
            new, opt_state = server_opt.update(params, dbar, opt_state)
            new = sm.project_l2(new, self.scfg.proj_radius)
        return new, opt_state, {"loss": losses.mean(),
                                "client_loss": losses}


# ---------------------------------------------------------------------------
# Deprecated factory shims — use repro.api.fed_round instead
# ---------------------------------------------------------------------------


def _build_window_fed(model_loss_fn, scfg: SubmodelConfig, abstract,
                      axes_tree, spmd_axis=None, mesh=None,
                      mesh_agg="gather", kernel_backend=None,
                      client_opt=None, server_opt=None,
                      windowed_loss_fn=None,
                      fused_forward="auto",
                      capacities=None,
                      uplink_compression=None) -> WindowFedAvg:
    dims = collect_axis_dims(abstract, axes_tree)
    scheme = make_scheme(scfg, dims)
    return WindowFedAvg(loss_fn=model_loss_fn, scfg=scfg, abstract=abstract,
                        axes_tree=axes_tree, scheme=scheme,
                        spmd_axis=spmd_axis, mesh=mesh, mesh_agg=mesh_agg,
                        kernel_backend=kernel_backend,
                        client_opt=client_opt, server_opt=server_opt,
                        windowed_loss_fn=windowed_loss_fn,
                        fused_forward=fused_forward,
                        capacities=capacities,
                        uplink_compression=uplink_compression)


def _build_mask_fed(model_loss_fn, scfg: SubmodelConfig, abstract, axes_tree,
                    capacities, kernel_backend=None, client_opt=None,
                    server_opt=None) -> MaskFedAvg:
    return MaskFedAvg(loss_fn=model_loss_fn, scfg=scfg, abstract=abstract,
                      axes_tree=axes_tree,
                      capacities=jnp.asarray(capacities, jnp.float32),
                      kernel_backend=kernel_backend, client_opt=client_opt,
                      server_opt=server_opt)


def make_window_fed_round(model_loss_fn, scfg: SubmodelConfig, abstract,
                          axes_tree, spmd_axis=None,
                          kernel_backend=None) -> WindowFedAvg:
    """Deprecated: use ``repro.api.fed_round(model, scfg, mode="window")``."""
    warnings.warn("make_window_fed_round is deprecated; use "
                  "repro.api.fed_round", DeprecationWarning, stacklevel=2)
    return _build_window_fed(model_loss_fn, scfg, abstract, axes_tree,
                             spmd_axis=spmd_axis,
                             kernel_backend=kernel_backend)


def make_mask_fed_round(model_loss_fn, scfg: SubmodelConfig, abstract,
                        axes_tree, capacities,
                        kernel_backend=None) -> MaskFedAvg:
    """Deprecated: use ``repro.api.fed_round(model, scfg, mode="mask")``."""
    warnings.warn("make_mask_fed_round is deprecated; use "
                  "repro.api.fed_round", DeprecationWarning, stacklevel=2)
    return _build_mask_fed(model_loss_fn, scfg, abstract, axes_tree,
                           capacities, kernel_backend=kernel_backend)


# ---------------------------------------------------------------------------
# Output model (hat-w) — paper's final one-step corrected output
# ---------------------------------------------------------------------------


def output_model(fed, params, batch, rng, lipschitz=1.0, round_idx=0):
    """hat-w = P_W(w - (1/L) avg_i m_i ⊙ grad f_i(m_i ⊙ w))  (Alg. 1/2 output).

    Works in both modes: mask mode evaluates the literal dense-mask formula;
    window mode evaluates the same quantity in compact form (gradient on the
    extracted sub-model, scattered back — the two agree because slicing is
    linear, property-tested in tests/test_api.py).
    """
    scfg = fed.scfg
    if isinstance(fed, MaskFedAvg):
        masks = dense_client_masks(rng, fed.abstract, fed.axes_tree, scfg,
                                   fed.capacities, round_idx)
        mvg = sm.masked_value_and_grad(fed.loss_fn)
        w_c = jax.tree_util.tree_map(
            lambda w, m: w[None] * m.astype(w.dtype), params, masks)
        mb = jax.tree_util.tree_map(lambda x: x[0], batch)
        (_, _), g = jax.vmap(mvg)(w_c, masks, mb)
        gbar = jax.tree_util.tree_map(
            lambda m, gr: (m * gr).mean(0), masks, g)
        new = jax.tree_util.tree_map(
            lambda w, d: w - d.astype(w.dtype) / lipschitz, params, gbar)
        return sm.project_l2(new, scfg.proj_radius)

    # Window mode: one gradient on each client's compact sub-model, scattered
    # back and averaged — reuses the round's client-extraction and
    # mean-delta helpers.
    offsets = fed._client_offsets(params, round_idx, rng)
    sub0 = fed._extract_clients(params, offsets)
    mb = jax.tree_util.tree_map(lambda x: x[0], batch)
    (_, _), g = fed._vmap(
        jax.value_and_grad(fed.loss_fn, has_aux=True))(sub0, mb)
    gbar = fed._mean_delta_full(params, g, offsets)
    new = jax.tree_util.tree_map(
        lambda w, d: w - d.astype(w.dtype) / lipschitz, params, gbar)
    return sm.project_l2(new, scfg.proj_radius)


# ---------------------------------------------------------------------------
# Training-loop driver (superseded by repro.core.trainer.Trainer)
# ---------------------------------------------------------------------------


def run_rounds(fed, params, batch_iter, n_rounds, rng, jit=True,
               callback=None):
    """Thin wrapper over :class:`repro.core.trainer.Trainer` (kept for the
    theory/stability harnesses).  Returns ``(params, history)`` where
    history is the per-round *metrics* record list (``h["loss"]`` etc.)."""
    from repro.core.trainer import Trainer
    trainer = Trainer(fed, params, rng=rng, jit=jit,
                      callbacks=(callback,) if callback else ())
    return trainer.run(batch_iter, n_rounds)
