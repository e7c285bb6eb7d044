"""repro.api — the library's single public entry surface.

One facade constructs a federated sub-model round in either executable
form, with pluggable client/server optimizers; one :class:`Trainer` owns
the loop.  ``model`` is anything exposing the model-zoo protocol
(``.loss``, ``.abstract_params()``, ``.axes()``) or a raw ``(loss_fn,
abstract, axes_tree)`` triple — the theory/benchmark problems use the
latter.  End to end on a tiny least-squares triple:

>>> import jax, jax.numpy as jnp
>>> from repro import api
>>> from repro.configs.base import SubmodelConfig
>>> def loss(w, batch):
...     # window mode hands each client a COMPACT sub-model (here: a
...     # contiguous half of w), so the objective must be shape-agnostic
...     r = w["w"] - batch["target"].mean()
...     return 0.5 * jnp.mean(r * r), {}
>>> abstract = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
>>> scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
...                       clients_per_round=4, client_lr=0.3)
>>> fed = api.fed_round((loss, abstract, {"w": ("d_ff",)}), scfg)
>>> type(fed).__name__                    # rolling -> compact window mode
'WindowFedAvg'
>>> def batches():                        # leaves [K, C, ...]
...     while True:
...         yield {"target": jnp.ones((2, 4, 1))}
>>> trainer = api.Trainer(fed, {"w": jnp.zeros(8)}, rng=1)
>>> params, history = trainer.run(batches(), 8)
>>> params["w"].shape, len(history)
((8,), 8)
>>> trainer.losses[-1] < trainer.losses[0]    # rolling windows cover w
True

Mode selection (``mode="auto"``): ``bernoulli`` → dense-mask mode (the
only form that can express unstructured Algorithm-1 masks); every other
scheme → compact window mode (the production TPU path).  ``mode="mask"``
forces the paper-faithful dense path; ``mode="window"`` forces the
compact path.  Both accept per-client heterogeneous ``capacities`` —
dense masks at per-client fractions, or per-client window widths run as
capacity buckets (see :func:`fed_round`):

>>> bern = SubmodelConfig(scheme="bernoulli", capacity=0.5,
...                       clients_per_round=4)
>>> api.resolve_mode("auto", bern.scheme)
'mask'

Deprecated constructors (kept as shims): ``make_window_fed_round`` /
``make_mask_fed_round`` in ``repro.core.fedavg``.  The paper → code
mapping lives in ``docs/paper_map.md``; the module layering in
``docs/architecture.md``.
"""
from __future__ import annotations

import inspect
from typing import Any, Optional, Tuple

import numpy as np

from repro.configs.base import SubmodelConfig
from repro.core.fedavg import (MESH_AGGS, CapacityBucket, MaskFedAvg,
                               WindowFedAvg, _build_mask_fed,
                               _build_window_fed, output_model, run_rounds)
from repro.sharding.spmd import axis_size, resolve_client_axis
from repro.core.server_opt import SERVER_OPTS, ServerOpt
from repro.core.trainer import Trainer, checkpoint_callback
from repro.fleet import (STALENESS_POLICIES, SERVER_LR_SCHEDULES,
                         AsyncTrainer, EpochPermutationSampler,
                         FleetSimulator, LatencyModel)
from repro.optim.client import (CLIENT_OPTS, ClientOpt, client_momentum,
                                client_proximal, client_sgd,
                                resolve_client_opt)

__all__ = [
    "fed_round", "Trainer", "checkpoint_callback", "output_model",
    "run_rounds", "resolve_mode", "MODES",
    "ClientOpt", "CLIENT_OPTS", "client_sgd", "client_momentum",
    "client_proximal", "ServerOpt", "SERVER_OPTS",
    "WindowFedAvg", "MaskFedAvg", "CapacityBucket",
    "AsyncTrainer", "FleetSimulator", "LatencyModel",
    "EpochPermutationSampler", "STALENESS_POLICIES", "SERVER_LR_SCHEDULES",
]

MODES = ("auto", "window", "mask")


def resolve_mode(mode: str, scheme: str) -> str:
    """``auto`` → ``mask`` for unstructured Bernoulli masks, else ``window``."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    if mode == "auto":
        return "mask" if scheme == "bernoulli" else "window"
    if mode == "window" and scheme == "bernoulli":
        raise ValueError(
            "scheme 'bernoulli' (unstructured Algorithm-1 masks) has no "
            "compact window form; use mode='mask' or 'auto'")
    return mode


def _model_parts(model) -> Tuple[Any, Any, Any]:
    if all(hasattr(model, a) for a in ("loss", "abstract_params", "axes")):
        return model.loss, model.abstract_params(), model.axes()
    if isinstance(model, (tuple, list)) and len(model) == 3:
        return tuple(model)
    raise TypeError(
        "model must expose the model-zoo protocol (.loss, "
        ".abstract_params(), .axes()) or be a (loss_fn, abstract, "
        f"axes_tree) triple; got {type(model).__name__}")


def _windowed_loss(loss_fn):
    """``loss_fn`` itself when it is window-aware (accepts a ``window=``
    kwarg, like the model zoo's ``Model.loss``), else None — the fused
    rolling-window arm is only offered where it exists.  Works for both
    model-zoo objects and raw ``(loss_fn, abstract, axes)`` triples."""
    try:
        if "window" in inspect.signature(loss_fn).parameters:
            return loss_fn
    except (TypeError, ValueError):
        pass
    return None


def _resolve_server_opt(server_opt, scfg: SubmodelConfig) \
        -> Optional[ServerOpt]:
    if server_opt is None or isinstance(server_opt, str) and \
            server_opt in ("", "none"):
        return None
    if isinstance(server_opt, str):
        if server_opt not in SERVER_OPTS:
            raise ValueError(
                f"unknown server optimizer {server_opt!r}; expected one of "
                f"{sorted(SERVER_OPTS)} or 'none'")
        if server_opt in ("sgd", "momentum"):
            # these step in server_lr units (sgd(lr=server_lr) IS the
            # paper's update); adam's adaptive step keeps its own scale.
            return SERVER_OPTS[server_opt](lr=scfg.server_lr)
        return SERVER_OPTS[server_opt]()
    return server_opt


def fed_round(model, scfg: SubmodelConfig, *, mode: str = "auto",
              client_opt=None, server_opt=None,
              kernel_backend: Optional[str] = None, spmd_axis=None,
              mesh=None, mesh_agg: str = "gather",
              capacities=None, fused_forward="auto",
              uplink_compression: Optional[str] = None):
    """Build one federated sub-model round (Algorithms 1 & 2).

    Args:
      model: model-zoo object or ``(loss_fn, abstract, axes_tree)`` triple.
      scfg: the :class:`SubmodelConfig` (scheme, capacity, K, C, lrs, ...).
      mode: ``auto`` (scheme-derived) | ``window`` (compact) | ``mask``
        (dense, paper-faithful).
      client_opt: local-step optimizer — a :class:`ClientOpt`, a registry
        name (``sgd`` | ``momentum`` | ``proximal``), or None for the
        paper's plain SGD.
      server_opt: optional stateful server optimizer applied to the mean
        delta — a ``ServerOpt``, a registry name (``sgd`` | ``momentum`` |
        ``adam``), or None for the paper's plain averaging.  Registry
        names ``sgd``/``momentum`` are built with ``lr=scfg.server_lr``
        (so ``server_opt="sgd"`` is exactly the paper's update); ``adam``
        keeps its adaptive-scale default.  Consumed by :class:`Trainer`
        (which then steps ``round_with_server_opt``).
      kernel_backend: ``pallas`` | ``jnp`` | ``auto`` (None = env default).
      spmd_axis: mesh axis carrying the per-client dim (window mode only).
        With ``mesh`` it names the axis ``shard_map`` splits clients over
        (None derives it: ``clients`` if present, else ``data``, else the
        leading axis) and must exist on the mesh; without ``mesh`` it is
        the legacy ``vmap(spmd_axis_name=...)`` annotation.
      mesh: window mode only — a ``jax.sharding.Mesh``.  The round then
        executes under ``shard_map``: per-client inputs (batch streams,
        offset vectors) are split over the ``spmd_axis`` mesh axis, every
        shard runs the (fused or extract) client phase on its own
        ``C / axis_size`` clients, and aggregation crosses shards per
        ``mesh_agg``.  ``scfg.clients_per_round`` must be divisible by the
        client mesh-axis size.  See ``repro.launch.mesh.make_host_mesh``
        for CPU test meshes (forced host devices) and
        ``docs/architecture.md`` § mesh scale-out.
      mesh_agg: ``gather`` (default) all_gathers the per-client deltas and
        replays the single-device aggregation — the sharded round is
        **bitwise-equal** to the ``mesh=None`` round (CI-gated).  ``psum``
        reduces shard-local f32 scatter-add partials over the client axis
        — O(model) comm instead of O(C·sub), equal to the single-device
        round only to fp roundoff.
      capacities: per-client ``[C]`` capacity fractions (heterogeneous
        fleets: phones next to workstations).  Mask mode draws each
        client's dense mask at its own fraction (defaults to
        ``scfg.capacity`` for every client).  Window mode derives each
        client's window *width* from its fraction and buckets clients by
        width (``CapacityBucket``): every bucket runs the ordinary
        homogeneous fused/extract client phase at its own static width,
        and the bucket delta sums accumulate in descending-beta order —
        so the heterogeneous round composes **bitwise** from per-bucket
        homogeneous rounds (pinned in ``tests/test_hetero.py``).
        Window-mode capacities require ``mesh=None`` and are incompatible
        with ``shared_window=True``; values must lie in ``(0, 1]``.
      fused_forward: window mode only — ``"auto"`` (default) takes the
        fused multi-axis window client phase: each client's window is cut
        from the tree once a round and the cut tree is carried through
        the K local steps, except axes the forward must index absolutely
        (a share's ``experts`` window), which stay whole and are narrowed
        by the model's window-aware forward.  On a dense model that is
        the extract round, and the rolling-matmul kernels do not run in
        it (the cut weights take plain XLA dots).  It is taken whenever
        the model exposes a window-aware ``loss(params, batch,
        window=...)`` and every properly-windowed axis has a fused
        forward: ``d_ff`` (MLP/MTP), GQA-coupled
        ``heads``/``kv_heads`` (windowed q/k/v/o projections), MLA's
        standalone ``heads`` (windowed per-head up-projections),
        ``experts`` and ``moe_d_ff`` (MoE routing + per-expert/shared
        MLPs), and ``ssm_heads`` (windowed SSD projections) — the full
        default ``SubmodelConfig.axes`` tuple across the model zoo.
        A shared window (rolling/static/importance without stagger) is
        cut once for all clients; per-client schemes (staggered rolling,
        random, staggered importance) cut once per client.
        ``"on"``/True forces fusion (error when unavailable),
        ``"off"``/False keeps the extract-based client phase.  Fused and
        extract rounds are bitwise-equal on f32 (property-tested; see the
        README fused-coverage matrix, pinned by ``tests/test_docs.py``).
      uplink_compression: window mode only — ``None`` (default) ships the
        exact f32 client deltas; ``"bf16"`` rounds each delta to bfloat16
        on the simulated uplink (half the client→server bytes) and
        decompresses to f32 before the server mean, so accumulation stays
        f32 with one final rounding into the param dtype.  ``"bf16"``
        trades the fused == extract bitwise guarantee for comm volume.

    Returns a :class:`WindowFedAvg` or :class:`MaskFedAvg` whose ``round``
    signature is identical across modes (mask mode additionally accepts
    per-round ``capacities``).

    A per-client-capacity mask round (the paper's heterogeneous-device
    setting), stepped directly:

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from repro import api
    >>> from repro.configs.base import SubmodelConfig
    >>> def loss(w, batch):
    ...     r = batch["x"] @ w["w"] - batch["y"]
    ...     return 0.5 * jnp.mean(r * r), {}
    >>> abstract = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
    >>> scfg = SubmodelConfig(scheme="bernoulli", capacity=0.5,
    ...                       local_steps=1, clients_per_round=2)
    >>> fed = api.fed_round((loss, abstract, {"w": ("d_ff",)}), scfg,
    ...                     capacities=np.array([0.25, 1.0], np.float32))
    >>> type(fed).__name__
    'MaskFedAvg'
    >>> batch = {"x": jnp.ones((1, 2, 4, 8)), "y": jnp.ones((1, 2, 4))}
    >>> params, metrics = fed.round({"w": jnp.zeros(8)}, batch, 0,
    ...                             jax.random.PRNGKey(0))
    >>> params["w"].shape, metrics["client_loss"].shape
    ((8,), (1, 2))

    A heterogeneous-capacity *window* round buckets clients by width —
    each bucket is a homogeneous round at its own beta:

    >>> scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
    ...                       local_steps=1, clients_per_round=4)
    >>> fed = api.fed_round((loss, abstract, {"w": ("d_ff",)}), scfg,
    ...                     mode="window",
    ...                     capacities=[1.0, 0.5, 0.5, 0.25])
    >>> [(b.beta, list(b.idx)) for b in fed.hetero]
    [(1.0, [0]), (0.5, [1, 2]), (0.25, [3])]
    """
    loss_fn, abstract, axes_tree = _model_parts(model)
    resolved = resolve_mode(mode, scfg.scheme)
    client_opt = resolve_client_opt(client_opt)
    server_opt = _resolve_server_opt(server_opt, scfg)
    if mesh_agg not in MESH_AGGS:
        raise ValueError(f"unknown mesh_agg {mesh_agg!r}; expected one of "
                         f"{MESH_AGGS}")
    if mesh is not None:
        if resolved != "window":
            raise ValueError("mesh execution applies to window mode only "
                             "(mask mode is the dense-mask oracle)")
        spmd_axis = resolve_client_axis(mesh, spmd_axis)
        n_shards = axis_size(mesh, spmd_axis)
        if scfg.clients_per_round % n_shards:
            raise ValueError(
                f"clients_per_round={scfg.clients_per_round} must be "
                f"divisible by the {spmd_axis!r} mesh-axis size {n_shards} "
                f"(each shard runs an equal slice of the client vmap)")
    if resolved == "window":
        return _build_window_fed(loss_fn, scfg, abstract, axes_tree,
                                 spmd_axis=spmd_axis,
                                 mesh=mesh, mesh_agg=mesh_agg,
                                 kernel_backend=kernel_backend,
                                 client_opt=client_opt,
                                 server_opt=server_opt,
                                 windowed_loss_fn=_windowed_loss(loss_fn),
                                 fused_forward=fused_forward,
                                 capacities=capacities,
                                 uplink_compression=uplink_compression)
    if spmd_axis is not None:
        raise ValueError("spmd_axis applies to window mode only")
    if fused_forward in (True, "on"):
        raise ValueError("fused_forward applies to window mode only "
                         "(mask mode is the dense-mask oracle)")
    if uplink_compression is not None:
        raise ValueError("uplink_compression applies to window mode only "
                         "(mask mode is the dense-mask oracle)")
    if capacities is None:
        capacities = np.full(scfg.clients_per_round, scfg.capacity,
                             np.float32)
    return _build_mask_fed(loss_fn, scfg, abstract, axes_tree, capacities,
                           kernel_backend=kernel_backend,
                           client_opt=client_opt, server_opt=server_opt)
