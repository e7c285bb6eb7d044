"""The asynchronous federated round server (``api.AsyncTrainer``).

Event loop tying the fleet pieces together: idle slots (the
``launch/batching.py`` slot-pool idiom, one slot per in-flight client)
dispatch as a *cohort* at the current virtual instant — one stacked call
of the UNCHANGED fused/extract client phase from ``core/fedavg.py`` —
and their completion times go on a ``(time, seq)`` heap drawn from the
:class:`~repro.fleet.simulator.FleetSimulator`.  Completed reports land
in the :class:`~repro.fleet.buffer.DeltaBuffer`; once M of the N
in-flight clients have reported, the buffered deltas are aggregated
through the round object's OWN aggregation arms (`_apply_mean_delta*`,
``_mean_delta_full*`` + ``ServerOpt``), with staleness weights and the
server-lr schedule folded into a per-entry scale vector.

Exactness anchor (pinned in ``tests/test_fleet.py``, gated by
``async_sync_equiv`` in CI bench-smoke): with M = N, zero latency
spread, and no dropouts, every dispatch cohort is the full client set at
one instant, every report has τ = 0 (scale exactly 1.0, multiply
skipped), and the round sequence is **bitwise-equal** (0 ulp f32) to the
synchronous ``api.Trainer`` loop over ``api.fed_round``.

Layering: this package consumes the round object handed to it (built by
``repro.api.fed_round``) and never constructs rounds — it imports
neither ``repro.core.fedavg`` nor ``repro.api`` (CI ``policy`` job +
``tests/test_fleet.py`` enforce this).
"""
from __future__ import annotations

import dataclasses
import heapq
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import tracing
from repro.core import submodel as sm
from repro.core.trainer import _record
from repro.fleet.buffer import ClientReport, DeltaBuffer
from repro.fleet.sampler import (EpochPermutationSampler,
                                 resolve_server_lr_schedule)
from repro.fleet.simulator import FleetSimulator


def _tree_slice(tree, j):
    """[1]-leading slice of entry j — pure data movement."""
    return jax.tree_util.tree_map(lambda x: x[j:j + 1], tree)


def _tree_concat(trees):
    """Stack [1]-leading slices back to [M] — pure data movement, so the
    M=N anchor's reassembled delta is the cohort's stacked delta bitwise."""
    if len(trees) == 1:
        return trees[0]
    return jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *trees)


@dataclass
class AsyncTrainer:
    """Asynchronous counterpart of :class:`repro.api.Trainer`.

    Construct with a *window-mode* round object from
    :func:`repro.api.fed_round` and the initial params, then call
    :meth:`run` with a batch source::

        fed = api.fed_round(model, scfg)
        fleet = api.FleetSimulator(64, api.LatencyModel(straggler_frac=.25))
        at = api.AsyncTrainer(fed, params, rng=0, buffer_size=4,
                              fleet=fleet)
        params, history = at.run(batches, n_rounds=50)

    ``source`` is either an iterator yielding batches with leaves
    ``[K, C, ...]`` (each dispatch consumes one item and takes the
    dispatched slots' lanes) or a callable ``(client_ids) -> batch`` with
    leaves ``[K, len(client_ids), ...]`` (e.g.
    ``lambda ids: fd.round_batch(ids, K, mb)`` over a
    :class:`repro.data.federated.FederatedDataset`).

    Defaults are the sync-equivalence anchor: ``buffer_size=None`` means
    M = ``scfg.clients_per_round``, ``fleet=None`` a zero-spread fleet of
    that size, so ``run`` replays the synchronous round sequence
    bitwise.  ``history`` mirrors ``Trainer``'s (``round`` / ``loss`` /
    ``client_loss`` records, device arrays, host sync only at
    log/eval boundaries) plus async extras per record: ``virtual_time``
    (the virtual clock at aggregation), ``staleness`` (mean τ of the
    aggregated reports), and ``lr_mult`` (the server-lr schedule value).

    Heterogeneous-capacity rounds (``fed_round(capacities=)``) dispatch
    through the bucket-loop phase and buffer FULL-shaped per-client
    deltas; aggregation then sums reports in arrival (client) order
    rather than the sync round's bucket order, so their M=N anchor holds
    to f32 roundoff (allclose), not bitwise — the homogeneous bitwise
    anchor is unchanged.  With ``FleetSimulator(capacities=)`` also set,
    dispatch rank-matches device capacity to window width
    (:meth:`_pair_capacities`).
    """

    fed: Any                               # window-mode round (api.fed_round)
    params: Any
    rng: Any = None                        # PRNGKey (int seeds accepted)
    buffer_size: Optional[int] = None      # M; None = clients_per_round
    fleet: Optional[FleetSimulator] = None  # None = zero-spread, N = C
    sampler: Optional[EpochPermutationSampler] = None
    staleness: Union[str, Callable] = "inverse_sqrt"
    server_opt: Any = None                 # overrides fed.server_opt
    server_lr_schedule: Any = None         # name | callable(round) -> mult
    jit: bool = True
    callbacks: Sequence[Callable] = ()
    eval_fn: Optional[Callable] = None
    eval_every: int = 0
    log_every: int = 0
    log_fn: Callable = print
    max_ticks: int = 1_000_000             # scheduler-event safety valve

    round_idx: int = field(default=0, init=False)
    history: List[Dict] = field(default_factory=list, init=False)
    opt_state: Any = field(default=None, init=False)

    def __post_init__(self):
        fed = self.fed
        for attr in ("_client_phase", "_client_phase_fused",
                     "_apply_mean_delta", "scfg"):
            if not hasattr(fed, attr):
                raise TypeError(
                    "AsyncTrainer drives window-mode rounds only (build "
                    "one with repro.api.fed_round(model, scfg); mask mode "
                    "has no per-client window deltas to buffer); got "
                    f"{type(fed).__name__}")
        if getattr(fed, "mesh", None) is not None:
            raise ValueError(
                "AsyncTrainer owns the client axis (dispatch cohorts are "
                "dynamic); build the round with mesh=None")
        if self.rng is None:
            self.rng = jax.random.PRNGKey(0)
        elif isinstance(self.rng, int):
            self.rng = jax.random.PRNGKey(self.rng)

        self._C = fed.scfg.clients_per_round       # in-flight slots N
        m = self._C if self.buffer_size is None else self.buffer_size
        self.buffer = DeltaBuffer(m, self.staleness)
        if self.fleet is None:
            self.fleet = FleetSimulator(self._C)
        if self.fleet.n_clients < self._C:
            raise ValueError(
                f"fleet of {self.fleet.n_clients} clients cannot fill "
                f"{self._C} in-flight slots; grow the fleet or shrink "
                "scfg.clients_per_round")
        if self.sampler is None:
            self.sampler = EpochPermutationSampler(self.fleet.n_clients,
                                                   seed=fed.scfg.seed)
        self._schedule = resolve_server_lr_schedule(self.server_lr_schedule)
        if self.server_opt is None:
            self.server_opt = getattr(fed, "server_opt", None)
        if self.server_opt is not None:
            self.opt_state = self.server_opt.init(fed.abstract)

        # scheduler state (persists across run() calls — in-flight work
        # resumes exactly where it stopped)
        self._clock = 0.0
        self._seq = 0                       # dispatch sequence counter
        self._events: list = []             # heap of (time, seq, slot, rep)
        self._idle: List[int] = list(range(self._C))
        self._round_offsets: Dict[int, Any] = {}   # tag -> full [C] offsets
        self._offsets_host: Dict[int, Any] = {}    # host mirror, same tags
        self._fused: Optional[bool] = None  # resolved at first dispatch
        self._phase = None
        self._scatter_fed = None            # shared_window=False clone
        self._agg_cache: Dict[Any, Any] = {}
        # Heterogeneous capacities (window mode, capacities=): dispatch
        # cohorts run the bucket-loop phase and report FULL-shaped
        # per-client deltas, so buffered aggregation is width-agnostic.
        self._hetero = getattr(fed, "hetero", None)
        self._phase_cache: Dict[Any, Any] = {}

    # -- round context (rng chain + offsets mirror the sync Trainer) ----------

    def _offsets_for(self, tag):
        """Full [C] offset vectors for a server-round tag.

        One ``jax.random.split`` per NEW tag — the same rng chain as
        ``Trainer.step``, and one offsets draw per round like the sync
        ``fed.round``; cohorts redispatched against the same tag reuse
        them (a straggler retry trains the same round's window).

        A host mirror of the tiny [C] int32 vectors is synced here, ONCE
        per new tag — reports then carry host slices, so the aggregation
        path's shared-window check never touches the device."""
        if tag not in self._round_offsets:
            self.rng, sub = jax.random.split(self.rng)
            off = self.fed._client_offsets(self.params, tag, sub)
            self._round_offsets[tag] = off
            self._offsets_host[tag] = jax.device_get(off)
        return self._round_offsets[tag]

    def _phase_fn(self, slots):
        if self._hetero is not None:
            # bucket membership depends on WHICH lanes dispatched: one
            # jitted phase per distinct slot set (slot pools are small
            # and recur, so the cache stays tiny)
            key = tuple(slots)
            if key not in self._phase_cache:
                f = self.fed._hetero_phase_for(key)
                self._phase_cache[key] = jax.jit(f) if self.jit else f
            return self._phase_cache[key]
        if self._phase is None:
            fed = self.fed

            def f(params, batch, offsets):
                phase = (fed._client_phase_fused if self._fused
                         else fed._client_phase)
                _, delta, losses = phase(params, batch, offsets)
                return delta, losses

            self._phase = jax.jit(f) if self.jit else f
        return self._phase

    # -- dispatch --------------------------------------------------------------

    def _next_batch(self, source, ids, slots):
        if callable(source):
            batch = source(ids)  # sampler already yields a host ndarray
        else:
            batch = next(source)
            if len(slots) != self._C or slots != list(range(self._C)):
                # partial cohort: take the dispatched slots' lanes — a
                # device-side gather, so host batches upload once and
                # device batches never round-trip
                lanes = jnp.asarray(slots, jnp.int32)
                batch = jax.tree_util.tree_map(
                    lambda v: jnp.take(jnp.asarray(v), lanes, axis=1),
                    batch)
        if isinstance(batch, dict):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return batch

    def _pair_capacities(self, ids, slots):
        """Rank-match sampled clients to width slots: when both the fleet
        (device capability, ``FleetSimulator(capacities=)``) and the
        round (per-slot window width, ``fed_round(capacities=)``) carry
        capacity vectors, the most capable sampled client takes the
        widest dispatched slot — slow/small devices train small windows.
        Pure host-side reindexing of the sampled ids; with either vector
        absent, ids pass through unchanged."""
        fleet_caps = getattr(self.fleet, "capacities", None)
        slot_caps = getattr(self.fed, "capacities", None)
        if fleet_caps is None or slot_caps is None:
            return ids
        ids = np.asarray(ids)
        slot_rank = np.argsort(
            -np.asarray([slot_caps[s] for s in slots]), kind="stable")
        id_rank = np.argsort(-fleet_caps[ids], kind="stable")
        paired = np.empty_like(ids)
        paired[slot_rank] = ids[id_rank]
        return paired

    def _dispatch(self, source):
        slots, self._idle = sorted(self._idle), []
        ids = self._pair_capacities(self.sampler.sample(len(slots)), slots)
        tag = self.round_idx
        offsets = self._offsets_for(tag)
        if self._fused is None:
            # heterogeneous cohorts report FULL-shaped per-client deltas
            # (exact zeros outside each window) → the *_fused agg arms
            self._fused = (True if self._hetero is not None
                           else self.fed.use_fused and bool(offsets))
        lanes = jnp.asarray(slots, jnp.int32)
        cohort_off = {k: jnp.take(v, lanes, axis=0)
                      for k, v in offsets.items()}
        host_off = self._offsets_host[tag]
        with TraceAnnotation(tracing.ROUND_PUT):
            batch = self._next_batch(source, ids, slots)
        with TraceAnnotation(tracing.ROUND_DISPATCH):
            delta, losses = self.fleet.run_cohort(
                self._phase_fn(slots), self.params, batch, cohort_off)
        for j, (slot, cid) in enumerate(zip(slots, ids)):
            delay, ok = self.fleet.completion(int(cid), self._seq)
            rep = ClientReport(
                client_id=int(cid), slot=slot, round_tag=tag,
                delta=_tree_slice(delta, j),
                offsets={k: v[slot:slot + 1] for k, v in host_off.items()},
                losses=losses[:, j:j + 1]) if ok else None
            heapq.heappush(self._events,
                           (self._clock + delay, self._seq, slot, rep))
            self._seq += 1

    # -- aggregation -----------------------------------------------------------

    def _scatter_arm(self):
        """shared_window=False clone for mixed-offset buffers: a shared-
        window scheme's mean+single-scatter fast path is only valid when
        every buffered entry trained the SAME window; stale entries from
        older rounds break that, so they aggregate through the per-client
        scatter arm instead (the same math the staggered schemes use)."""
        if self._scatter_fed is None:
            self._scatter_fed = dataclasses.replace(self.fed,
                                                    shared_window=False)
        return self._scatter_fed

    def _entry_scales(self, taus, weights, lr_mult, denom, m):
        """Per-entry multipliers g making the round's fixed-denominator
        aggregation compute the staleness-weighted, schedule-scaled mean:
        the arm divides by ``denom`` (m on the shared-mean path, C on the
        per-client scatter path), so g_i = lr_mult · w_i · denom / Σw.
        Equal weights shortcut to g = lr_mult · denom / m exactly — with
        τ = 0, M = C, and multiplier 1 that is exactly 1.0, and the
        caller skips the multiply entirely (the bitwise anchor)."""
        if np.all(taus == taus[0]):
            g = np.full(m, lr_mult * (denom / m), np.float64)
        else:
            g = lr_mult * weights * (denom / weights.sum())
        return g

    def _agg_fn(self, fused, shared_arm, scale, with_opt):
        key = (fused, shared_arm, scale, with_opt)
        if key in self._agg_cache:
            return self._agg_cache[key]
        fed = self.fed
        arm = fed if (shared_arm or not fed.shared_window) \
            else self._scatter_arm()
        server_opt = self.server_opt

        def scaled(delta, g):
            if not scale:
                return delta
            return jax.tree_util.tree_map(
                lambda d: d * g.reshape((-1,) + (1,) * (d.ndim - 1)), delta)

        if with_opt:
            def f(params, opt_state, delta, offsets, g):
                with jax.named_scope(tracing.AGGREGATE):
                    delta = scaled(delta, g)
                full = (arm._mean_delta_full_fused(delta) if fused
                        else arm._mean_delta_full(params, delta, offsets))
                with jax.named_scope(tracing.SERVER_STEP):
                    new, opt_state = server_opt.update(params, full,
                                                       opt_state)
                    return (sm.project_l2(new, fed.scfg.proj_radius),
                            opt_state)
        else:
            def f(params, delta, offsets, g):
                with jax.named_scope(tracing.AGGREGATE):
                    delta = scaled(delta, g)
                new = (arm._apply_mean_delta_fused(params, delta, offsets)
                       if fused else
                       arm._apply_mean_delta(params, delta, offsets))
                with jax.named_scope(tracing.SERVER_STEP):
                    return sm.project_l2(new, fed.scfg.proj_radius)

        self._agg_cache[key] = jax.jit(f) if self.jit else f
        return self._agg_cache[key]

    def _aggregate(self):
        r = self.round_idx
        reps, taus, weights = self.buffer.take(r)
        m = len(reps)
        delta = _tree_concat([rep.delta for rep in reps])
        # report offsets are host slices (mirrored once per round tag in
        # _offsets_for): concat on host, upload the [m] vector once
        off_host = ({k: np.concatenate([rep.offsets[k] for rep in reps])
                     for k in reps[0].offsets} if reps[0].offsets else {})
        offsets = {k: jnp.asarray(v) for k, v in off_host.items()}
        losses = jnp.concatenate([rep.losses for rep in reps], axis=1)

        # the shared-window mean+single-scatter fast path applies only when
        # every buffered entry trained the same window (pure host check on
        # the tiny [m] offset vectors; staleness can mix rounds' windows)
        shared_arm = bool(self.fed.shared_window) and bool(offsets) and all(
            all(np.array_equal(rep.offsets[k], reps[0].offsets[k])
                for k in offsets) for rep in reps[1:])
        denom = m if shared_arm else self._C
        lr_mult = float(self._schedule(r))
        g = self._entry_scales(taus, weights, lr_mult, denom, m)
        scale = not np.all(g == 1.0)
        gj = jnp.asarray(g, jnp.float32)

        fn = self._agg_fn(self._fused, shared_arm, scale,
                          self.server_opt is not None)
        if self.server_opt is None:
            self.params = fn(self.params, delta, offsets, gj)
        else:
            self.params, self.opt_state = fn(self.params, self.opt_state,
                                             delta, offsets, gj)
        self.round_idx += 1
        return _record(r, {
            "loss": losses.mean(), "client_loss": losses,
            "virtual_time": self._clock, "staleness": float(taus.mean()),
            "lr_mult": lr_mult})

    # -- the event loop --------------------------------------------------------

    def run(self, source, n_rounds):
        """Run until ``n_rounds`` more aggregations; returns
        ``(params, history)``.  In-flight work persists across calls."""
        if not callable(source):
            source = iter(source)
        last = self.round_idx + n_rounds - 1
        ticks = 0
        while self.round_idx <= last:
            if self._idle:
                with TraceAnnotation(tracing.ROUND):
                    self._dispatch(source)
            if not self._events:
                raise RuntimeError("fleet deadlock: no in-flight clients "
                                   "and nothing left to dispatch")
            # drain every event at the next virtual instant, in dispatch
            # order — so a full zero-spread cohort lands as one sync round
            t, _, _, _ = self._events[0]
            self._clock = t
            while self._events and self._events[0][0] == t:
                _, _, slot, rep = heapq.heappop(self._events)
                if rep is not None:
                    self.buffer.report(rep)
                self._idle.append(slot)
            while self.buffer.ready() and self.round_idx <= last:
                rec = self._aggregate()
                r = rec["round"]
                if self.eval_fn and (r == last or (
                        self.eval_every and r % self.eval_every == 0)):
                    # eval boundary: the sanctioned place to sync metrics
                    # repro-lint: disable=host-sync
                    rec.update({k: float(v) for k, v in
                                self.eval_fn(self.params).items()})
                self.history.append(rec)
                for cb in self.callbacks:
                    cb(r, self.params, rec)
                if self.log_every and (r % self.log_every == 0 or r == last):
                    # log boundary (trainer._record convention)
                    # repro-lint: disable=host-sync
                    extras = " ".join(f"{k} {float(v):.4f}"
                                      for k, v in rec.items()
                                      if k not in ("round", "loss")
                                      and np.ndim(v) == 0)
                    # repro-lint: disable=host-sync
                    msg = f"round {r:4d} loss {float(rec['loss']):.4f}"
                    self.log_fn(msg + (f"  {extras}" if extras else ""))
            ticks += 1
            if ticks > self.max_ticks:
                raise RuntimeError(
                    f"no round completed within {self.max_ticks} scheduler "
                    "ticks — dropout/timeout settings may be starving the "
                    "buffer")
        return self.params, self.history

    @property
    def losses(self) -> List[float]:
        # reporting accessor, not the event loop: sync is the point here
        # repro-lint: disable=host-sync
        return [float(h["loss"]) for h in self.history]
