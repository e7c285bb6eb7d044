"""The single training loop over jitted fed rounds.

Every entry point (launch/train, paper_protocol, benchmarks, examples) used
to re-roll its own ``for r in range(rounds)`` loop; :class:`Trainer` owns
that loop once: rng splitting, the jitted step (plain round or the
server-optimizer round when one is attached), per-round metrics history,
eval / logging / checkpoint callbacks, and ``--rounds`` pacing with resume
(``trainer.run`` can be called repeatedly; ``round_idx`` persists).

Batch iterators yield either a batch dict (leaves [K, C, ...]) or a
``(batch, round_kwargs)`` pair — the kwargs are forwarded to the round
(e.g. mask mode's per-round ``capacities``).

Each round's host work is a ``repro.round`` profiler span (children
``repro.round.put`` and ``repro.round.dispatch``) and each host sync a
``repro.sync`` span (:mod:`repro.tracing`); :attr:`Trainer.compiles`
counts the round's jit cache misses.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro import tracing


def _record(round_idx, metrics) -> Dict[str, Any]:
    """Per-round history record: every metric stays a device array.

    ``float(v)`` here would block on the previous round's result and
    serialize dispatch of the next jitted round; the host sync happens only
    at log/eval/checkpoint boundaries and in :attr:`Trainer.losses`."""
    return {"round": round_idx, **metrics}


@dataclass
class Trainer:
    """Drives ``fed.round`` (or ``fed.round_with_server_opt``) for N rounds.

    Construct with a round object from :func:`repro.api.fed_round` and the
    initial params, then call :meth:`run` with a batch iterator (leaves
    ``[K, C, ...]``; items may be ``(batch, round_kwargs)`` pairs)::

        fed = api.fed_round(model, scfg, server_opt="adam")
        trainer = api.Trainer(fed, params, rng=0, log_every=10)
        params, history = trainer.run(batches, n_rounds=50)
        trainer.run(batches, 50)          # resumes at round 50

    When the round carries a server optimizer (or ``server_opt=`` is
    passed here), the trainer steps ``round_with_server_opt`` and carries
    ``opt_state`` across rounds.  ``history`` keeps per-round metric
    records as device arrays (no host sync in the loop);
    :attr:`losses` materializes the float loss curve once.

    Callbacks run after each round as ``cb(round_idx, params, record)``
    where ``record`` is the metrics dict appended to ``history`` (eval
    metrics merged in on eval rounds — see ``eval_fn`` / ``eval_every``).
    Checkpoint periodically via :func:`checkpoint_callback`; ``start_round``
    resumes a restored schedule mid-way.

    :attr:`compiles` counts the jitted round's cache misses, each a
    compile or a load from the persistent cache: 1 after the first round,
    and one more for every new batch shape or round kwarg.
    """

    fed: Any                              # WindowFedAvg | MaskFedAvg
    params: Any
    rng: Any = None                       # PRNGKey (int seeds accepted)
    server_opt: Any = None                # overrides fed.server_opt
    jit: bool = True
    callbacks: Sequence[Callable] = ()
    eval_fn: Optional[Callable] = None    # (params) -> {name: scalar}
    eval_every: int = 0                   # 0 = never (eval_fn still runs last)
    log_every: int = 0                    # 0 = silent
    log_fn: Callable = print
    start_round: int = 0                  # resume mid-schedule (checkpoints)

    round_idx: int = field(default=0, init=False)
    history: List[Dict] = field(default_factory=list, init=False)
    opt_state: Any = field(default=None, init=False)
    _step: Any = field(default=None, init=False)
    _compiles: Any = field(default=None, init=False)

    def __post_init__(self):
        self.round_idx = self.start_round
        if self.rng is None:
            self.rng = jax.random.PRNGKey(0)
        elif isinstance(self.rng, int):
            self.rng = jax.random.PRNGKey(self.rng)
        if self.server_opt is None:
            self.server_opt = getattr(self.fed, "server_opt", None)
        if self.server_opt is not None:
            self.opt_state = self.server_opt.init(
                getattr(self.fed, "abstract", None) or self.params)

        if self.server_opt is None:
            step = self.fed.round
        else:
            def step(params, opt_state, batch, round_idx, rng, **kw):
                return self.fed.round_with_server_opt(
                    params, opt_state, batch, round_idx, self.server_opt,
                    rng=rng, **kw)
        self._step = jax.jit(step) if self.jit else step
        self._compiles = tracing.CompileCounter()

    @property
    def compiles(self) -> int:
        """Jit cache misses of the round so far (compiled or loaded)."""
        return self._compiles.count

    def step(self, batch, round_kwargs=None):
        """Run exactly one round on ``batch``; returns the history record."""
        r, kw = self.round_idx, dict(round_kwargs or {})
        with StepTraceAnnotation(tracing.ROUND, step_num=r):
            self.rng, sub = jax.random.split(self.rng)
            with TraceAnnotation(tracing.ROUND_PUT):
                if isinstance(batch, dict):
                    batch = {k: jax.numpy.asarray(v)
                             for k, v in batch.items()}
            with TraceAnnotation(tracing.ROUND_DISPATCH), self._compiles:
                if self.server_opt is None:
                    self.params, metrics = self._step(self.params, batch, r,
                                                      sub, **kw)
                else:
                    self.params, self.opt_state, metrics = self._step(
                        self.params, self.opt_state, batch, r, sub, **kw)
        rec = _record(r, metrics)
        self.round_idx += 1
        return rec

    def run(self, batch_iter, n_rounds):
        """Train for ``n_rounds``; returns ``(params, history)``."""
        batch_iter = iter(batch_iter)
        last = self.round_idx + n_rounds - 1
        for _ in range(n_rounds):
            item = next(batch_iter)
            batch, kw = item if isinstance(item, tuple) else (item, None)
            rec = self.step(batch, kw)
            r = rec["round"]
            if self.eval_fn and (r == last or (
                    self.eval_every and r % self.eval_every == 0)):
                # eval boundary: the sanctioned place to sync metrics
                with TraceAnnotation(tracing.SYNC):
                    # repro-lint: disable=host-sync
                    rec.update({k: float(v) for k, v in
                                self.eval_fn(self.params).items()})
            self.history.append(rec)
            for cb in self.callbacks:
                cb(r, self.params, rec)
            if self.log_every and (r % self.log_every == 0 or r == last):
                # the log boundary is where the host sync is allowed
                with TraceAnnotation(tracing.SYNC):
                    # repro-lint: disable=host-sync
                    extras = " ".join(f"{k} {float(v):.4f}"
                                      for k, v in rec.items()
                                      if k not in ("round", "loss")
                                      and np.ndim(v) == 0)
                    # repro-lint: disable=host-sync
                    loss = float(rec["loss"])
                self.log_fn(f"round {r:4d} loss {loss:.4f}"
                            + (f"  {extras}" if extras else ""))
        return self.params, self.history

    @property
    def losses(self) -> List[float]:
        # reporting accessor, not the hot loop: sync is the point here
        with TraceAnnotation(tracing.SYNC):
            # repro-lint: disable=host-sync
            return [float(h["loss"]) for h in self.history]


def checkpoint_callback(path, every=0, meta=None):
    """Trainer callback that checkpoints params (+ running loss history).

    ``every=0`` saves on every call (use with small round counts or pair
    with ``every=N`` for periodic saves).
    """
    losses: List[float] = []

    def cb(round_idx, params, record):
        from repro.checkpoint.checkpoint import save
        with TraceAnnotation(tracing.SYNC):
            losses.append(float(record["loss"]))
            if every and round_idx % every != 0:
                return
            save(path, params, {**(meta or {}), "round": round_idx + 1,
                                "history": losses})

    return cb
