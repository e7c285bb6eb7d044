"""Names the round carries in a profiler trace, and the compile counter.

There is one tracing path: the JAX profiler's.  Device work is named by
``jax.named_scope``, which lands in every compiled instruction's
``op_name`` (and in the trace's ``tf_op`` stat) and survives ``grad`` and
remat, e.g. ``jit(step)/transpose(jvp(fed.client_phase))/...``.  Host work
is named by ``jax.profiler.TraceAnnotation`` spans, which land on the host
plane of the same trace.  Outside a profiler session both cost next to
nothing.

Scopes of the federated round (``core/fedavg.py``):

- ``fed.offsets``       window selection: per-client offsets or masks;
- ``fed.client_phase``  the K local steps of every client, up to its delta;
- ``fed.aggregate``     client deltas to new parameters: window extract,
  uplink, mean or scatter-add, the cross-chip exchange, ``w + lr * d``;
- ``fed.server_step``   the l2 projection and a stateful server optimizer.

``model.attention`` (``models/attention.py``) covers the attention core:
scores, softmax and the weighted values, not the q/k/v/o projections.
``model.moe`` (``models/moe.py``) covers the whole expert layer, with the
children ``model.moe.dispatch`` (routing and top-k, the balance loss, the
dense path's weighted combine) and ``model.moe.experts`` (the expert
matmuls, forward and backward).

Host spans of the trainers (``core/trainer.py``, ``fleet/server.py``):
``repro.round`` is one round's host work, with the children
``repro.round.put`` (the batch to the device) and ``repro.round.dispatch``
(the jitted call); ``repro.sync`` is a host sync at a log, eval or
checkpoint boundary.
"""
from __future__ import annotations

import functools
import threading
from typing import Callable, List

import jax

OFFSETS = "fed.offsets"
CLIENT_PHASE = "fed.client_phase"
AGGREGATE = "fed.aggregate"
SERVER_STEP = "fed.server_step"
ATTENTION = "model.attention"
MOE = "model.moe"
MOE_DISPATCH = "model.moe.dispatch"
MOE_EXPERTS = "model.moe.experts"

ROUND = "repro.round"
ROUND_PUT = "repro.round.put"
ROUND_DISPATCH = "repro.round.dispatch"
SYNC = "repro.sync"

#: Fires once per executable compiled or loaded from the persistent cache
#: (``jax._src.dispatch.BACKEND_COMPILE_EVENT``): once per jit cache miss.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def scoped(name: str) -> Callable:
    """Decorator: each call of the function runs under a fresh
    ``jax.named_scope(name)``.  (``jax.named_scope`` used as a decorator
    keeps one context object for every call, so a phase that nests in
    itself, as a bucket's offsets do in the round's, restores the wrong
    scope on its way out.)"""
    def decorate(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return decorate


_active = threading.local()


def _on_event(event: str, duration_secs: float, **kwargs) -> None:
    stack: List[CompileCounter] = getattr(_active, "stack", None)
    if stack and event == COMPILE_EVENT:
        stack[-1].count += 1


jax.monitoring.register_event_duration_secs_listener(_on_event)


class CompileCounter:
    """Counts the jit cache misses (compiled or loaded from the persistent
    cache) that happen on this thread while the counter is entered
    (``with counter: ...``, as often as needed); an inner counter takes
    the misses from an outer one."""

    def __init__(self):
        self.count = 0

    def __enter__(self):
        stack = getattr(_active, "stack", None)
        if stack is None:
            stack = _active.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _active.stack.pop()
        return False
