"""Activation-sharding context.

Models are written mesh-agnostically; they annotate activations with
*semantic* axis names via :func:`constrain`.  Launch code installs an
:class:`ActivationPolicy` (mesh + semantic->mesh-axis rules); outside any
policy the calls are no-ops, so unit tests and CPU examples never touch
device state.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.spmd import axis_size as _axis_size

_STATE = threading.local()


class ActivationPolicy:
    def __init__(self, mesh: Mesh, rules: dict):
        """rules: semantic axis name -> mesh axis (str | tuple | None)."""
        self.mesh = mesh
        self.rules = dict(rules)

    def spec(self, axes) -> P:
        entries, used = [], set()
        for a in axes:
            cand = self.rules.get(a) if a else None
            flat = cand if isinstance(cand, tuple) else (cand,)
            if cand is None or any(c in used for c in flat):
                entries.append(None)
            else:
                entries.append(cand)
                used.update(flat)
        return P(*entries)


def current_policy() -> Optional[ActivationPolicy]:
    return getattr(_STATE, "policy", None)


@contextlib.contextmanager
def activation_policy(policy: Optional[ActivationPolicy]):
    prev = current_policy()
    _STATE.policy = policy
    try:
        yield
    finally:
        _STATE.policy = prev


def constrain(x, *axes):
    """Annotate ``x`` with semantic axis names (None = unconstrained dim)."""
    pol = current_policy()
    if pol is None or x.ndim != len(axes):
        return x
    spec = pol.spec(axes)
    # drop entries that do not divide the actual dim
    ent = [e if (e is not None and d % _axis_size(pol.mesh, e) == 0) else None
           for e, d in zip(spec, x.shape)]
    if all(e is None for e in ent):
        # an all-None constraint is NOT a no-op — it pins the value
        # replicated; leave the partitioner free instead
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pol.mesh, P(*ent)))


# default rule-sets -----------------------------------------------------------

def default_rules() -> dict:
    return {
        "batch": "data", "clients": "data", "seq": None,
        "d_model": None, "heads": "model", "kv_heads": "model",
        "d_ff": "model", "moe_d_ff": "model", "experts": "model",
        "vocab": "model", "ssm_heads": None,
    }
