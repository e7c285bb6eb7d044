"""Pluggable client (local-step) optimizers for the fed round.

The paper's local update is plain SGD on the sub-model.  ``ClientOpt``
makes that update a plug-point so both executable forms of the round
(window mode's compact sub-models and mask mode's dense m ⊙ w) can run
richer local optimizers without touching the round code:

* ``client_sgd``      — the paper's update (default); routes through the
  dispatch layer: ``dispatch.sgd_step`` (one XLA elementwise arm on every
  backend) in window mode, ``dispatch.masked_sgd`` (pallas == jnp per
  local step) in mask mode.
* ``client_momentum`` — heavy-ball local steps; the velocity lives in the
  scan carry and is discarded at round end (state is round-local, exactly
  like the paper's client state).
* ``client_proximal`` — FedProx: g + mu (w − w0) with w0 the round-start
  sub-model, damping client drift under heterogeneous data.

The ``update`` contract mirrors the round's inner scan: state is a pytree
shaped like the (stacked, per-client) sub-model, gradients arrive already
masked in mask mode (chain rule of m ⊙ w), and ``masks`` is forwarded so
elementwise steps can stay on the fused masked kernels.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch


class ClientOpt(NamedTuple):
    """(init, update) pair over (stacked) sub-model pytrees.

    init:   (sub0) -> state                        # round-start sub-models
    update: (params, grads, state, lr, *, masks=None, backend=None)
            -> (new_params, new_state)
    """

    name: str
    init: Callable
    update: Callable


def _dispatched_step(params, grads, lr, masks, backend):
    if masks is None:
        return dispatch.sgd_step(params, grads, lr, backend=backend)
    return dispatch.masked_sgd(params, masks, grads, lr, backend=backend)


def client_sgd():
    """The paper's local update: w ← w − η·g (masked in mask mode)."""

    def init(sub0):
        return ()

    def update(params, grads, state, lr, *, masks=None, backend=None):
        return _dispatched_step(params, grads, lr, masks, backend), state

    return ClientOpt("sgd", init, update)


def client_momentum(beta=0.9):
    """Heavy-ball local steps: v ← β·v + g; w ← w − η·v."""

    def init(sub0):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), sub0)

    def update(params, grads, state, lr, *, masks=None, backend=None):
        v = jax.tree_util.tree_map(
            lambda vv, g: beta * vv + g.astype(jnp.float32), state, grads)
        return _dispatched_step(params, v, lr, masks, backend), v

    return ClientOpt("momentum", init, update)


def client_proximal(mu=0.01):
    """FedProx local steps: w ← w − η·(g + μ (w − w0)), w0 = round start."""

    def init(sub0):
        return {"anchor": sub0}

    def update(params, grads, state, lr, *, masks=None, backend=None):
        g = jax.tree_util.tree_map(
            lambda gr, w, w0: gr + mu * (w - w0).astype(gr.dtype),
            grads, params, state["anchor"])
        return _dispatched_step(params, g, lr, masks, backend), state

    return ClientOpt("proximal", init, update)


CLIENT_OPTS = {"sgd": client_sgd, "momentum": client_momentum,
               "proximal": client_proximal}


def resolve_client_opt(client_opt) -> ClientOpt:
    """None → default SGD; str → registry lookup; ClientOpt → itself."""
    if client_opt is None:
        return client_sgd()
    if isinstance(client_opt, str):
        try:
            return CLIENT_OPTS[client_opt]()
        except KeyError:
            raise ValueError(
                f"unknown client optimizer {client_opt!r}; expected one of "
                f"{sorted(CLIENT_OPTS)}") from None
    return client_opt
