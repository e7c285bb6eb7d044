"""Weights made from the run's seed, on the device, in one jitted call.

The benchmark makes the weights itself (not through the program's own
``Model.init``), so the plain reference can make the same weights from the
same seed without taking anything the program made.  The layout is the
program's parameter tree; the scales follow the usual rules: norms at 1,
the embedding at 0.02, each attention output projection at
0.02 / sqrt(2 x layers), every other matrix at 1 / sqrt(fan-in).  An
expert stack (``[E, in, out]`` under a ``moe`` group, after the layer
axis) takes each expert's own fan-in, ``in``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: Top-level groups whose leaves carry a leading stacked-layer dimension.
STACKS = ("layers", "dense_layers", "moe_layers", "ssm_layers")


def seed32(seed: int) -> np.uint32:
    """A run seed of any size folded to the 32 bits a PRNG key takes."""
    return np.random.SeedSequence([seed % 2**64, 7]).generate_state(1)[0]


def leaves(tree, prefix=()):
    """``[(path, leaf)]`` in sorted path order."""
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(leaves(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), v))
    return out


def path_name(path) -> str:
    return "/".join(path)


def _scale(path, shape, n_layers):
    """Standard deviation of the leaf, or None for a norm weight (ones)."""
    name = path[-1]
    stacked = path[0] in STACKS
    rank = len(shape) - stacked
    if rank == 1:
        return None
    if name == "embed":
        return 0.02
    if name == "wo":
        return 0.02 / math.sqrt(2 * max(n_layers, 1))
    if "moe" in path[:-1] and rank == 3:
        return 1.0 / math.sqrt(shape[-2])
    return 1.0 / math.sqrt(shape[1] if stacked else shape[0])


def _unflatten(items):
    tree = {}
    for path, v in items:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def make_init(abstract, n_layers, out_shardings=None):
    """Jitted ``init(seed32) -> params`` for the tree ``abstract``."""
    spec = [(p, a.shape, a.dtype) for p, a in leaves(abstract)]

    def init(s):
        keys = jax.random.split(jax.random.PRNGKey(s), len(spec))
        items = []
        for key, (path, shape, dtype) in zip(keys, spec):
            scale = _scale(path, shape, n_layers)
            if scale is None:
                items.append((path, jnp.ones(shape, dtype)))
            else:
                items.append((path, (jax.random.normal(key, shape, jnp.float32)
                                     * scale).astype(dtype)))
        return _unflatten(items)

    return jax.jit(init, out_shardings=out_shardings)


def make_change_norms(init):
    """Jitted ``norms(params, seed32) -> {path: ||params - init(seed)||}``:
    how far each leaf has moved from the weights the seed made."""

    def norms(params, s):
        p0 = init(s)
        return {path_name(p): jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                for (p, a), (_, b) in zip(leaves(params), leaves(p0))}

    return jax.jit(norms)
