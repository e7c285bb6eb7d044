"""Window extraction / scatter on axis-tagged parameter trees.

``extract`` materializes a client's *compact* sub-model (contiguous slices on
every windowed axis — the TPU-native form of the paper's m ⊙ w),
``scatter_delta`` places a sub-model delta back into a full-shaped zero tree
(the delta form of the paper's fill-in averaging), and ``add_at_window`` adds
one into a full-shaped accumulator in place.

Offsets may be traced (per-client, per-round); window sizes are static.
These functions are vmap-safe over client offsets.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.masking import AxisKey


def _windowed_dims(shape, axes, sizes: Dict[AxisKey, int]):
    out = []
    for d, name in enumerate(axes):
        key = (name, int(shape[d]))
        if key in sizes and sizes[key] < shape[d]:
            out.append((d, key))
    return out


def extract(params, axes_tree, offsets, sizes):
    """Slice every leaf down to its client window."""

    def f(leaf, axes):
        for d, key in _windowed_dims(leaf.shape, axes, sizes):
            leaf = jax.lax.dynamic_slice_in_dim(leaf, offsets[key],
                                                sizes[key], axis=d)
        return leaf

    return _tree_map_with_axes(f, params, axes_tree)


def scatter_delta(delta, full_abstract, axes_tree, offsets, sizes):
    """Place sub-model delta into a full-shaped zero tree at the window."""

    def f(sub, full, axes):
        out = jnp.zeros(full.shape, sub.dtype)
        starts = [0] * out.ndim
        for d, key in _windowed_dims(full.shape, axes, sizes):
            starts[d] = offsets[key]
        return jax.lax.dynamic_update_slice(out, sub, tuple(starts))

    return _tree_map_with_axes2(f, delta, full_abstract, axes_tree)


def add_at_window(acc, delta, axes_tree, offsets, sizes):
    """``acc`` (full-shaped) plus ``delta`` (cut to ``sizes``) at its
    window, in place: no zero-padded full-shaped copy of ``delta``, and
    bitwise the sum with the scattered delta (``x + 0 == x``)."""

    def f(a, d, axes):
        d = d.astype(a.dtype)
        dims = _windowed_dims(a.shape, axes, sizes)
        if not dims:
            return a + d
        starts = [0] * a.ndim
        for dim, key in dims:
            starts[dim] = offsets[key]
        cur = jax.lax.dynamic_slice(a, starts, d.shape)
        return jax.lax.dynamic_update_slice(a, cur + d, starts)

    return _tree_map_with_axes2(f, acc, delta, axes_tree)


def window_mask(full_abstract, axes_tree, offsets, sizes, dtype=jnp.float32):
    """Dense 0/1 masks equivalent to the window (for mask-mode equivalence)."""

    def f(full, axes):
        m = jnp.ones(full.shape, dtype)
        for d, key in _windowed_dims(full.shape, axes, sizes):
            idx = jnp.arange(full.shape[d])
            sel = (idx >= offsets[key]) & (idx < offsets[key] + sizes[key])
            shape = [1] * full.ndim
            shape[d] = full.shape[d]
            m = m * sel.reshape(shape).astype(dtype)
        return m

    return _tree_map_with_axes(f, full_abstract, axes_tree)


def sub_abstract(full_abstract, axes_tree, sizes):
    """ShapeDtypeStructs of the compact sub-model (static shapes)."""

    def f(full, axes):
        shape = list(full.shape)
        for d, key in _windowed_dims(full.shape, axes, sizes):
            shape[d] = sizes[key]
        return jax.ShapeDtypeStruct(tuple(shape), full.dtype)

    return _tree_map_with_axes(f, full_abstract, axes_tree)


# -- helpers ------------------------------------------------------------------


def _tree_map_with_axes(f, tree, axes_tree):
    if isinstance(tree, dict):
        return {k: _tree_map_with_axes(f, tree[k], axes_tree[k])
                for k in tree}
    return f(tree, axes_tree)


def _tree_map_with_axes2(f, a, b, axes_tree):
    if isinstance(a, dict):
        return {k: _tree_map_with_axes2(f, a[k], b[k], axes_tree[k])
                for k in a}
    return f(a, b, axes_tree)
