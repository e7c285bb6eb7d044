"""``shard_map`` + mesh-axis utilities.

One home for the two helpers the sharding stack kept duplicating:

* :func:`shard_map` — the manual-SPMD entry point (``jax.shard_map``
  with ``check_vma`` off).  Used by the mesh fed round
  (``core/fedavg.py``) and the context-parallel attention path
  (``models/attention.py``).
* :func:`axis_size` — size of a (possibly tuple) mesh axis; previously
  copy-pasted as ``_axis_size`` in both ``sharding/ctx.py`` and
  ``sharding/policy.py``.

Plus :func:`resolve_client_axis`, the validation front door for
``api.fed_round(..., mesh=..., spmd_axis=...)``: a bad axis name fails
here with a readable error instead of an opaque partitioner failure.
"""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check
    (``check_vma``) off."""
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)


def axis_size(mesh, name) -> int:
    """Total size of mesh axis ``name`` (None = 1, tuples multiply)."""
    if name is None:
        return 1
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= mesh.shape[a]
        return n
    return mesh.shape[name]


def resolve_client_axis(mesh, spmd_axis=None):
    """The mesh axis carrying the per-client dim of a fed round.

    ``None`` derives it (``clients`` if the mesh has one, else ``data``,
    else the leading axis).  An explicit name (or tuple of names) must
    exist on the mesh — this is where ``api.fed_round`` turns a typo'd
    axis into a real ``ValueError``.
    """
    names = tuple(mesh.axis_names)
    if spmd_axis is None:
        for cand in ("clients", "data"):
            if cand in names:
                return cand
        return names[0]
    flat = spmd_axis if isinstance(spmd_axis, tuple) else (spmd_axis,)
    missing = [a for a in flat if a not in names]
    if missing:
        raise ValueError(
            f"spmd_axis {spmd_axis!r} names mesh axes {missing} that the "
            f"mesh does not have (mesh axes: {names}); pass one of the "
            f"mesh's axis names or spmd_axis=None to derive it")
    return spmd_axis
