"""Host mesh construction: (data, model) meshes over the visible devices.

Functions (not module constants) so importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the round's ``shard_map`` and
    ``with_sharding_constraint`` calls are written for the partitioner to
    propagate shardings, which ``Explicit`` axes (``jax.make_mesh``'s
    default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples).

    Clamps to the available device count — convenient for examples that
    should run anywhere.  Launch paths that *require* the requested shape
    (``--mesh``) go through :func:`host_mesh` instead, which raises.
    """
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _make_mesh((data, model), ("data", "model"))


def parse_mesh(spec: str):
    """``"4"`` → ``(4, 1)``; ``"4x2"`` → ``(4, 2)`` — (data, model) sizes."""
    parts = str(spec).lower().split("x")
    if not 1 <= len(parts) <= 2:
        raise ValueError(f"bad mesh spec {spec!r}; expected DATA or "
                         "DATAxMODEL, e.g. '4' or '4x2'")
    try:
        dims = tuple(int(p) for p in parts)
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}; expected DATA or "
                         "DATAxMODEL, e.g. '4' or '4x2'") from None
    if any(d < 1 for d in dims):
        raise ValueError(f"mesh spec {spec!r} has non-positive axis sizes")
    return dims if len(dims) == 2 else (dims[0], 1)


def host_mesh(spec: str):
    """Strict (data, model) host mesh from a ``--mesh`` spec string.

    Unlike :func:`make_host_mesh` this raises when fewer devices exist
    than the spec needs, with a hint about forcing host devices — a
    silently clamped mesh would make a '--mesh 4' run single-device.
    """
    data, model = parse_mesh(spec)
    need, have = data * model, len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"mesh {spec!r} needs {need} devices but only {have} are "
            "visible; on CPU, force host devices before JAX initializes "
            "(train.py --devices N, REPRO_HOST_DEVICES=N for pytest, or "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return _make_mesh((data, model), ("data", "model"))
