"""Parameter sharding policy — derived from the same semantic axis tags that
drive sub-model windowing.

Rules map axis name -> desired mesh axis; a leaf dim is sharded only if the
mesh axis divides it and the mesh axis is not already used by an earlier dim
of the same leaf (first-match-wins).  This one table produces:

* ``param_specs``      — PartitionSpecs for server parameters,
* ``constrain_tree``   — axis-aware activation/sub-model constraints used
  inside the fed round (client axis + per-leaf tags).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.ctx import current_policy
from repro.sharding.spmd import axis_size as _axis_size


def default_param_rules() -> dict:
    return {
        "vocab": "model",
        "d_ff": "model", "moe_d_ff": "model",
        "heads": "model", "kv_heads": "model",
        "experts": "model",
        "ssm_heads": "model",
        "mla_q_rank": "model",
        "channels": None,
        "clients": "data",
        "d_model": "data",               # ZeRO-3-style shard of the residual dim
    }


def leaf_spec(shape, axes, rules, mesh: Mesh) -> P:
    entries = []
    used = set()
    for dim, name in zip(shape, axes):
        cand = rules.get(name)
        flat = cand if isinstance(cand, tuple) else (cand,)
        if (cand is None or any(c in used for c in flat)
                or dim % _axis_size(mesh, cand) != 0
                or _axis_size(mesh, cand) > dim):
            entries.append(None)
        else:
            entries.append(cand)
            used.update(flat)
    return P(*entries)


def param_specs(abstract, axes_tree, rules, mesh: Mesh):
    def walk(p, a):
        if isinstance(p, dict):
            return {k: walk(p[k], a[k]) for k in p}
        return leaf_spec(p.shape, a, rules, mesh)
    return walk(abstract, axes_tree)


def round_input_shardings(mesh: Mesh, axis, abstract, batch):
    """``NamedSharding`` placement for a mesh fed round's inputs.

    Server params are replicated (every shard trains clients against the
    same full tree); batch leaves are ``[K, C, ...]`` and split on the
    client mesh ``axis``.  Used by ``benchmarks/run.py`` and launch code
    to ``device_put`` round inputs so the jitted ``shard_map`` round
    starts from the right placement instead of resharding on entry.
    """
    rep = NamedSharding(mesh, P())
    params_sh = jax.tree_util.tree_map(lambda _: rep, abstract)
    batch_sh = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(None, axis)), batch)
    return params_sh, batch_sh


def constrain_tree(tree, axes_tree, leading=("clients",)):
    """Constrain a (possibly client-stacked) param tree by its axis tags,
    using the installed activation policy's mesh + rules."""
    pol = current_policy()
    if pol is None:
        return tree

    def walk(t, a):
        if isinstance(t, dict):
            return {k: walk(t[k], a[k]) for k in t}
        axes = tuple(leading) + tuple(a)
        spec = leaf_spec(t.shape, axes, pol.rules, pol.mesh)
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(pol.mesh, spec))

    return walk(tree, axes_tree)
