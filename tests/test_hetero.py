"""Heterogeneous per-client capacities in window mode.

The tentpole contract — the **bitwise composition pin**: a
``api.fed_round(..., capacities=)`` round with mixed per-client window
fractions equals the bucket-ordered composition of INDEPENDENTLY built
homogeneous rounds (one per width class), bit for bit, on both the
extract and the fused client-phase arms.  Around it: the uniform-
capacities degenerate case (``hetero is None``, plain round unchanged),
fused == extract agreement on a heterogeneous cohort, the server-opt
hetero path, construction-time validation, the ``AsyncTrainer`` M = N
allclose anchor (arrival-order aggregation is fp-reassociated, so the
hetero anchor is roundoff-level, not bitwise — documented on the
trainer), capacity rank-pairing of sampled clients to width slots, and
``FleetSimulator(capacities=)`` validation.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import SubmodelConfig, get_reduced_config
from repro.core import submodel as sm
from repro.core.masking import capacity_size

D_IN, D_H, C, K, MB = 6, 8, 4, 2, 3
CAPS = (1.0, 0.5, 0.5, 0.25)


def _maxdelta(t1, t2):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))


def _triple():
    def loss(w, b):
        h = jnp.tanh(b["x"] @ w["w1"] + w["b1"])
        r = h @ w["w2"] - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    kp = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(kp, (D_IN, D_H)) * 0.3,
              "b1": jnp.zeros((D_H,)),
              "w2": jax.random.normal(jax.random.fold_in(kp, 1),
                                      (D_H,)) * 0.3}
    ab = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    axes = {"w1": ("d_model", "d_ff"), "b1": ("d_ff",), "w2": ("d_ff",)}
    return (loss, ab, axes), params


def _scfg(**kw):
    base = dict(scheme="rolling", capacity=0.5, local_steps=K,
                clients_per_round=C, client_lr=0.1)
    base.update(kw)
    return SubmodelConfig(**base)


def _batch(clients=C, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": jnp.asarray(rng.standard_normal(
                (K, clients, MB, D_IN)).astype(np.float32)),
            "y": jnp.asarray(rng.standard_normal(
                (K, clients, MB)).astype(np.float32))}


def _items(n, clients=C, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((K, clients, MB, D_IN)).astype(
                np.float32),
             "y": rng.standard_normal((K, clients, MB)).astype(np.float32)}
            for _ in range(n)]


def _compose_delta_sum(model, scfg, buckets, params, batch, round_idx,
                       rng, **fed_kw):
    """The reference: per width class, build a homogeneous fed FROM
    SCRATCH (api.fed_round, not the hetero round's own clones), run its
    client phase on that bucket's batch lanes, and accumulate its f32
    scatter-add delta sum in descending-beta bucket order."""
    acc = None
    for b in buckets:
        bscfg = dataclasses.replace(scfg, capacity=b.beta,
                                    clients_per_round=len(b.idx),
                                    shared_window=False)
        ref = api.fed_round(model, bscfg, **fed_kw)
        lanes = jnp.asarray(b.idx, jnp.int32)
        bb = jax.tree_util.tree_map(
            lambda x: jnp.take(x, lanes, axis=1), batch)
        boff = ref._client_offsets(params, round_idx, rng)
        phase, sizes = ref._phase_for(boff)
        _, delta, _ = phase(params, bb, boff)
        part = ref._local_delta_sum(delta, boff, sizes)
        acc = part if acc is None else jax.tree_util.tree_map(
            lambda a, d: a + d, acc, part)
    return acc


# ---------------------------------------------------------------------------
# The bitwise composition pin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [{}, {"stagger": True},
                                {"scheme": "static"}],
                         ids=["rolling", "stagger", "static"])
def test_hetero_composes_from_homogeneous_rounds_bitwise(kw):
    """Extract arm (shape-agnostic MLP loss): the heterogeneous round is
    the per-bucket homogeneous composition, 0 ulp."""
    model, params = _triple()
    scfg = _scfg(**kw)
    fed = api.fed_round(model, scfg, capacities=CAPS)
    assert [(b.beta, list(b.idx)) for b in fed.hetero] == \
        [(1.0, [0]), (0.5, [1, 2]), (0.25, [3])]

    batch, key = _batch(), jax.random.PRNGKey(9)
    new, info = fed.round(params, batch, 0, key)

    acc = _compose_delta_sum(model, scfg, fed.hetero, params, batch, 0, key)
    ref = jax.tree_util.tree_map(
        lambda w, d: (w + scfg.server_lr * d / C).astype(w.dtype),
        params, acc)
    ref = sm.project_l2(ref, scfg.proj_radius)
    assert _maxdelta(new, ref) == 0.0
    assert info["client_loss"].shape == (K, C)
    assert bool(jnp.all(jnp.isfinite(info["client_loss"])))


def _tiny_transformer():
    from repro.data.synthetic import lm_batches
    from repro.models import build_model

    cfg = dataclasses.replace(
        get_reduced_config("tinyllama_1_1b"), n_layers=2, vocab=64,
        d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    batch = next(lm_batches(cfg.vocab, (K, C, 2), 16, seed=0))
    return m, params, batch


def test_hetero_fused_arm_composes_bitwise():
    """Fused arm (transformer with a windowed forward): same pin.  No
    beta = 1.0 bucket, so fused_forward='on' is honored bucket-wide."""
    caps = (0.5, 0.5, 0.25, 0.25)
    m, params, batch = _tiny_transformer()
    scfg = _scfg(client_lr=0.05)
    fed = api.fed_round(m, scfg, fused_forward="on", capacities=caps)
    assert all(b.fed.use_fused for b in fed.hetero)

    key = jax.random.PRNGKey(3)
    new, _ = fed.round(params, batch, 0, key)

    acc = _compose_delta_sum(m, scfg, fed.hetero, params, batch, 0, key,
                             fused_forward="on")
    ref = jax.tree_util.tree_map(
        lambda w, d: (w + scfg.server_lr * d / C).astype(w.dtype),
        params, acc)
    ref = sm.project_l2(ref, scfg.proj_radius)
    assert _maxdelta(new, ref) == 0.0


def test_hetero_fused_equals_extract_bitwise():
    """Per bucket the fused forward is pinned bitwise against
    extract/scatter (test_fedavg), so the bucket loop preserves it on a
    heterogeneous cohort — including a beta = 1.0 full-width bucket
    (which resolves fused_forward='auto' and takes the replica arm)."""
    m, params, batch = _tiny_transformer()
    scfg = _scfg(client_lr=0.05)
    f_on = api.fed_round(m, scfg, fused_forward="on", capacities=CAPS)
    f_off = api.fed_round(m, scfg, fused_forward="off", capacities=CAPS)
    key = jax.random.PRNGKey(3)
    p_on, i_on = f_on.round(params, batch, 0, key)
    p_off, i_off = f_off.round(params, batch, 0, key)
    assert _maxdelta(p_on, p_off) == 0.0
    np.testing.assert_array_equal(np.asarray(i_on["client_loss"]),
                                  np.asarray(i_off["client_loss"]))


def test_hetero_server_opt_round_composes_bitwise():
    """The server-opt arm: mean of the composed delta sum through
    ``server_opt.update``, same 0-ulp contract."""
    model, params = _triple()
    scfg = _scfg()
    fed = api.fed_round(model, scfg, server_opt="adam", capacities=CAPS)
    opt = fed.server_opt
    st = opt.init(fed.abstract)
    batch, key = _batch(), jax.random.PRNGKey(9)
    new, st2, info = fed.round_with_server_opt(params, st, batch, 0,
                                               rng=key)

    acc = _compose_delta_sum(model, scfg, fed.hetero, params, batch, 0, key)
    full_delta = jax.tree_util.tree_map(lambda d: d / C, acc)
    ref, _ = opt.update(params, full_delta, opt.init(fed.abstract))
    ref = sm.project_l2(ref, scfg.proj_radius)
    assert _maxdelta(new, ref) == 0.0
    assert info["client_loss"].shape == (K, C)


# ---------------------------------------------------------------------------
# Degenerate cases + the width formula
# ---------------------------------------------------------------------------


def test_uniform_capacities_keep_the_plain_round():
    """capacities all equal to scfg.capacity: no buckets, and the round
    is bitwise the no-capacities round."""
    model, params = _triple()
    fed_u = api.fed_round(model, _scfg(), capacities=[0.5] * C)
    fed_p = api.fed_round(model, _scfg())
    assert fed_u.hetero is None
    assert fed_u.capacities == (0.5,) * C    # normalized, still recorded
    batch, key = _batch(), jax.random.PRNGKey(2)
    p_u, _ = fed_u.round(params, batch, 0, key)
    p_p, _ = fed_p.round(params, batch, 0, key)
    assert _maxdelta(p_u, p_p) == 0.0


def test_capacity_size_is_the_shared_width_formula():
    """Bucket window sizes come from the same aligned-width formula
    ``make_scheme`` uses — one source of truth for beta -> width."""
    assert capacity_size(1.0, 8, 1) == 8
    assert capacity_size(0.5, 8, 1) == 4
    assert capacity_size(0.25, 8, 1) == 2
    assert capacity_size(0.3, 10, 4) == 4     # rounds down to align, floor a
    assert capacity_size(0.01, 8, 2) == 2     # never below min(align, n)
    model, _ = _triple()
    fed = api.fed_round(model, _scfg(), capacities=CAPS)
    key = ("d_ff", D_H)
    for b in fed.hetero:
        if b.beta == 1.0:     # full width: nothing windowed at all
            assert b.fed.scheme.sizes == {}
        else:
            assert b.fed.scheme.sizes[key] == capacity_size(b.beta, D_H, 1)


def test_hetero_validation():
    model, _ = _triple()
    with pytest.raises(ValueError, match="clients_per_round"):
        api.fed_round(model, _scfg(), capacities=[0.5, 0.5])
    with pytest.raises(ValueError, match=r"in \(0, 1\]"):
        api.fed_round(model, _scfg(), capacities=[1.0, 0.5, 0.5, 0.0])
    with pytest.raises(ValueError, match=r"in \(0, 1\]"):
        api.fed_round(model, _scfg(), capacities=[1.0, 0.5, 0.5, 1.5])
    with pytest.raises(ValueError, match="scheme='full'"):
        api.fed_round(model, _scfg(scheme="full"), capacities=CAPS)
    with pytest.raises(ValueError, match="shared_window"):
        api.fed_round(model, _scfg(shared_window=True), capacities=CAPS)
    fed = api.fed_round(model, _scfg())
    with pytest.raises(ValueError, match="mesh"):
        dataclasses.replace(fed, mesh=object(), capacities=CAPS)


# ---------------------------------------------------------------------------
# AsyncTrainer: the M = N anchor + capacity pairing
# ---------------------------------------------------------------------------


def test_async_hetero_m_equals_n_allclose():
    """M = N, zero-spread fleet: the async heterogeneous sequence replays
    the sync one to f32 roundoff (arrival-order aggregation reassociates
    the bucket-ordered sum, so this anchor is allclose, not bitwise)."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg(), capacities=CAPS)
    n = 4
    items = _items(n)

    tr = api.Trainer(fed, params, rng=jax.random.PRNGKey(5))
    p_sync, h_sync = tr.run(iter(items), n)
    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(5))
    p_async, h_async = at.run(iter(items), n)

    assert at._sizes == {}      # full-shaped deltas, cut to no window
    assert _maxdelta(p_sync, p_async) < 1e-5
    for rs, ra in zip(h_sync, h_async):
        np.testing.assert_allclose(np.asarray(rs["client_loss"]),
                                   np.asarray(ra["client_loss"]),
                                   rtol=1e-6, atol=1e-6)


def test_async_hetero_straggler_fleet_runs():
    """A real async regime over a capacity-annotated fleet: stragglers,
    M < N, rank-paired dispatch — finite losses, full history."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg(), capacities=CAPS)
    fleet = api.FleetSimulator(
        8, api.LatencyModel(jitter_sigma=0.3, straggler_frac=0.25, seed=1),
        capacities=[1.0, 0.9, 0.6, 0.5, 0.4, 0.3, 0.2, 0.1])
    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(7),
                          buffer_size=2, fleet=fleet)

    rng = np.random.default_rng(0)

    def source(ids):
        return {"x": rng.standard_normal((K, len(ids), MB, D_IN)).astype(
                    np.float32),
                "y": rng.standard_normal((K, len(ids), MB)).astype(
                    np.float32)}

    _, h = at.run(source, 6)
    assert len(h) == 6
    assert all(np.isfinite(float(r["loss"])) for r in h)


def test_pair_capacities_rank_matches_clients_to_slots():
    """Most capable sampled client -> widest dispatched slot; without a
    fleet capacity vector ids pass through untouched."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg(), capacities=CAPS)  # slots 1,.5,.5,.25
    fleet = api.FleetSimulator(
        6, capacities=[0.1, 0.9, 0.5, 0.7, 0.3, 0.2])
    at = api.AsyncTrainer(fed, params, fleet=fleet)
    paired = at._pair_capacities(np.array([0, 1, 2, 3]), [0, 1, 2, 3])
    # slot widths (1.0, .5, .5, .25) vs client caps (.1, .9, .5, .7):
    # 1 (cap .9) -> slot 0, 3 (.7) -> slot 1, 2 (.5) -> slot 2, 0 -> slot 3
    assert paired.tolist() == [1, 3, 2, 0]

    at_plain = api.AsyncTrainer(fed, params)   # zero-spread default fleet
    ids = np.array([2, 0, 1, 3])
    np.testing.assert_array_equal(
        at_plain._pair_capacities(ids, [0, 1, 2, 3]), ids)


def test_fleet_capacity_validation():
    with pytest.raises(ValueError, match="n_clients"):
        api.FleetSimulator(4, capacities=[0.5, 0.5])
    with pytest.raises(ValueError, match=r"in \(0, 1\]"):
        api.FleetSimulator(2, capacities=[0.5, 2.0])
