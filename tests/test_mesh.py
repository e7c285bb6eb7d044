"""Mesh scale-out of the fed round (shard_map over the client axis).

The tentpole contract: a ``WindowFedAvg`` round built with ``mesh=`` runs
under ``shard_map`` with clients split over the mesh's data axis and is
**bitwise-equal** to the single-device (``mesh=None``) round in the
default ``mesh_agg="gather"`` mode — fused and extract client phases,
shared and per-client (staggered) windows, plain and server-opt rounds.
``mesh_agg="psum"`` is the scalable arm: exact losses, params equal to fp
roundoff only.

Multi-device cases need forced host devices, which must reach XLA before
the backend initializes — run with ``REPRO_HOST_DEVICES=4`` (see
tests/conftest.py); without it the >1-device cases skip.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.analysis import hlo_check
from repro.configs.base import SubmodelConfig, get_reduced_config
from repro.data.synthetic import lm_batches
from repro.launch.mesh import host_mesh
from repro.models import build_model

MESHES = [1, 2, 4]


def _mesh(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (run with REPRO_HOST_DEVICES={n})")
    return host_mesh(str(n))


def _maxdelta(t1, t2):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))


def _tiny_model():
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2, vocab=64,
                  d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16)
    return cfg, build_model(cfg, remat=False)


def _lm_setup(stagger=False):
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          stagger=stagger)
    it = lm_batches(cfg.vocab, (2, 4, 2), 16, seed=0)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    return m, params, scfg, batch


def _triple():
    """Least-squares triple: no window-aware loss, so the round takes the
    extract-based client phase — the arm the transformer tests skip."""
    def loss(w, batch):
        r = w["w"] - batch["target"].mean(-1)
        return 0.5 * jnp.mean(r * r), {}
    abstract = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
    params = {"w": jnp.linspace(0.0, 1.0, 8)}
    batch = {"target": jnp.arange(2 * 4 * 3, dtype=jnp.float32
                                  ).reshape(2, 4, 3)}
    return (loss, abstract, {"w": ("d_ff",)}), params, batch


def _run_rounds(fed, params, batch, n=2, **kw):
    step = jax.jit(fed.round)
    outs = []
    for r in range(n):
        params, metrics = step(params, batch, r, jax.random.PRNGKey(1), **kw)
        outs.append((params, metrics))
    return outs


def _assert_rounds_bitwise(fed_a, fed_b, params, batch):
    for (pa, ma), (pb, mb) in zip(_run_rounds(fed_a, params, batch),
                                  _run_rounds(fed_b, params, batch)):
        assert _maxdelta(pa, pb) == 0.0
        np.testing.assert_array_equal(np.asarray(ma["client_loss"]),
                                      np.asarray(mb["client_loss"]))


# -- the acceptance property: mesh round == single-device round, 0 ulp --------


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("stagger", [False, True],
                         ids=["rolling", "staggered"])
def test_mesh_fused_round_bitwise_equals_single_device(n, stagger):
    mesh = _mesh(n)
    m, params, scfg, batch = _lm_setup(stagger=stagger)
    single = api.fed_round(m, scfg, fused_forward="on")
    sharded = api.fed_round(m, scfg, fused_forward="on", mesh=mesh)
    assert single.use_fused and sharded.use_fused
    assert sharded.spmd_axis == "data"
    _assert_rounds_bitwise(single, sharded, params, batch)


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("scheme,stagger", [
    ("rolling", False),       # shared window: mean-then-scatter arm
    ("rolling", True),        # per-client windows: scatter-add scan arm
    ("full", False),          # empty offsets dict under shard_map
])
def test_mesh_extract_round_bitwise_equals_single_device(n, scheme, stagger):
    mesh = _mesh(n)
    model, params, batch = _triple()
    scfg = SubmodelConfig(scheme=scheme, capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.3,
                          stagger=stagger)
    single = api.fed_round(model, scfg)
    sharded = api.fed_round(model, scfg, mesh=mesh)
    assert not sharded.use_fused
    _assert_rounds_bitwise(single, sharded, params, batch)


@pytest.mark.parametrize("n", MESHES)
def test_mesh_fused_equals_mesh_extract(n):
    """Per shard, the fused == extract contract is the single-device one."""
    mesh = _mesh(n)
    m, params, scfg, batch = _lm_setup(stagger=True)
    fused = api.fed_round(m, scfg, fused_forward="on", mesh=mesh)
    extract = api.fed_round(m, scfg, fused_forward="off", mesh=mesh)
    _assert_rounds_bitwise(fused, extract, params, batch)


@pytest.mark.parametrize("n", MESHES)
def test_mesh_server_opt_round_bitwise_equals_single_device(n):
    mesh = _mesh(n)
    m, params, scfg, batch = _lm_setup()
    single = api.fed_round(m, scfg, server_opt="adam")
    sharded = api.fed_round(m, scfg, server_opt="adam", mesh=mesh)
    st_a = single.server_opt.init(params)
    st_b = sharded.server_opt.init(params)
    for r in range(2):
        pa, st_a, ma = jax.jit(single.round_with_server_opt)(
            params, st_a, batch, r, rng=jax.random.PRNGKey(1))
        pb, st_b, mb = jax.jit(sharded.round_with_server_opt)(
            params, st_b, batch, r, rng=jax.random.PRNGKey(1))
        assert _maxdelta(pa, pb) == 0.0
        assert _maxdelta(st_a, st_b) == 0.0
        np.testing.assert_array_equal(np.asarray(ma["client_loss"]),
                                      np.asarray(mb["client_loss"]))
        params = pa


# -- the scalable arm: psum aggregation ---------------------------------------


@pytest.mark.parametrize("n", MESHES)
def test_mesh_psum_close_losses_exact(n):
    mesh = _mesh(n)
    m, params, scfg, batch = _lm_setup(stagger=True)
    single = api.fed_round(m, scfg, fused_forward="on")
    psum = api.fed_round(m, scfg, fused_forward="on", mesh=mesh,
                         mesh_agg="psum")
    (pa, ma), = _run_rounds(single, params, batch, n=1)
    (pb, mb), = _run_rounds(psum, params, batch, n=1)
    # client losses are computed pre-aggregation and gathered: exact
    np.testing.assert_array_equal(np.asarray(ma["client_loss"]),
                                  np.asarray(mb["client_loss"]))
    # params differ only by cross-shard fp reassociation
    assert _maxdelta(pa, pb) < 1e-5


# -- the round really is sharded ----------------------------------------------


def test_mesh_round_hlo_contains_all_gather():
    mesh = _mesh(2)
    m, params, scfg, batch = _lm_setup()
    sharded = api.fed_round(m, scfg, fused_forward="on", mesh=mesh)
    hlo = hlo_check.compiled_text(sharded.round, params, batch, 0,
                                  jax.random.PRNGKey(1))
    assert hlo_check.has_collective(hlo, "all-gather")


@pytest.mark.parametrize("n", MESHES)
@pytest.mark.parametrize("agg", ["gather", "psum"])
def test_mesh_round_phase_scopes(n, agg):
    """The sharded round keeps the phase scopes, and the cross-chip
    exchange (the deltas' all-gather or psum, the losses' all-gather) is
    aggregation work."""
    mesh = _mesh(n)
    m, params, scfg, batch = _lm_setup()
    sharded = api.fed_round(m, scfg, fused_forward="on", mesh=mesh,
                            mesh_agg=agg)
    hlo = hlo_check.compiled_text(sharded.round, params, batch, 0,
                                  jax.random.PRNGKey(1))
    assert {"fed.offsets", "fed.client_phase",
            "fed.aggregate"} <= hlo_check.scopes(hlo)
    exchange = [line for line in hlo.splitlines()
                if " all-gather(" in line or " all-reduce(" in line
                or "all-gather-start(" in line or "all-reduce-start(" in line]
    assert len(exchange) > 0 or n == 1
    for line in exchange:
        names = hlo_check.op_names(line)
        assert names and all(
            hlo_check.outermost_scope(name, "fed.") == "fed.aggregate"
            for name in names), line


# -- validation (no extra devices needed) -------------------------------------


def _one_device_mesh():
    return host_mesh("1")


def test_mesh_rejects_unknown_axis():
    model, _, _ = _triple()
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
                          clients_per_round=4)
    with pytest.raises(ValueError, match="mesh does not have"):
        api.fed_round(model, scfg, mesh=_one_device_mesh(),
                      spmd_axis="clients")


def test_mesh_rejects_indivisible_clients():
    model, _, _ = _triple()
    mesh = _mesh(2)
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
                          clients_per_round=3)
    with pytest.raises(ValueError, match="divisible"):
        api.fed_round(model, scfg, mesh=mesh)


def test_mesh_rejects_mask_mode():
    model, _, _ = _triple()
    scfg = SubmodelConfig(scheme="bernoulli", capacity=0.5,
                          clients_per_round=4)
    with pytest.raises(ValueError, match="window mode only"):
        api.fed_round(model, scfg, mesh=_one_device_mesh())


def test_mesh_rejects_unknown_agg():
    model, _, _ = _triple()
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
                          clients_per_round=4)
    with pytest.raises(ValueError, match="mesh_agg"):
        api.fed_round(model, scfg, mesh=_one_device_mesh(),
                      mesh_agg="reduce")


def test_host_mesh_raises_without_devices():
    from repro.launch import mesh as lm
    if len(jax.devices()) >= 64:
        pytest.skip("unexpectedly many devices")
    with pytest.raises(RuntimeError, match="force host devices"):
        lm.host_mesh("64")


def test_parse_mesh():
    from repro.launch.mesh import parse_mesh
    assert parse_mesh("4") == (4, 1)
    assert parse_mesh("4x2") == (4, 2)
    with pytest.raises(ValueError):
        parse_mesh("4x2x1")
    with pytest.raises(ValueError):
        parse_mesh("abc")
