"""repro.fleet: the async federated round server.

The tentpole contract — the **sync-equivalence anchor**: an
``api.AsyncTrainer`` with M = N (buffer = clients_per_round), a
zero-spread fleet, and no dropouts replays the synchronous
``api.Trainer`` round sequence **bitwise** (0 ulp f32) — plain rounds,
server-opt rounds, and staggered per-client windows alike.  Around it:
the FedBuff staleness-policy contract (w(0) = 1 exactly, monotone
non-increasing), the epoch-permutation sampler (arXiv 2201.11066),
deterministic fleet simulation (latency/straggler/dropout/timeout
draws), bit-identical replay of a full async regime, and the layering
policy that ``src/repro/fleet`` never constructs rounds (it drives the
round object built by ``repro.api.fed_round``).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import SubmodelConfig, get_reduced_config
from repro.fleet.buffer import (STALENESS_POLICIES, ClientReport,
                                DeltaBuffer, resolve_staleness)
from repro.fleet.sampler import (SERVER_LR_SCHEDULES,
                                 EpochPermutationSampler,
                                 resolve_server_lr_schedule)
from repro.fleet.simulator import FleetSimulator, LatencyModel

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _maxdelta(t1, t2):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))


# -- MLP triple: shape-agnostic loss, so every scheme (shared window,
# staggered, full) runs the extract-based client phase at its own widths.
D_IN, D_H, C, K, MB = 6, 8, 4, 2, 3


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


def _items(n, clients=C, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((K, clients, MB, D_IN)).astype(
                np.float32),
             "y": rng.standard_normal((K, clients, MB)).astype(np.float32)}
            for _ in range(n)]


def _stream(clients=C, seed=0):
    """Fresh deterministic infinite batch stream (same seed, same items)."""
    rng = np.random.default_rng(seed)
    while True:
        yield {"x": rng.standard_normal((K, clients, MB, D_IN)).astype(
                   np.float32),
               "y": rng.standard_normal((K, clients, MB)).astype(np.float32)}


# ---------------------------------------------------------------------------
# The bitwise sync-equivalence anchor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,kw,sopt", [
    ("rolling", {}, "none"),
    ("rolling_adam", {}, "adam"),
    ("stagger", {"stagger": True}, "none"),
    ("static", {"scheme": "static"}, "none"),
    ("full", {"scheme": "full"}, "none"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_async_m_equals_n_matches_sync_bitwise(name, kw, sopt):
    """M = N, zero-spread fleet, no dropouts: the async round sequence is
    the synchronous ``api.Trainer`` loop, bit for bit — params AND the
    per-round client-loss records."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg(**kw), server_opt=sopt)
    n = 5
    items = _items(n)

    tr = api.Trainer(fed, params, rng=jax.random.PRNGKey(5))
    p_sync, h_sync = tr.run(iter(items), n)

    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(5))
    p_async, h_async = at.run(iter(items), n)

    assert _maxdelta(p_sync, p_async) == 0.0
    assert len(h_async) == n
    for rs, ra in zip(h_sync, h_async):
        assert rs["round"] == ra["round"]
        np.testing.assert_array_equal(np.asarray(rs["client_loss"]),
                                      np.asarray(ra["client_loss"]))
        assert float(ra["staleness"]) == 0.0
        assert float(ra["lr_mult"]) == 1.0


def test_async_anchor_fused_transformer():
    """The anchor holds on the fused multi-axis client phase too (the
    transformer arm the MLP triple cannot reach)."""
    from dataclasses import replace
    from repro.data.synthetic import lm_batches
    from repro.models import build_model

    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2,
                  vocab=64, d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                  head_dim=16)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = _scfg(client_lr=0.05)
    fed = api.fed_round(m, scfg, fused_forward="on")
    it = lm_batches(cfg.vocab, (K, C, 2), 16, seed=0)
    items = [next(it) for _ in range(2)]

    tr = api.Trainer(fed, params, rng=jax.random.PRNGKey(5))
    p_sync, _ = tr.run(iter(items), 2)
    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(5))
    p_async, _ = at.run(iter(items), 2)
    assert at._arm == fed._client_phase_fused   # the fused phase ran
    assert _maxdelta(p_sync, p_async) == 0.0


def test_async_regime_bit_identical_replay():
    """A genuinely asynchronous regime — stragglers, jitter, dropouts,
    timeout, M < N — is deterministic: two fresh servers over the same
    seeds produce identical histories and identical params, and actually
    exercise staleness (mixed-window aggregation included)."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg())

    def run_once():
        fleet = api.FleetSimulator(16, api.LatencyModel(
            jitter_sigma=0.3, straggler_frac=0.25, dropout=0.2,
            timeout=5.0, seed=1))
        at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(7),
                              buffer_size=2, fleet=fleet,
                              server_lr_schedule="inv_sqrt")
        p, h = at.run(_stream(), 12)
        return p, h

    p1, h1 = run_once()
    p2, h2 = run_once()
    assert _maxdelta(p1, p2) == 0.0
    assert [float(r["loss"]) for r in h1] == [float(r["loss"]) for r in h2]
    taus = [float(r["staleness"]) for r in h1]
    assert any(t > 0 for t in taus), taus     # staleness really happened
    for r in h1:                               # schedule folded per round
        assert float(r["lr_mult"]) == 1.0 / np.sqrt(1.0 + r["round"])
    vts = [float(r["virtual_time"]) for r in h1]
    assert vts == sorted(vts)


def test_async_run_resumes_in_flight():
    """Two run() calls == one: in-flight work persists across calls."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg())

    at1 = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(3))
    p_once, _ = at1.run(_stream(), 6)
    src = _stream()                       # one stream across both calls
    at2 = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(3))
    at2.run(src, 2)
    p_split, _ = at2.run(src, 4)
    assert _maxdelta(p_once, p_split) == 0.0
    assert at2.round_idx == 6


def test_async_callable_source_gets_sampled_ids():
    """Callable sources receive the sampled client ids (the
    FederatedDataset.round_batch integration path)."""
    model, params = _triple()
    fed = api.fed_round(model, _scfg())
    seen = []
    rng = np.random.default_rng(0)

    def source(ids):
        seen.append(np.asarray(ids))
        return {"x": rng.standard_normal((K, len(ids), MB, D_IN)).astype(
                    np.float32),
                "y": rng.standard_normal((K, len(ids), MB)).astype(
                    np.float32)}

    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(1),
                          fleet=api.FleetSimulator(8))
    at.run(source, 4)
    assert seen and all(len(np.unique(s)) == len(s) for s in seen)
    # epoch permutation across dispatches: first 8 sampled ids cover 0..7
    flat = np.concatenate(seen)[:8]
    assert sorted(flat.tolist()) == list(range(8))


# ---------------------------------------------------------------------------
# Staleness policies + server-lr schedules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(STALENESS_POLICIES))
def test_staleness_policy_contract(name):
    w = STALENESS_POLICIES[name]
    assert w(0) == 1.0                            # fresh never discounted
    vals = [w(float(t)) for t in range(9)]
    assert all(a >= b for a, b in zip(vals, vals[1:]))   # non-increasing
    assert all(v > 0 for v in vals)


def test_staleness_default_is_fedbuff_inverse_sqrt():
    w = resolve_staleness("inverse_sqrt")
    assert w(1.0) == 1.0 / np.sqrt(2.0)
    assert w(3.0) == 0.5
    assert resolve_staleness(lambda t: 0.25)(7.0) == 0.25   # pluggable
    with pytest.raises(ValueError, match="staleness"):
        resolve_staleness("nope")


def test_server_lr_schedules():
    assert resolve_server_lr_schedule(None)(0) == 1.0
    assert resolve_server_lr_schedule("constant")(123) == 1.0
    inv = resolve_server_lr_schedule("inv_sqrt")
    assert inv(0) == 1.0 and inv(3) == 0.5
    step = SERVER_LR_SCHEDULES["step"](gamma=0.5, every=2)
    assert [step(r) for r in range(5)] == [1.0, 1.0, 0.5, 0.5, 0.25]
    assert resolve_server_lr_schedule(lambda r: 2.0)(0) == 2.0
    with pytest.raises(ValueError, match="schedule"):
        resolve_server_lr_schedule("nope")


# ---------------------------------------------------------------------------
# Epoch-permutation sampler (arXiv 2201.11066 participation)
# ---------------------------------------------------------------------------


def test_sampler_epoch_coverage_when_dividing():
    s = EpochPermutationSampler(8, seed=0)
    a, b = s.sample(4), s.sample(4)
    assert sorted(np.concatenate([a, b]).tolist()) == list(range(8))
    assert s.epoch == 1
    c, d = s.sample(4), s.sample(4)
    assert sorted(np.concatenate([c, d]).tolist()) == list(range(8))
    assert s.epoch == 2


def test_sampler_deterministic_and_distinct_within_call():
    draws = [3, 5, 2, 7, 1, 6]
    seqs = [np.concatenate([EpochPermutationSampler(7, seed=4).sample(n)
                            for n in draws]) for _ in range(2)]
    np.testing.assert_array_equal(seqs[0], seqs[1])
    s = EpochPermutationSampler(7, seed=4)
    for n in draws:                    # 7 is not divisible by any draw
        got = s.sample(n)
        assert len(np.unique(got)) == n
    other = np.concatenate([EpochPermutationSampler(7, seed=5).sample(n)
                            for n in draws])
    assert (seqs[0] != other).any()


def test_sampler_errors():
    s = EpochPermutationSampler(4)
    with pytest.raises(ValueError):
        s.sample(0)
    with pytest.raises(ValueError):
        s.sample(5)
    with pytest.raises(ValueError):
        EpochPermutationSampler(0)


# ---------------------------------------------------------------------------
# Delta buffer
# ---------------------------------------------------------------------------


def _rep(cid, tag):
    return ClientReport(client_id=cid, slot=0, round_tag=tag,
                        delta={"w": np.zeros((1, 2))}, offsets={},
                        losses=np.zeros((K, 1)))


def test_buffer_fifo_ready_and_staleness_weights():
    buf = DeltaBuffer(2, staleness="inverse_sqrt")
    assert len(buf) == 0 and not buf.ready()
    for cid, tag in ((7, 0), (3, 1), (9, 2)):
        buf.report(_rep(cid, tag))
    assert buf.ready() and len(buf) == 3
    reps, taus, weights = buf.take(server_round=2)
    assert [r.client_id for r in reps] == [7, 3]     # oldest two, in order
    np.testing.assert_array_equal(taus, [2, 1])
    np.testing.assert_allclose(weights,
                               [1.0 / np.sqrt(3.0), 1.0 / np.sqrt(2.0)])
    assert len(buf) == 1 and not buf.ready()         # third entry waits


def test_buffer_errors():
    with pytest.raises(ValueError, match="m must be"):
        DeltaBuffer(0)
    buf = DeltaBuffer(2)
    buf.report(_rep(0, 0))
    with pytest.raises(RuntimeError, match="1 of 2"):
        buf.take(0)
    buf.report(_rep(1, 5))
    with pytest.raises(RuntimeError, match="future"):
        buf.take(1)                                  # tag 5 > round 1


# ---------------------------------------------------------------------------
# Fleet simulator
# ---------------------------------------------------------------------------


def test_simulator_zero_spread_default():
    f = FleetSimulator(4)
    assert f.stragglers == frozenset()
    for cid in range(4):
        assert f.completion(cid, seq=cid) == (1.0, True)


def test_simulator_deterministic_draws():
    lm = LatencyModel(jitter_sigma=0.5, dropout=0.3, seed=2)
    a = [FleetSimulator(8, lm).draw(c, s) for c in range(8)
         for s in range(3)]
    b = [FleetSimulator(8, lm).draw(c, s) for c in range(8)
         for s in range(3)]
    assert a == b
    assert len({d for d, _ in a}) > 1                # jitter actually varies


def test_simulator_straggler_set_monotone_in_frac():
    small = FleetSimulator(16, LatencyModel(straggler_frac=0.25,
                                            seed=3)).stragglers
    big = FleetSimulator(16, LatencyModel(straggler_frac=0.5,
                                          seed=3)).stragglers
    assert len(small) == 4 and len(big) == 8
    assert small <= big                   # sweeping frac only ADDS stragglers
    lm = LatencyModel(straggler_frac=0.25, straggler_mult=10.0, seed=3)
    f = FleetSimulator(16, lm)
    cid = next(iter(f.stragglers))
    assert f.draw(cid, 0) == (10.0, False)


def test_simulator_dropout_and_timeout_free_the_slot():
    f = FleetSimulator(4, LatencyModel(dropout=1.0, timeout=2.5, seed=0))
    assert f.completion(0, 0) == (2.5, False)        # dropped -> at timeout
    f = FleetSimulator(4, LatencyModel(dropout=1.0, seed=0))
    delay, ok = f.completion(0, 0)
    assert (delay, ok) == (1.0, False)     # no timeout: at would-be finish
    f = FleetSimulator(4, LatencyModel(straggler_frac=1.0, straggler_mult=8.0,
                                       timeout=3.0, seed=0))
    assert f.completion(0, 0) == (3.0, False)        # over-timeout abandoned


def test_simulate_sync_barrier_baseline():
    f = FleetSimulator(8)
    assert f.simulate_sync(EpochPermutationSampler(8), 5, cohort=4) == 5.0
    # every straggler-containing cohort pays the full multiplier
    lm = LatencyModel(straggler_frac=0.5, straggler_mult=10.0, seed=0)
    fs = FleetSimulator(8, lm)
    t = fs.simulate_sync(EpochPermutationSampler(8), 2, cohort=8)
    assert t == 20.0                       # both rounds barriered at 10s


# ---------------------------------------------------------------------------
# Validation + layering policy
# ---------------------------------------------------------------------------


def test_async_trainer_rejects_mask_mode():
    model, params = _triple()
    fed = api.fed_round(model, _scfg(scheme="bernoulli"),
                        capacities=np.full(C, 0.5))
    with pytest.raises(TypeError, match="window-mode"):
        api.AsyncTrainer(fed, params)


def test_async_trainer_rejects_mesh_rounds():
    from repro.launch.mesh import host_mesh
    model, params = _triple()
    fed = api.fed_round(model, _scfg(), mesh=host_mesh("1"))
    with pytest.raises(ValueError, match="mesh"):
        api.AsyncTrainer(fed, params)


def test_async_trainer_rejects_undersized_fleet():
    model, params = _triple()
    fed = api.fed_round(model, _scfg())
    with pytest.raises(ValueError, match="fleet"):
        api.AsyncTrainer(fed, params, fleet=api.FleetSimulator(C - 1))


def test_fleet_never_constructs_rounds():
    """Layering policy (mirrors the CI ``policy`` job): repro.fleet drives
    the round object handed to it and must not import the facade or the
    round factories."""
    pats = [re.compile(r"^\s*(?:from|import)\s+repro\.api\b", re.M),
            re.compile(r"^\s*from\s+repro\s+import\b.*\bapi\b", re.M),
            re.compile(r"^\s*(?:from|import)\s+repro\.core\.fedavg\b", re.M),
            re.compile(r"^\s*from\s+repro\.core\s+import\b.*\bfedavg\b",
                       re.M)]
    pkg = os.path.join(SRC, "repro", "fleet")
    offenders, scanned = [], set()
    for f in sorted(os.listdir(pkg)):
        if not f.endswith(".py"):
            continue
        scanned.add(f)
        with open(os.path.join(pkg, f)) as fh:
            text = fh.read()
        if any(p.search(text) for p in pats):
            offenders.append(f)
    assert not offenders, f"fleet imports the round layer: {offenders}"
    assert {"__init__.py", "buffer.py", "sampler.py", "server.py",
            "simulator.py"} <= scanned
