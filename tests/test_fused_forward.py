"""The fused multi-axis window forward (tentpole property tests).

When every properly-windowed axis has a fused forward (``d_ff``,
GQA-coupled ``heads``/``kv_heads``, ``experts``, ``moe_d_ff``,
``ssm_heads``), ``WindowFedAvg`` resolves the fused arm: each client's
windows are cut once a round and the cut tree is carried through the K
steps, but for axes the forward must index absolutely (a share's
``experts``), which stay whole and are narrowed by the window-aware
``Model.forward``.  On every model here nothing stays whole, so the fused
round must be **bitwise equal (f32, 0 ulp)** to the extract-based round —
pinned across schemes, multi-axis combinations, model families,
optimizers, backends, and the unaligned exact-tail grid entry.  The
layer-level window-aware forward is pinned against the compact forward at
the end of the file.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import SubmodelConfig, get_reduced_config
from repro.data.synthetic import lm_batches
from repro.models import build_model
from repro.models.layers import AxisWindow, WindowMap


def _tiny_model(d_ff=128):
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2, vocab=64,
                  d_model=64, d_ff=d_ff, n_heads=4, n_kv_heads=2,
                  head_dim=16)
    return cfg, build_model(cfg, remat=False)


def _batch(cfg, K=2, C=4, mb=2, S=16, seed=0):
    it = lm_batches(cfg.vocab, (K, C, mb), S, seed=seed)
    return {k: jnp.asarray(v) for k, v in next(it).items()}


def _maxdelta(t1, t2):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))


def _pair(m, scfg, **kw):
    return (api.fed_round(m, scfg, fused_forward="on", **kw),
            api.fed_round(m, scfg, fused_forward="off", **kw))


# -- the acceptance property: fused == extract to 0 ulp on f32 ----------------


@pytest.mark.parametrize("scheme", ["rolling", "static", "importance"])
def test_fused_round_bitwise_equals_extract(scheme):
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme=scheme, capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",))
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not extract.use_fused
    batch = _batch(cfg)
    step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
    for r in range(3):  # cover several grid windows
        pf, mf = step_f(params, batch, r, jax.random.PRNGKey(1))
        pe, me = step_e(params, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0, f"round {r} not bitwise equal"
        np.testing.assert_array_equal(np.asarray(mf["client_loss"]),
                                      np.asarray(me["client_loss"]))
        params = pf


# -- the tentpole acceptance: multi-axis fused == extract, 0 ulp ---------------


# (arch, axes) matrix: GQA-coupled heads/kv_heads, MoE per-expert +
# experts windows, MLA/MTP/shared-expert composition, windowed SSD
# (ssm_heads on the pure-SSM and hybrid families), MLA standalone heads,
# and the full default SubmodelConfig.axes tuple (axes=None).
MULTI_AXIS = [
    ("tinyllama_1_1b", ("d_ff", "kv_heads", "heads")),
    ("tinyllama_1_1b", None),               # full default axes tuple
    ("mixtral_8x22b", ("moe_d_ff",)),
    ("mixtral_8x22b", None),                # + experts + GQA heads
    ("deepseek_v3_671b", ("d_ff", "moe_d_ff")),  # MLA + shared + MTP
    ("deepseek_v3_671b", ("heads",)),       # MLA standalone head window
    ("deepseek_v3_671b", ("d_ff", "heads", "moe_d_ff")),
    ("mamba2_130m", None),                  # windowed SSD (== ssm_heads,
                                            # the family's only proper axis)
    ("hymba_1_5b", None),                   # hybrid: d_ff + ssm_heads
]


@pytest.mark.parametrize("arch,axes", MULTI_AXIS)
def test_fused_multi_axis_bitwise_equals_extract(arch, axes):
    cfg = replace(get_reduced_config(arch), n_layers=2)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    kw = {"axes": axes} if axes else {}
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1, **kw)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not extract.use_fused
    batch = _batch(cfg)
    step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
    for r in range(2):
        pf, mf = step_f(params, batch, r, jax.random.PRNGKey(1))
        pe, me = step_e(params, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0, \
            f"{arch}/{axes} round {r} not bitwise equal"
        np.testing.assert_array_equal(np.asarray(mf["client_loss"]),
                                      np.asarray(me["client_loss"]))
        params = pf


@pytest.mark.parametrize("arch,windowed", [
    ("tinyllama_1_1b", {"d_ff", "kv_heads", "heads"}),
    ("mixtral_8x22b", {"kv_heads", "heads", "experts", "moe_d_ff"}),
    ("mamba2_130m", {"ssm_heads"}),
    ("hymba_1_5b", {"d_ff", "ssm_heads"}),   # 1 kv head: improper, skipped
    ("deepseek_v3_671b", {"d_ff", "heads", "experts", "moe_d_ff"}),
])
def test_resolve_fused_full_default_axes(arch, windowed):
    """Acceptance pin: _resolve_fused returns True for the full default
    SubmodelConfig.axes tuple under a shared window, covering every
    windowed axis the model actually has."""
    cfg = replace(get_reduced_config(arch), n_layers=2)
    m = build_model(cfg, remat=False)
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4)   # default axes tuple
    fed = api.fed_round(m, scfg)
    assert fed.use_fused
    assert {k[0] for k in fed._fused_keys} == windowed
    # GQA coupling: on models WITH a kv_heads axis the heads window is
    # derived from kv_heads; MLA (no kv_heads axis) windows heads standalone
    heads = [k for k in fed._fused_keys if k[0] == "heads"]
    if "kv_heads" in windowed:
        assert all(k in fed.scheme.derived for k in heads)
    else:
        assert all(k not in fed.scheme.derived for k in heads)


def test_fused_round_bitwise_on_unaligned_tail():
    """align=8 with d_ff=100 puts the exact-tail offset (52) off the
    alignment grid: its certificate refuses the fused Pallas arm, and the
    round stays bitwise-equal to extraction on every grid window."""
    cfg, m = _tiny_model(d_ff=100)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",), align=8)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused
    # the tail entry breaks the alignment certificate: a traced offset must
    # NOT be allowed onto the fused Pallas arm for this grid
    key = ("d_ff", 100)
    win = fused.scheme.sizes[key]
    spec = AxisWindow(0, win, fused._fused_mults[key])
    assert not spec.aligned(min(128, win))
    batch = _batch(cfg)
    step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
    R = fused.scheme.n_windows
    for r in range(R):  # every grid window incl. the exact tail
        pf, _ = step_f(params, batch, r, jax.random.PRNGKey(1))
        pe, _ = step_e(params, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0, f"round {r} not bitwise equal"


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_fused_round_backends(backend):
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",))
    fused, extract = _pair(m, scfg, kernel_backend=backend)
    batch = _batch(cfg)
    pf, _ = jax.jit(fused.round)(params, batch, 0, jax.random.PRNGKey(1))
    pe, _ = jax.jit(extract.round)(params, batch, 0, jax.random.PRNGKey(1))
    tol = 0.0 if backend == "jnp" else 5e-4
    assert _maxdelta(pf, pe) <= tol


def test_fused_with_server_opt_bitwise():
    """round_with_server_opt: the fused arm's cut mean delta, scattered,
    must reproduce the extract path's pseudo-gradient bit for bit."""
    from repro.core.server_opt import server_momentum
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",))
    fused, extract = _pair(m, scfg)
    batch = _batch(cfg)
    opt = server_momentum(lr=1.0)
    step_f = jax.jit(lambda p, s, b, r, rng: fused.round_with_server_opt(
        p, s, b, r, opt, rng=rng))
    step_e = jax.jit(lambda p, s, b, r, rng: extract.round_with_server_opt(
        p, s, b, r, opt, rng=rng))
    sf = se = opt.init(m.abstract_params())
    pf = pe = params
    for r in range(2):
        pf, sf, _ = step_f(pf, sf, batch, r, jax.random.PRNGKey(1))
        pe, se, _ = step_e(pe, se, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0
        assert _maxdelta(sf, se) == 0.0


def test_fused_trains():
    """Sanity: the fused path actually trains (loss decreases)."""
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",))
    fed = api.fed_round(m, scfg, fused_forward="on")
    it = ( {k: jnp.asarray(v) for k, v in b.items()}
          for b in lm_batches(cfg.vocab, (2, 4, 2), 16, seed=0))
    trainer = api.Trainer(fed, params, rng=jax.random.PRNGKey(1))
    _, history = trainer.run(it, 6)
    losses = trainer.losses
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


# -- staggered / per-client windows: the batched-offset fused arm -------------


# per-client window schemes: staggered rolling (each client rotates through
# the permuted grid), random structured (independent per-client offsets),
# and staggered importance (clients take the R mass-ranked grid windows).
PER_CLIENT = [("rolling", True), ("random", False), ("importance", True)]


@pytest.mark.parametrize("scheme,stagger", PER_CLIENT)
def test_staggered_fused_round_bitwise_equals_extract(scheme, stagger):
    """Per-client windows run fused (one cut per client, each client's
    delta added in place at its window) and must stay bitwise-equal to the
    per-client extract/scatter round."""
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme=scheme, capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff", "heads", "kv_heads"),
                          stagger=stagger)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not fused.shared_window
    batch = _batch(cfg)
    step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
    for r in range(3):
        pf, mf = step_f(params, batch, r, jax.random.PRNGKey(1))
        pe, me = step_e(params, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0, \
            f"{scheme} stagger={stagger} round {r} not bitwise equal"
        np.testing.assert_array_equal(np.asarray(mf["client_loss"]),
                                      np.asarray(me["client_loss"]))
        params = pf


def test_staggered_clients_get_distinct_windows():
    """The staggered rolling scheme really assigns different grid windows
    to different clients (the coverage property the fused arm must keep)."""
    cfg, m = _tiny_model()
    scfg = SubmodelConfig(scheme="rolling", capacity=0.25, local_steps=1,
                          clients_per_round=4, axes=("d_ff",), stagger=True)
    fed = api.fed_round(m, scfg)
    offs = fed._client_offsets(m.init(jax.random.PRNGKey(0)), 0,
                               jax.random.PRNGKey(1))
    per_client = np.asarray(offs[("d_ff", cfg.d_ff)])
    assert len(set(per_client.tolist())) > 1


def test_staggered_fused_bitwise_on_unaligned_tail():
    """Stagger + the exact-tail grid entry: some clients sit on the
    unaligned tail offset while others are aligned — the batched arm must
    drop to the oracle (mult certificate fails) and stay bitwise."""
    cfg, m = _tiny_model(d_ff=100)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",), align=8, stagger=True)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not fused.shared_window
    batch = _batch(cfg)
    step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
    R = fused.scheme.n_windows
    for r in range(R):
        pf, _ = step_f(params, batch, r, jax.random.PRNGKey(1))
        pe, _ = step_e(params, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0, f"round {r} not bitwise equal"


def test_staggered_fused_with_server_opt_bitwise():
    """round_with_server_opt on per-client windows: the fused cut deltas
    feed the same scatter-average scan as extract — pseudo-gradient and
    optimizer state must match bit for bit."""
    from repro.core.server_opt import server_momentum
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff",), stagger=True)
    fused, extract = _pair(m, scfg)
    batch = _batch(cfg)
    opt = server_momentum(lr=1.0)
    step_f = jax.jit(lambda p, s, b, r, rng: fused.round_with_server_opt(
        p, s, b, r, opt, rng=rng))
    step_e = jax.jit(lambda p, s, b, r, rng: extract.round_with_server_opt(
        p, s, b, r, opt, rng=rng))
    sf = se = opt.init(m.abstract_params())
    pf = pe = params
    for r in range(2):
        pf, sf, _ = step_f(pf, sf, batch, r, jax.random.PRNGKey(1))
        pe, se, _ = step_e(pe, se, batch, r, jax.random.PRNGKey(1))
        assert _maxdelta(pf, pe) == 0.0
        assert _maxdelta(sf, se) == 0.0


@pytest.mark.parametrize("arch", ["hymba_1_5b", "mamba2_130m"])
def test_staggered_fused_default_axes_families(arch):
    """Acceptance pin: the staggered scheme runs fused on the default axes
    tuple for the SSM families (each client's ssm_heads window cut) and
    stays bitwise-equal to extract."""
    cfg = replace(get_reduced_config(arch), n_layers=2)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1, stagger=True)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not fused.shared_window
    assert "ssm_heads" in {k[0] for k in fused._fused_keys}
    batch = _batch(cfg)
    pf, _ = jax.jit(fused.round)(params, batch, 0, jax.random.PRNGKey(1))
    pe, _ = jax.jit(extract.round)(params, batch, 0, jax.random.PRNGKey(1))
    assert _maxdelta(pf, pe) == 0.0


def test_staggered_fused_mla_heads_bitwise():
    """Acceptance pin: staggered + MLA standalone head windows."""
    cfg = replace(get_reduced_config("deepseek_v3_671b"), n_layers=2)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          axes=("d_ff", "heads"), stagger=True)
    fused, extract = _pair(m, scfg)
    assert fused.use_fused and not fused.shared_window
    batch = _batch(cfg)
    pf, _ = jax.jit(fused.round)(params, batch, 0, jax.random.PRNGKey(1))
    pe, _ = jax.jit(extract.round)(params, batch, 0, jax.random.PRNGKey(1))
    assert _maxdelta(pf, pe) == 0.0


_EXPERTS_MLA_CACHE = []


def _experts_mla_round_pair():
    """``(fused, worst fused-vs-extract maxdelta)`` for an ``experts``
    window on the MLA+shared+sigmoid family, K>1 local steps, over the two
    rounds the bitwise MULTI_AXIS matrix runs (each round from the fused
    round's parameters, as there).  Computed once, shared by both pins."""
    if not _EXPERTS_MLA_CACHE:
        cfg = replace(get_reduced_config("deepseek_v3_671b"), n_layers=2)
        m = build_model(cfg, remat=False)
        params = m.init(jax.random.PRNGKey(0))
        scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                              clients_per_round=4, client_lr=0.1,
                              axes=("experts",))
        fused, extract = _pair(m, scfg)
        batch = _batch(cfg)
        step_f, step_e = jax.jit(fused.round), jax.jit(extract.round)
        worst = 0.0
        for r in range(2):
            pf, _ = step_f(params, batch, r, jax.random.PRNGKey(1))
            pe, _ = step_e(params, batch, r, jax.random.PRNGKey(1))
            worst = max(worst, _maxdelta(pf, pe))
            params = pf
        _EXPERTS_MLA_CACHE.append((fused, worst))
    return _EXPERTS_MLA_CACHE[0]


def test_fused_experts_window_mla_family_close():
    """A whole layer's ``experts`` window (its router spans the experts it
    holds) is cut once a round like every dense window: the fused arm keeps
    no axis whole there.  Until the cut carry this point agreed with
    extract only to f32 roundoff (1 ulp in round 1), because the full-tree
    carry was another program shape; the round still stays within that old
    5e-7 pin."""
    fused, worst = _experts_mla_round_pair()
    assert fused.use_fused and fused._whole_keys == {}
    assert worst <= 5e-7


def test_fused_experts_window_mla_family_zero_ulp():
    """The same point at 0 ulp over both rounds: with the cut carry both
    arms run the same program, so the former roundoff caveat is gone."""
    assert _experts_mla_round_pair()[1] == 0.0


# -- resolution / validation --------------------------------------------------


def test_fused_auto_resolution():
    cfg, m = _tiny_model()
    only_dff = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                              clients_per_round=4, axes=("d_ff",))
    multi = replace(only_dff, axes=("d_ff", "heads", "kv_heads"))
    assert api.fed_round(m, only_dff).use_fused
    # multi-axis windows (GQA-coupled heads) fuse too now
    assert api.fed_round(m, multi).use_fused
    # an uncoupled heads window (no kv_heads to derive from) cannot fuse
    uncoupled = replace(only_dff, axes=("d_ff", "heads"))
    assert not api.fed_round(m, uncoupled).use_fused
    with pytest.raises(ValueError, match="GQA-derived"):
        api.fed_round(m, uncoupled, fused_forward="on")
    # an axis with no fused forward (d_model) falls back to extract
    unsupported = replace(only_dff, axes=("d_ff", "d_model"))
    assert not api.fed_round(m, unsupported).use_fused
    with pytest.raises(ValueError, match="no fused window-aware forward"):
        api.fed_round(m, unsupported, fused_forward="on")
    # a raw triple fuses iff its loss_fn is window-aware
    triple = (m.loss, m.abstract_params(), m.axes())
    assert api.fed_round(triple, only_dff).use_fused
    plain = (lambda p, b: m.loss(p, b), m.abstract_params(), m.axes())
    assert not api.fed_round(plain, only_dff).use_fused
    with pytest.raises(ValueError, match="windowed forward"):
        api.fed_round(plain, only_dff, fused_forward="on")
    # per-client windows fuse too now (the batched-offset arm): the
    # explicit per-client scatter baseline, staggered rolling, and the
    # random structured scheme all resolve fused without a shared window
    for scfg2 in (replace(only_dff, shared_window=False),
                  replace(only_dff, stagger=True),
                  replace(only_dff, scheme="random")):
        fed2 = api.fed_round(m, scfg2)
        assert fed2.use_fused and not fed2.shared_window
    # mask mode has no fused arm
    bern = replace(only_dff, scheme="bernoulli")
    with pytest.raises(ValueError, match="window mode"):
        api.fed_round(m, bern, fused_forward="on")


def test_windowed_forward_matches_compact_forward():
    """Model.loss(params, batch, window=...) == Model.loss on the extracted
    compact tree (the layer-level equivalence the round builds on)."""
    from repro.core import extract as ex
    from repro.core.masking import collect_axis_dims, make_scheme
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, axes=("d_ff",))
    scheme = make_scheme(scfg, collect_axis_dims(m.abstract_params(),
                                                 m.axes()))
    key = next(iter(scheme.sizes))
    win = scheme.sizes[key]
    off = int(scheme.grids[key][1])
    batch = {k: v[0, 0] for k, v in _batch(cfg).items()}
    sub = ex.extract(params, m.axes(), {key: off}, scheme.sizes)
    l_compact, _ = m.loss(sub, batch)
    l_fused, _ = m.loss(params, batch, window=(off, win))
    np.testing.assert_array_equal(np.asarray(l_compact),
                                  np.asarray(l_fused))


def test_windowed_forward_multi_axis_matches_compact():
    """Same layer-level equivalence for a per-axis window mapping covering
    d_ff + GQA-coupled heads/kv_heads, passed as a plain dict."""
    from repro.core import extract as ex
    from repro.core.masking import collect_axis_dims, make_scheme
    cfg, m = _tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5,
                          axes=("d_ff", "heads", "kv_heads"))
    scheme = make_scheme(scfg, collect_axis_dims(m.abstract_params(),
                                                 m.axes()))
    offsets = {k: int(v[1]) for k, v in scheme.grids.items()}
    for k, (src, group) in scheme.derived.items():
        offsets[k] = offsets[src] * group
    batch = {k: v[0, 0] for k, v in _batch(cfg).items()}
    sub = ex.extract(params, m.axes(), offsets, scheme.sizes)
    l_compact, _ = m.loss(sub, batch)
    window = {k: (offsets[k], scheme.sizes[k]) for k in scheme.sizes}
    l_fused, _ = m.loss(params, batch, window=window)
    np.testing.assert_array_equal(np.asarray(l_compact),
                                  np.asarray(l_fused))


def test_window_map_validation():
    """WindowMap refuses axes without a fused forward; the model refuses
    kv_heads windows on MLA attention (it has no kv_heads axis)."""
    with pytest.raises(ValueError, match="no window-aware forward"):
        WindowMap({("d_model", 64): (0, 32)})
    # spec normalization: bare tuples become AxisWindow with mult=1
    wm = WindowMap({("d_ff", 128): (0, 64)})
    spec = wm.get("d_ff", 128)
    assert isinstance(spec, AxisWindow) and spec.mult == 1
    assert wm.get("d_ff", 256) is None
    # alignment certificate: mult scales with the flattened layout
    assert AxisWindow(0, 4, 2).aligned(64, scale=32)
    assert not AxisWindow(0, 4, 1).aligned(64, scale=32)
    assert AxisWindow(0, 4, 0).aligned(64)   # offsets always 0
    cfg = get_reduced_config("deepseek_v3_671b")
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    batch = {k: v[0, 0] for k, v in _batch(cfg).items()}
    # MLA heads window standalone: supported (per-head up-projections)
    l, _ = m.loss(params, batch,
                  window={("heads", cfg.n_heads): (0, cfg.n_heads // 2)})
    assert np.isfinite(float(l))
    # ... but a kv_heads window has nothing to bind to — loud refusal
    with pytest.raises(ValueError, match="kv_heads"):
        m.loss(params, batch,
               window={("kv_heads", cfg.n_kv_heads):
                       (0, max(cfg.n_kv_heads // 2, 1))})
