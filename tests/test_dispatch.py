"""Kernel backend-dispatch layer tests.

* compat.py resolves Pallas TPU symbols on the installed JAX, and is the
  ONLY module importing ``jax.experimental.pallas.tpu`` (grep assertion).
* every dispatched op's pallas arm matches its jnp-oracle arm; the plain
  client update has one arm, the f32 formula bit for bit,
* a full ``MaskFedAvg.round`` is backend-equivalent (max|Δ| < 1e-5 fp32),
* ``WindowFedAvg.round_with_server_opt`` honors the importance scheme.
"""
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SubmodelConfig
from repro.core.fedavg import (make_mask_fed_round, make_window_fed_round)
from repro.core.server_opt import server_momentum
from repro.kernels import compat, dispatch, ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# -- compat -------------------------------------------------------------------


def test_compat_resolves_on_installed_jax():
    assert compat._pltpu.__name__ == "jax.experimental.pallas.tpu"
    scratch = compat.vmem((8, 128), jnp.float32)
    assert scratch is not None
    spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[compat.pl.BlockSpec((8, 128), lambda i, off: (0, 0))],
        out_specs=compat.pl.BlockSpec((8, 128), lambda i, off: (0, 0)))
    assert spec is not None


def test_compat_sole_tpu_importer():
    """Policy: all Pallas TPU symbols go through kernels/compat.py.

    Thin delegate to the linter's ``sole-tpu-importer`` rule
    (repro.analysis.lint) so there is one source of truth; this test
    keeps the policy in the fast tier and pins the sweep's coverage."""
    from repro.analysis import lint

    offenders = lint.run_lint([SRC], rules=["sole-tpu-importer"])
    assert not offenders, \
        f"pallas.tpu imported outside compat: {offenders}"
    # the sweep must keep covering every kernel module, in particular the
    # rolling-matmul forward AND the newer backward kernel
    scanned = {os.path.relpath(str(p), SRC) for p in
               lint.iter_py_files([SRC])}
    for mod in ("rolling_matmul.py", "rolling_matmul_bwd.py",
                "rolling_matmul_batched.py", "masked_update.py",
                "ssd_chunk.py", "dispatch.py"):
        assert os.path.join("repro", "kernels", mod) in scanned, mod


def test_auto_backend_resolution(monkeypatch):
    monkeypatch.delenv(dispatch.BACKEND_ENV, raising=False)
    expected = "pallas" if dispatch.on_tpu() else "jnp"
    assert dispatch.resolve_backend() == expected
    assert dispatch.resolve_backend("pallas") == "pallas"
    monkeypatch.setenv(dispatch.BACKEND_ENV, "jnp")
    assert dispatch.resolve_backend() == "jnp"
    with pytest.raises(ValueError):
        dispatch.resolve_backend("mosaic")


# -- per-op arm equivalence ---------------------------------------------------


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (7, 13)),
            "b": {"c": jax.random.normal(jax.random.fold_in(k, 1), (33,))}}


def _assert_trees_close(t1, t2, tol=1e-6):
    for l1, l2 in zip(jax.tree_util.tree_leaves(t1),
                      jax.tree_util.tree_leaves(t2)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=tol, atol=tol)


def test_masked_sgd_arms_match():
    p = _tree()
    m = jax.tree_util.tree_map(lambda x: (x > 0).astype(x.dtype), p)
    g = _tree(1)
    _assert_trees_close(dispatch.masked_sgd(p, m, g, 0.07, backend="pallas"),
                        dispatch.masked_sgd(p, m, g, 0.07, backend="jnp"))


# a norm, odd 2-D sides, a stacked expert leaf, the Phi-3 head's 32,064
# columns (off the 128-lane grid) and a bf16 leaf
SGD_LEAVES = {
    "norm": ((33,), jnp.float32),
    "7x13": ((7, 13), jnp.float32),
    "12x96": ((12, 96), jnp.float32),
    "experts": ((2, 16, 1408), jnp.float32),
    "head": ((2, 24, 32064), jnp.float32),
    "bf16": ((12, 96), jnp.bfloat16),
}


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
@pytest.mark.parametrize("leaf", sorted(SGD_LEAVES))
def test_sgd_step_is_the_f32_update_bit_for_bit(leaf, backend):
    """One arm on every backend: the update's formula on each leaf as it
    lies, f32 arithmetic cast back to the leaf's dtype."""
    shape, dtype = SGD_LEAVES[leaf]
    k = jax.random.PRNGKey(len(shape))
    p = jax.random.normal(k, shape).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(k, 1), shape).astype(dtype)
    got = dispatch.sgd_step({"w": p}, {"w": g}, 0.07, backend=backend)["w"]
    want = (p.astype(jnp.float32)
            - 0.07 * g.astype(jnp.float32)).astype(dtype)
    assert got.shape == shape and got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_fillin_agg_arms_match():
    C = 3
    w = _tree()
    wc = jax.tree_util.tree_map(
        lambda x: jnp.stack([x * (i + 1) for i in range(C)]), w)
    mc = jax.tree_util.tree_map(
        lambda x: jnp.stack([(x > 0.1 * i).astype(x.dtype)
                             for i in range(C)]), w)
    _assert_trees_close(dispatch.fillin_agg(w, wc, mc, backend="pallas"),
                        dispatch.fillin_agg(w, wc, mc, backend="jnp"),
                        tol=1e-5)
    # stacked client leaves also flow through masked_sgd (the in-round use)
    g = jax.tree_util.tree_map(lambda x: x * 0.3, wc)
    _assert_trees_close(
        dispatch.masked_sgd(wc, mc, g, 0.05, backend="pallas"),
        dispatch.masked_sgd(wc, mc, g, 0.05, backend="jnp"))


def test_rolling_matmul_arms_and_fallback(monkeypatch):
    monkeypatch.setattr(dispatch, "ORACLE_FALLBACKS", Counter())
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 512))
    y1 = dispatch.rolling_matmul(x, w, 128, 256, backend="pallas")
    y2 = dispatch.rolling_matmul(x, w, 128, 256, backend="jnp")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-3)
    assert not dispatch.ORACLE_FALLBACKS
    # non-MXU-tileable shapes degrade to the oracle instead of asserting,
    # and the pallas arm counts the fallback (the jnp arm does not)
    y3 = dispatch.rolling_matmul(x[:100], w, 100, 156, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(y3), np.asarray(ref.rolling_matmul_ref(x[:100], w, 100,
                                                          156)),
        rtol=1e-5, atol=1e-5)
    dispatch.rolling_matmul(x[:100], w, 100, 156, backend="jnp")
    assert dispatch.ORACLE_FALLBACKS == Counter(rolling_matmul=1)


def test_rolling_matmul_traced_unaligned_offset_safe(monkeypatch):
    """A traced offset of unknown alignment must take the oracle arm (the
    kernel floor-rounds offsets to block boundaries) unless the caller
    vouches with assume_aligned=True."""
    monkeypatch.setattr(dispatch, "ORACLE_FALLBACKS", Counter())
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 512))
    off = jnp.int32(100)  # NOT a multiple of bn=128

    y = jax.jit(lambda o: dispatch.rolling_matmul(x, w, o, 128,
                                                  backend="pallas"))(off)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.rolling_matmul_ref(x, w, 100, 128)),
        rtol=1e-4, atol=1e-3)
    # the traced forward's fallback is visible, not silent
    assert dispatch.ORACLE_FALLBACKS["rolling_matmul"] >= 1


def test_dense_masks_reject_importance_scheme():
    """Mask mode cannot honor importance (needs live params) — it must
    refuse instead of silently training random windows."""
    from repro.core.fedavg import dense_client_masks
    ab = {"w": jax.ShapeDtypeStruct((4, 32), jnp.float32)}
    scfg = SubmodelConfig(scheme="importance", capacity=0.5, axes=("d_ff",))
    with pytest.raises(ValueError, match="dense-mask"):
        dense_client_masks(jax.random.PRNGKey(0), ab,
                           {"w": ("d_model", "d_ff")}, scfg,
                           jnp.full((2,), 0.5), 0)


def test_mlp_apply_rolling_equals_extract():
    from repro.models.layers import mlp_apply, mlp_apply_rolling
    D, F, win, off = 128, 512, 256, 128
    k = jax.random.PRNGKey(0)
    p = {"w_gate": jax.random.normal(k, (D, F)) * 0.1,
         "w_up": jax.random.normal(jax.random.fold_in(k, 1), (D, F)) * 0.1,
         "w_down": jax.random.normal(jax.random.fold_in(k, 2), (F, D)) * 0.1}
    x = jax.random.normal(jax.random.fold_in(k, 3), (2, 16, D))
    sub = {"w_gate": p["w_gate"][:, off:off + win],
           "w_up": p["w_up"][:, off:off + win],
           "w_down": p["w_down"][off:off + win]}
    want = mlp_apply(sub, x)
    for backend in ("jnp", "pallas"):
        got = mlp_apply_rolling(p, x, off, win, backend=backend)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# -- full-round equivalence (the acceptance property) -------------------------


def _small_problem():
    d_in, d_h, C, K = 24, 33, 4, 2
    k = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(k, (d_in, d_h)) * 0.3,
              "b1": jnp.zeros((d_h,)),
              "w2": jax.random.normal(jax.random.fold_in(k, 1), (d_h,)) * 0.3}
    ab = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    axes = {"w1": ("d_model", "d_ff"), "b1": ("d_ff",), "w2": ("d_ff",)}

    def loss(w, b):
        h = jnp.tanh(b["x"] @ w["w1"] + w["b1"])
        r = h @ w["w2"] - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    rng = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rng.standard_normal((K, C, 8, d_in)),
                              jnp.float32),
             "y": jnp.asarray(rng.standard_normal((K, C, 8)), jnp.float32)}
    return params, ab, axes, loss, batch, C, K


@pytest.mark.parametrize("scheme", ["bernoulli", "rolling"])
def test_mask_round_pallas_equals_jnp(scheme):
    """Dispatched pallas arm == jnp oracle arm for a full MaskFedAvg.round
    (jitted, tolerance-bounded)."""
    params, ab, axes, loss, batch, C, K = _small_problem()
    scfg = SubmodelConfig(scheme=scheme, capacity=0.5, local_steps=K,
                          clients_per_round=C, client_lr=0.05,
                          axes=("d_ff",))
    outs = {}
    for backend in ("jnp", "pallas"):
        fed = make_mask_fed_round(loss, scfg, ab, axes, np.full(C, 0.5),
                                  kernel_backend=backend)
        outs[backend], m = jax.jit(fed.round)(params, batch, 3,
                                              jax.random.PRNGKey(7))
        assert np.isfinite(float(m["loss"]))
    maxdelta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(outs["pallas"]),
        jax.tree_util.tree_leaves(outs["jnp"])))
    assert maxdelta < 1e-5, maxdelta


def test_window_round_backend_equivalent():
    """Window mode with the dispatched client SGD: pallas == jnp arms."""
    params, ab, axes, loss, batch, C, K = _small_problem()
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=K,
                          clients_per_round=C, client_lr=0.05,
                          axes=("d_ff",), align=1)
    outs = {}
    for backend in ("jnp", "pallas"):
        fed = make_window_fed_round(loss, scfg, ab, axes,
                                    kernel_backend=backend)
        outs[backend], _ = jax.jit(fed.round)(params, batch, 1,
                                              jax.random.PRNGKey(3))
    maxdelta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(outs["pallas"]),
        jax.tree_util.tree_leaves(outs["jnp"])))
    assert maxdelta < 1e-5, maxdelta


# -- satellite: importance scheme in round_with_server_opt --------------------


def test_server_opt_round_honors_importance_scheme():
    """round_with_server_opt used to silently fall back to the first grid
    window under scheme="importance"; it must use importance_offsets like
    round() does."""
    params, ab, axes, loss, batch, C, K = _small_problem()
    scfg = SubmodelConfig(scheme="importance", capacity=0.5, local_steps=K,
                          clients_per_round=C, client_lr=0.05,
                          axes=("d_ff",), align=1)
    fed = make_window_fed_round(loss, scfg, ab, axes)
    calls = []
    orig = fed.scheme.importance_offsets

    def spy(params_, axes_tree_, n_clients_):
        calls.append(n_clients_)
        return orig(params_, axes_tree_, n_clients_)

    fed.scheme.importance_offsets = spy
    opt = server_momentum(lr=1.0)
    state = opt.init(params)
    new, state, metrics = fed.round_with_server_opt(
        params, state, batch, 0, opt, rng=jax.random.PRNGKey(0))
    assert calls == [C]
    assert np.isfinite(float(metrics["loss"]))

    # and the chosen window is the max-mass one, not grid[0]
    offs = orig(params, axes, C)
    static = fed.scheme.offsets(jax.random.PRNGKey(0), 0, C)
    key = ("d_ff", 33)
    assert key in offs
    # sanity: importance offsets are within bounds
    o = np.asarray(offs[key])
    assert (o >= 0).all() and (o + fed.scheme.sizes[key] <= 33).all()
    del static


# -- block autotuner: hypothesis property tests -------------------------------
# hypothesis is optional (pyproject.toml [test] extra): degrade to per-test
# skips, keeping the rest of this module collectable without it.

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # pragma: no cover
    def given(*a, **k):
        return lambda f: pytest.mark.skip("hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class _NoSt:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _NoSt()

_dims = st.integers(min_value=1, max_value=1024)


@pytest.fixture
def fresh_tuner():
    """Isolate autotune cache + override; restore process state after."""
    dispatch.clear_block_cache()
    dispatch.set_block_override(None)
    yield
    dispatch.clear_block_cache()
    dispatch.set_block_override(None)


@given(M=_dims, K=_dims, win=_dims)
@settings(max_examples=100, deadline=None)
def test_autotune_blocks_divide_and_cover(M, K, win):
    """Every tuned (bm, bn, bk), in both roles, exactly tiles its dims (the
    kernels assert dim % block == 0); the window edge — the forward's bn,
    dx's bk — stays within the MXU-tile cap and prefers the f32 sublane
    multiple when the dim allows one; bm keeps that preference too; and
    the working set fits the VMEM budget."""
    dispatch.clear_block_cache()
    for role in dispatch.ROLES:
        bm, bn, bk = dispatch.autotune_blocks(M, K, win, role=role)
        wb, kb = (bn, bk) if role == "fwd" else (bk, bn)
        assert M % bm == 0 and win % wb == 0 and K % kb == 0
        assert 1 <= wb <= 128
        if M % 8 == 0:
            assert bm % 8 == 0
        if win % 8 == 0:
            assert wb % 8 == 0
        assert dispatch.tile_vmem_bytes(bm, bn, bk, 4) \
            <= dispatch._VMEM_BUDGET_BYTES


@given(M=_dims, K=_dims, win=_dims,
       dtype=st.sampled_from(["float32", "bfloat16"]))
@settings(max_examples=50, deadline=None)
def test_autotune_blocks_deterministic_per_key(M, K, win, dtype):
    """Same key -> same triple, with or without the memo: the tuner never
    times anything, so two processes (or a cold and a warm cache) must
    agree."""
    dispatch.clear_block_cache()
    cold = dispatch.autotune_blocks(M, K, win, dtype)
    warm = dispatch.autotune_blocks(M, K, win, dtype)
    dispatch.clear_block_cache()
    recold = dispatch.autotune_blocks(M, K, win, dtype)
    assert cold == warm == recold


@given(M=st.integers(2, 512), K=st.integers(2, 512), win=st.integers(2, 512))
@settings(max_examples=50, deadline=None)
def test_autotune_cache_never_crosses_keys(M, K, win):
    """A poisoned memo entry for one key must never leak into a different
    shape/dtype/backend key."""
    dispatch.clear_block_cache()
    poisoned = (-1, -1, -1)
    backend = dispatch.resolve_backend(None)
    dispatch._AUTOTUNE_CACHE[((M, K, win), "float32", backend, "fwd")] = \
        poisoned
    # the poisoned key itself is returned verbatim (proves exact keying) ...
    assert dispatch.autotune_blocks(M, K, win, "float32") == poisoned
    # ... while neighbouring shape keys, the other dtype and the other
    # role are untouched
    for other in ((M + 1, K, win), (M, K + 1, win), (M, K, win + 1)):
        got = dispatch.autotune_blocks(*other, "float32")
        assert got != poisoned
        assert other[0] % got[0] == 0 and other[2] % got[1] == 0 \
            and other[1] % got[2] == 0
    assert dispatch.autotune_blocks(M, K, win, "bfloat16") != poisoned
    assert dispatch.autotune_blocks(M, K, win, "float32",
                                    role="dx") != poisoned
    dispatch.clear_block_cache()


@given(M=_dims, K=_dims, win=_dims,
       ov=st.tuples(st.integers(1, 256), st.integers(1, 256),
                    st.integers(1, 256)))
@settings(max_examples=50, deadline=None)
def test_block_override_wins_over_tuner(M, K, win, ov):
    """Resolution order: explicit call args > set_block_override > tuner.
    The override must never be written into the autotune cache."""
    dispatch.clear_block_cache()
    dispatch.set_block_override(None)
    try:
        tuned = dispatch._resolve_blocks(M, K, win, "float32", None,
                                         None, None, None)
        dispatch.set_block_override(ov)
        assert dispatch._resolve_blocks(M, K, win, "float32", None,
                                        None, None, None) == ov
        # explicit per-call args still beat the override
        assert dispatch._resolve_blocks(M, K, win, "float32", None,
                                        2, 3, 4) == (2, 3, 4)
        # partial explicit args: the missing slots come from the override
        assert dispatch._resolve_blocks(M, K, win, "float32", None,
                                        7, None, None) == (7, ov[1], ov[2])
        assert ov not in dispatch._AUTOTUNE_CACHE.values() or ov == tuned
        # clearing the override restores the tuned choice exactly
        dispatch.set_block_override(None)
        assert dispatch._resolve_blocks(M, K, win, "float32", None,
                                        None, None, None) == tuned
    finally:
        dispatch.set_block_override(None)
        dispatch.clear_block_cache()


# -- block autotuner: the contract at the benchmark cells' shapes -----------

# (M, K, win): rows a client step feeds one call, the projection's input
# width, the window.  ds7b-silo (2x2048 tokens, d_model 4096: q/k/v window
# 2048, gate/up window 5504), phi3-partition (1x1024 tokens, d_model 3072:
# 1536 and 4096), TinyLlama-1.1B at 1x512 tokens (d_ff window 2816), and
# CPU-test shapes.
TUNER_SHAPES = [
    pytest.param(4096, 4096, 2048, id="ds7b-qkv"),
    pytest.param(4096, 4096, 5504, id="ds7b-gate_up"),
    pytest.param(1024, 3072, 1536, id="phi3-qkv"),
    pytest.param(1024, 3072, 4096, id="phi3-gate_up"),
    pytest.param(512, 2048, 2816, id="tinyllama-gate_up"),
    pytest.param(128, 256, 256, id="cpu-aligned"),
    pytest.param(96, 160, 96, id="cpu-unaligned"),
    pytest.param(64, 96, 64, id="cpu-small"),
]


@pytest.mark.parametrize("M,K,win", TUNER_SHAPES)
def test_tuner_role_contract(fresh_tuner, M, K, win):
    """Forward and dx get their own blocks.  The edge that carries the
    window offset (forward bn, dx bk) is min(128, win), the block the
    models' alignment certificate vouches for; every edge divides its dim;
    the offset-free edges (bm, forward bk, dx bn over K) exceed 128 wherever
    the dim allows, the forward contracting all of K in one block."""
    fwd = dispatch.autotune_blocks(M, K, win, role="fwd")
    dx = dispatch.autotune_blocks(M, K, win, role="dx")
    assert fwd[1] == dx[2] == min(128, win)
    for bm, wb, kb in (fwd, (dx[0], dx[2], dx[1])):
        assert M % bm == 0 and win % wb == 0 and K % kb == 0
        assert bm == M or bm > 128
        assert kb == K or kb > 128
    assert fwd[2] == K


@pytest.mark.parametrize("M,K,win", TUNER_SHAPES)
def test_tuned_call_passes_a_vmem_limit_that_holds_its_blocks(
        fresh_tuner, M, K, win):
    """Trace (no run) one batched call and its VJP on the pallas arm: each
    kernel records the tuner's blocks for its role, its grid steps a call,
    and a vmem_limit_bytes that holds its double-buffered working set.  A
    window off the 128-lane grid reaches Mosaic only as the whole dim."""
    B, N = 2, win + 128 if win % 128 == 0 else win

    def loss(x, w, offs):
        return dispatch.rolling_matmul_batched(
            x, w, offs, win, backend="pallas", assume_aligned=True).sum()

    jax.eval_shape(jax.grad(loss, argnums=(0, 1)),
                   jax.ShapeDtypeStruct((B, M, K), jnp.float32),
                   jax.ShapeDtypeStruct((B, K, N), jnp.float32),
                   jax.ShapeDtypeStruct((B,), jnp.int32))
    got = {c["op"]: c for c in dispatch.block_choices()}
    assert set(got) == {"rolling_matmul_batched_fwd",
                        "rolling_matmul_batched_dx"}
    for op, role in (("rolling_matmul_batched_fwd", "fwd"),
                     ("rolling_matmul_batched_dx", "dx")):
        c = got[op]
        bm, bn, bk = dispatch.autotune_blocks(M, K, win, role=role)
        assert tuple(c["blocks"]) == (bm, bn, bk)
        steps = (B * (M // bm) * (win // bn) * (K // bk) if role == "fwd"
                 else B * (M // bm) * (K // bn) * (win // bk))
        assert c["grid_steps"] == steps
        assert dispatch.tile_vmem_bytes(bm, bn, bk, 4) \
            <= c["vmem_limit_bytes"] < 128 * 2**20


def test_block_override_validates(fresh_tuner):
    with pytest.raises(ValueError, match="block sizes"):
        dispatch.set_block_override((0, 8, 8))
    assert dispatch.set_block_override((8, 16, 32)) == (8, 16, 32)
    dispatch.set_block_override(None)


def test_autotuned_rolling_matmul_matches_oracle(fresh_tuner, monkeypatch):
    """End to end: dispatch.rolling_matmul with tuner-chosen blocks (block
    args left None) == the jnp oracle on a shape with unaligned M and K
    tails, its window on the 128-lane grid so that the Pallas arm runs."""
    monkeypatch.setattr(dispatch, "ORACLE_FALLBACKS", Counter())
    M, K, N, off, win = 96, 160, 384, 128, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    y = dispatch.rolling_matmul(x, w, off, win, backend="pallas")
    assert not dispatch.ORACLE_FALLBACKS
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.rolling_matmul_ref(x, w, off,
                                                                 win)),
                               rtol=1e-4, atol=1e-3)
