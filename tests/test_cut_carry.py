"""The fused client phase's carry: each client's windows are cut from the
tree once a round (a shared window once for all clients) and the cut tree
is carried through the K local steps, so the carry, the update and the f32
delta are window-sized (``WindowFedAvg._client_phase_fused``).  The share
of the tree a client carries is recorded when the round is traced
(``repro.tracing.CLIENT_CARRY``, the ``client_carry_pct`` metric), and a
round without windows (plain FedAvg, the benchmark's control cell) runs no
window machinery at all.  A share's whole ``experts`` axis is pinned in
``tests/test_deepseek_v2.py``."""
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

from repro import api, tracing  # noqa: E402
from repro.configs.base import SubmodelConfig, get_reduced_config  # noqa: E402
from repro.core import extract as ex  # noqa: E402
from repro.core import submodel as sm  # noqa: E402
from repro.data.synthetic import lm_batches  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.models import build_model  # noqa: E402

C = 4


def _tiny_model():
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2, vocab=64,
                  d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                  head_dim=16)
    return cfg, build_model(cfg, remat=False)


def _batch(cfg):
    it = lm_batches(cfg.vocab, (2, C, 2), 16, seed=0)
    return {k: jnp.asarray(v) for k, v in next(it).items()}


def _bytes(tree):
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("stagger", [False, True],
                         ids=["shared", "staggered"])
def test_carried_leaves_have_the_cut_shapes(stagger):
    """Every leaf a client carries, and its f32 delta, has the compact
    sub-model's shape (``extract.sub_abstract``) under a leading client
    axis; the windowed leaves are narrower than the whole tree's; and the
    traced phase records the cut tree's bytes against the whole tree's."""
    cfg, m = _tiny_model()
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=C, client_lr=0.1,
                          stagger=stagger)
    fed = api.fed_round(m, scfg)
    assert fed.use_fused and fed.shared_window is not stagger
    assert not fed._whole_keys and fed._cut_sizes == fed.scheme.sizes
    params = m.init(jax.random.PRNGKey(0))

    def phase(p, b, rng):
        sub0, delta, _ = fed._client_phase_fused(
            p, b, fed._client_offsets(p, 0, rng))
        return sub0, delta

    before = dict(tracing.CLIENT_CARRY)
    sub0, delta = jax.eval_shape(phase, params, _batch(cfg),
                                 jax.random.PRNGKey(1))
    cut = ex.sub_abstract(m.abstract_params(), m.axes(), fed.scheme.sizes)
    narrower = 0
    for s, d, w, full in zip(jax.tree_util.tree_leaves(sub0),
                             jax.tree_util.tree_leaves(delta),
                             jax.tree_util.tree_leaves(cut),
                             jax.tree_util.tree_leaves(params)):
        assert s.shape == d.shape == (C,) + w.shape
        assert d.dtype == jnp.float32
        narrower += int(w.shape != full.shape)
    assert narrower >= 5     # q, k, v, o and the gated MLP at least
    assert tracing.CLIENT_CARRY["carried"] - before["carried"] == _bytes(cut)
    assert tracing.CLIENT_CARRY["whole"] - before["whole"] == \
        _bytes(m.abstract_params())
    assert _bytes(cut) < _bytes(params)


def _plain_fedavg_round(model, scfg):
    """FedAvg written out with no window machinery: every client's K SGD
    steps on the whole tree, f32 deltas summed by a scan of adds, then
    ``w + lr * sum / C`` and the l2 projection."""
    C, lr, slr = scfg.clients_per_round, scfg.client_lr, scfg.server_lr
    grad = jax.vmap(jax.value_and_grad(model.loss, has_aux=True))

    def round_(params, batch, round_idx, rng):
        w0 = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (C,) + x.shape), params)

        def kstep(carry, mb):
            p, ost = carry
            (loss, _), g = grad(p, mb)
            return (dispatch.sgd_step(p, g, lr), ost), loss

        (wK, _), losses = jax.lax.scan(kstep, (w0, ()), batch)
        delta = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            wK, w0)

        def acc_step(acc, d):
            return jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), acc, d), None

        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        acc, _ = jax.lax.scan(acc_step, acc0, delta)
        new = jax.tree_util.tree_map(
            lambda w, d: (w + slr * d.astype(jnp.float32) / C
                          ).astype(w.dtype), params, acc)
        new = sm.project_l2(new, scfg.proj_radius)
        return new, {"loss": losses.mean(), "client_loss": losses}

    return round_


def test_full_fedavg_round_lowers_to_plain_fedavg():
    """The control cell's round (``ds7b-full-fedavg`` cut by
    ``bench/tests/tiny.py``: scheme full, one client, K = 2) bypasses the
    window machinery: it lowers to the same text as plain FedAvg written
    out, and a client carries the whole tree."""
    from bench.tests import tiny
    cell = tiny.cell("ds7b-full-fedavg")
    model = build_model(cell.model_config(), remat=True)
    scfg = cell.submodel_config()
    fed = api.fed_round(model, scfg)
    assert not fed.use_fused and not fed.scheme.sizes
    mix = cell.mix
    args = (model.abstract_params(),
            {"tokens": jax.ShapeDtypeStruct(
                (mix["local_steps"], mix["clients"], mix["seqs_per_step"],
                 mix["seq_len"]), jnp.int32)},
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
    before = dict(tracing.CLIENT_CARRY)
    got = jax.jit(fed.round).lower(*args).as_text()
    assert tracing.CLIENT_CARRY["carried"] - before["carried"] == \
        tracing.CLIENT_CARRY["whole"] - before["whole"] > 0
    want = jax.jit(_plain_fedavg_round(model, scfg)).lower(*args).as_text()
    assert got == want


def test_client_carry_reader(monkeypatch):
    """``client_carry_pct`` reads the record as a share of the whole
    tree, and nothing from a program that keeps no record."""
    from bench import spec
    read = spec.metric_reader("client_carry_pct")
    monkeypatch.setattr(tracing, "CLIENT_CARRY",
                        {"carried": 3 * 57, "whole": 300})
    assert read(None) == pytest.approx(57.0)
    monkeypatch.setattr(tracing, "CLIENT_CARRY", {"carried": 0, "whole": 0})
    assert read(None) is None
    monkeypatch.delattr(tracing, "CLIENT_CARRY")
    assert read(None) is None
