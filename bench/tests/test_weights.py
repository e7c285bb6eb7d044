"""The weights the seed makes (``bench.weights``): the dense trees of the
benchmark's cells come out bit for bit as under the rule they were
measured with, and expert stacks take each expert's own fan-in."""
import math

import jax
import numpy as np
import pytest

from bench import spec, weights
from bench.tests import tiny
from repro.models import build_model


def _rule_of_the_dense_cells(path, shape, n_layers):
    """``weights._scale`` as the dense cells were measured with, kept as
    the pin: every matrix but the embedding and ``wo`` at 1/sqrt of its
    first axis after the layer axis."""
    name = path[-1]
    if len(shape) - (path[0] in weights.STACKS) == 1:
        return None
    if name == "embed":
        return 0.02
    if name == "wo":
        return 0.02 / math.sqrt(2 * max(n_layers, 1))
    fan_in = shape[1] if path[0] in weights.STACKS else shape[0]
    return 1.0 / math.sqrt(fan_in)


def _tree(cell, seed=2**31 + 5):
    cfg = cell.model_config()
    model = build_model(cfg, remat=True)
    init = weights.make_init(model.abstract_params(), cfg.n_layers)
    return jax.device_get(init(weights.seed32(seed)))


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      spec.load_benchmark()["workloads"]])
def test_dense_trees_as_measured(workload, monkeypatch):
    cell = tiny.cell(workload)
    new = _tree(cell)
    monkeypatch.setattr(weights, "_scale", _rule_of_the_dense_cells)
    old = _tree(cell)
    assert [p for p, _ in weights.leaves(new)] == [
        p for p, _ in weights.leaves(old)]
    for (path, a), (_, b) in zip(weights.leaves(new), weights.leaves(old)):
        assert a.dtype == b.dtype and np.array_equal(a, b), path


def test_expert_stacks_at_their_fan_in():
    cell = spec.Cell("moe-mla", 1, dict(tiny.MOE_MLA), {}, {}, [], [])
    tree = _tree(cell)
    moe = tree["moe_layers"]["moe"]
    D, F = tiny.MOE_MLA["hidden_size"], tiny.MOE_MLA["moe_intermediate_size"]
    E = tiny.MOE_MLA["n_routed_experts"]
    for name, fan_in in (("w_gate", D), ("w_up", D), ("w_down", F)):
        leaf = moe[name]
        assert leaf.shape[1] == E
        assert np.std(leaf) == pytest.approx(1 / math.sqrt(fan_in),
                                             rel=0.05), name
    # the router and the shared experts are plain matrices, as before
    assert np.std(moe["router"]) == pytest.approx(1 / math.sqrt(D), rel=0.05)
    assert np.std(moe["shared"]["w_down"]) == pytest.approx(
        1 / math.sqrt(2 * F), rel=0.05)
