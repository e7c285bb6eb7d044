"""Substrate tests: data pipeline, checkpointing, optimizers, sharding
policy, HLO cost analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import load as ckpt_load, save as ckpt_save
from repro.data.federated import (FederatedDataset, dirichlet_partition,
                                  label_limited_partition)
from repro.data.synthetic import BigramLM, SyntheticCIFAR, lm_batches
from repro.optim.optimizers import adamw, cosine_schedule, momentum, sgd


# -- data ---------------------------------------------------------------------


def test_label_limited_partition():
    labels = np.random.default_rng(0).integers(0, 10, 1000)
    parts = label_limited_partition(labels, 20, 2, seed=0)
    assert sum(len(p) for p in parts) == 1000
    for p in parts:
        if len(p):
            assert len(np.unique(labels[p])) <= 2


def test_dirichlet_partition_covers_all():
    labels = np.random.default_rng(0).integers(0, 10, 500)
    parts = dirichlet_partition(labels, 10, alpha=0.1, seed=0)
    allidx = np.concatenate(parts)
    assert len(allidx) == 500 and len(np.unique(allidx)) == 500


def test_round_batch_layout():
    data = SyntheticCIFAR(n_classes=4, image_size=8, n_train=200, n_test=10)
    parts = label_limited_partition(data.train["labels"], 8, 2)
    fd = FederatedDataset(data.train, parts)
    b = fd.round_batch(fd.sample_clients(4), k_steps=3, mb_size=5)
    assert b["images"].shape == (3, 4, 5, 8, 8, 3)
    assert b["labels"].shape == (3, 4, 5)


def test_bigram_lm_learnable():
    src = BigramLM(32, seed=0)
    toks = src.sample(np.random.default_rng(0), 4, 64)
    assert toks.shape == (4, 64) and toks.max() < 32


def test_lm_batches_vision():
    it = lm_batches(100, (2, 3), 16, vision=(4, 8))
    b = next(it)
    assert b["tokens"].shape == (2, 3, 16)
    assert b["patches"].shape == (2, 3, 4, 8)


# -- checkpoint ---------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16),
                  "d": jnp.asarray(3, jnp.int32)},
            "opt": (jnp.zeros(2), jnp.ones(2))}
    path = os.path.join(tmp_path, "ck.npz")
    ckpt_save(path, tree, {"round": 7})
    back, meta = ckpt_load(path)
    assert meta["round"] == 7
    assert back["b"]["c"].dtype.name == "bfloat16"
    np.testing.assert_array_equal(np.asarray(tree["a"]), back["a"])
    np.testing.assert_array_equal(
        np.asarray(tree["b"]["c"], np.float32),
        np.asarray(back["b"]["c"], np.float32))
    assert isinstance(back["opt"], tuple) and len(back["opt"]) == 2


# -- optimizers ---------------------------------------------------------------


@pytest.mark.parametrize("opt", [sgd(0.1), momentum(0.05), adamw(0.05)])
def test_optimizers_descend(opt):
    w = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(w)
    loss = lambda p: jnp.sum(p["w"] ** 2)  # noqa: E731
    for _ in range(60):
        g = jax.grad(loss)(w)
        w, state = opt.update(g, state, w)
    assert float(loss(w)) < 0.05


def test_cosine_schedule():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 1e-6
    assert float(lr(100)) < 1e-6


# -- sharding policy ----------------------------------------------------------


def test_leaf_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.policy import leaf_spec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = {"d_ff": "model", "heads": "model", "d_model": "data"}
    spec = leaf_spec((32, 96), ("d_model", "d_ff"), rules, mesh)
    assert spec == P("data", "model")
    # duplicate mesh axis: second dim falls back to None
    spec2 = leaf_spec((96, 96), ("d_ff", "d_ff"), rules, mesh)
    assert spec2 == P("model", None)


def test_leaf_spec_divisibility():
    from jax.sharding import PartitionSpec as P
    from repro.sharding.policy import leaf_spec
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("model",))
    rules = {"heads": "model"}
    spec = leaf_spec((25, 4), ("heads", "head_dim"), rules, mesh)
    assert spec == P("model", None)  # 25 % 1 == 0 trivially sharded
