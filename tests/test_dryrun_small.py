"""Distribution tests on the host mesh (1 real device): the jitted fed round
lowers and runs under a mesh with sharding policy installed, and the
sharding machinery produces valid specs for every architecture."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SubmodelConfig, get_reduced_config, list_archs
from repro.core.fedavg import make_window_fed_round
from repro.data.synthetic import lm_batches
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.sharding import policy as pol
from repro.sharding.ctx import ActivationPolicy, activation_policy, \
    default_rules


def test_fed_round_under_mesh_policy():
    """Window fed round traces + runs with sharding constraints active."""
    cfg = get_reduced_config("tinyllama_1_1b")
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=1,
                          clients_per_round=2, client_lr=0.1,
                          axes=("d_ff", "heads", "kv_heads"))
    fed = make_window_fed_round(m.loss, scfg, m.abstract_params(), m.axes())
    mesh = make_host_mesh(1, 1)
    batch = {k: jnp.asarray(v) for k, v in next(
        lm_batches(cfg.vocab, (1, 2, 2), 16)).items()}
    with mesh, activation_policy(ActivationPolicy(mesh, default_rules())):
        p2, metrics = jax.jit(fed.round)(params, batch, 0,
                                         jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_valid(arch):
    """Every full config gets consistent PartitionSpecs (no duplicate mesh
    axes, divisibility respected) on the host mesh."""
    from jax.sharding import PartitionSpec as P
    cfg = get_reduced_config(arch)
    m = build_model(cfg)
    ab, axes = m.abstract_params(), m.axes()
    mesh = make_host_mesh(1, 1)
    rules = pol.default_param_rules()
    specs = pol.param_specs(ab, axes, rules, mesh)

    def walk(s, a):
        if isinstance(s, dict):
            for k in s:
                walk(s[k], a[k])
            return
        assert isinstance(s, P)
        flat = [e for e in s if e is not None]
        assert len(flat) == len(set(map(str, flat)))

    walk(specs, ab)


def test_constrain_tree_noop_without_policy():
    from repro.sharding.policy import constrain_tree
    tree = {"w": jnp.ones((4, 4))}
    out = constrain_tree(tree, {"w": ("d_model", "d_ff")})
    np.testing.assert_array_equal(np.asarray(out["w"]), 1.0)
