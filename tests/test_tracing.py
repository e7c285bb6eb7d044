"""What the round carries into a profiler trace (``repro.tracing``).

- every round path puts its work under the ``fed.*`` phase scopes, which
  reach the compiled instructions' ``op_name`` metadata through ``grad``;
- the attention core is under ``model.attention``, forward and backward;
- every Pallas kernel has a literal, unique ``name=``;
- ``Trainer.compiles`` counts the round's jit cache misses.

The mesh paths are covered in ``tests/test_mesh.py``.
"""
import ast
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, tracing
from repro.analysis import hlo_check
from repro.configs.base import SubmodelConfig, get_reduced_config
from repro.data.synthetic import lm_batches
from repro.models import build_model

KERNELS = os.path.join(os.path.dirname(__file__), "..", "src", "repro",
                       "kernels")
ROUND_PHASES = {tracing.OFFSETS, tracing.CLIENT_PHASE, tracing.AGGREGATE}


def _lm(stagger=False, scheme="rolling"):
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=1, vocab=64,
                  d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16)
    model = build_model(cfg, remat=False)
    scfg = SubmodelConfig(scheme=scheme, capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.1,
                          stagger=stagger)
    batch = {k: jnp.asarray(v) for k, v in
             next(lm_batches(cfg.vocab, (2, 4, 1), 16, seed=0)).items()}
    return model, scfg, batch


def _round_hlo(fed, params, batch):
    return hlo_check.compiled_text(fed.round, params, batch, 0,
                                   jax.random.PRNGKey(1))


@pytest.mark.parametrize("path", ["fused_shared", "fused_staggered",
                                  "extract", "hetero", "mask"])
def test_round_path_scopes_reach_the_compiled_round(path):
    model, scfg, batch = _lm(stagger=path == "fused_staggered",
                             scheme="bernoulli" if path == "mask"
                             else "rolling")
    kw = {"fused_shared": dict(fused_forward="on"),
          "fused_staggered": dict(fused_forward="on"),
          "extract": dict(fused_forward="off"),
          "hetero": dict(mode="window", capacities=[1.0, 0.5, 0.5, 0.25]),
          "mask": dict(mode="mask")}[path]
    fed = api.fed_round(model, scfg, **kw)
    if path.startswith("fused"):
        assert fed.use_fused
    if path == "hetero":
        assert fed.hetero is not None
    found = hlo_check.scopes(_round_hlo(fed, model.init(jax.random.PRNGKey(0)),
                                        batch))
    assert ROUND_PHASES <= found, found


@pytest.mark.parametrize("mode", ["window", "mask"])
def test_server_opt_round_scopes_the_server_step(mode):
    model, scfg, batch = _lm(scheme="bernoulli" if mode == "mask"
                             else "rolling")
    fed = api.fed_round(model, scfg, mode=mode, server_opt="adam")
    params = model.init(jax.random.PRNGKey(0))
    state = fed.server_opt.init(params)
    hlo = hlo_check.compiled_text(
        lambda p, s, b: fed.round_with_server_opt(p, s, b, 0,
                                                  rng=jax.random.PRNGKey(1)),
        params, state, batch)
    assert ROUND_PHASES | {tracing.SERVER_STEP} <= hlo_check.scopes(hlo)


def test_attention_scope_on_forward_and_backward_ops():
    model, scfg, batch = _lm()
    fed = api.fed_round(model, scfg, fused_forward="on")
    names = hlo_check.op_names(_round_hlo(
        fed, model.init(jax.random.PRNGKey(0)), batch))
    attn = [n for n in names if tracing.ATTENTION in n]
    assert any("transpose(" not in n for n in attn), "no forward op"
    assert any("transpose(" in n for n in attn), "no backward op"
    # the attention core sits inside the client phase, never outside it
    assert {hlo_check.outermost_scope(n, "fed.") for n in attn} == {
        tracing.CLIENT_PHASE}


def test_expert_scopes_on_forward_and_backward_ops():
    """A share of an expert layer (the router over twice the experts held)
    puts ``model.moe`` and its ``dispatch`` and ``experts``
    children on the round's forward and transposed instructions."""
    base = get_reduced_config("deepseek_v3_671b")
    cfg = replace(base, n_layers=2, vocab=64, moe=replace(
        base.moe, router="softmax", router_experts=2 * base.moe.n_experts,
        norm_topk_prob=False, aux_loss="seq"))
    model = build_model(cfg, remat=True)
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=1, client_lr=0.1)
    fed = api.fed_round(model, scfg)
    assert fed.use_fused
    batch = {k: jnp.asarray(v) for k, v in
             next(lm_batches(cfg.vocab, (2, 1, 1), 16, seed=0)).items()}
    names = hlo_check.op_names(_round_hlo(
        fed, model.init(jax.random.PRNGKey(0)), batch))
    for scope in (tracing.MOE, tracing.MOE_DISPATCH, tracing.MOE_EXPERTS):
        ops = [n for n in names if scope in re.split(r"[/()]", n)]
        assert any("transpose(" not in n for n in ops), (scope, "forward")
        assert any("transpose(" in n for n in ops), (scope, "backward")


def test_outermost_scope_strips_grad_wrappers():
    name = ("jit(step)/transpose(jvp(fed.client_phase))/jvp(fed.client_phase)"
            "/checkpoint/rematted_computation/model.attention/dot_general")
    assert hlo_check.outermost_scope(name, "fed.") == tracing.CLIENT_PHASE
    assert hlo_check.outermost_scope(name, "model.") == tracing.ATTENTION
    assert hlo_check.outermost_scope("jit(step)/add", "fed.") == ""


def test_scoped_restores_the_outer_scope_when_it_nests_in_itself():
    @tracing.scoped("fed.x")
    def phase(x, depth):
        return phase(x, depth - 1) if depth else x * 2.0

    def f(x):
        return jnp.sin(phase(x, 1))

    names = set(hlo_check.op_names(hlo_check.compiled_text(f, 1.0)))
    assert {n for n in names if n.endswith("/sin")} == {"jit(f)/sin"}
    assert {n for n in names if n.endswith("/mul")} == {
        "jit(f)/fed.x/fed.x/mul"}


def _pallas_calls():
    for fname in sorted(os.listdir(KERNELS)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(KERNELS, fname)) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                yield fname, node


def test_every_pallas_call_has_a_literal_unique_name():
    names = []
    for fname, call in _pallas_calls():
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{fname}:{call.lineno} pallas_call has no name="
        assert isinstance(kw["name"], ast.Constant) and isinstance(
            kw["name"].value, str), f"{fname}:{call.lineno} name= not literal"
        names.append(kw["name"].value)
    assert len(names) == 13
    assert len(set(names)) == len(names), sorted(names)


def _triple_fed():
    def loss(w, b):
        r = w["w"] - b["target"].mean()
        return 0.5 * jnp.mean(r * r), {}
    abstract = {"w": jax.ShapeDtypeStruct((8,), jnp.float32)}
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.3)
    return api.fed_round((loss, abstract, {"w": ("d_ff",)}), scfg)


def test_trainer_compiles_count_round_cache_misses():
    trainer = api.Trainer(_triple_fed(), {"w": jnp.zeros(8)}, rng=1)
    assert trainer.compiles == 0

    def batch(n):
        return {"target": np.ones((2, 4, n), np.float32)}

    trainer.step(batch(3))
    assert trainer.compiles == 1
    trainer.step(batch(3))
    assert trainer.compiles == 1
    trainer.step(batch(5))           # a new batch shape: one miss
    assert trainer.compiles == 2
    trainer.step(batch(3))           # back to a shape already compiled
    trainer.step(batch(5))
    assert trainer.compiles == 2


def test_compile_counters_take_only_their_own_misses():
    x = jnp.ones(3)
    outer, inner = tracing.CompileCounter(), tracing.CompileCounter()
    with outer:
        jax.jit(lambda x: x + 1.0)(x)
        with inner:
            jax.jit(lambda x: x * 3.0)(x)
    assert (outer.count, inner.count) == (1, 1)
