"""End-to-end behaviour tests: every assigned architecture builds, runs its
forward and trains a step."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config, get_reduced_config, list_archs
from repro.models import build_model

ARCHS = list_archs()


def _batch(cfg, B=2, S=16, seed=1):
    key = jax.random.PRNGKey(seed)
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    batch = {"tokens": jax.random.randint(key, shape, 0, cfg.vocab)}
    if cfg.vision_stub:
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(seed + 1), (B, cfg.vision_patches,
                                           cfg.vision_d))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """Reduced config: one SGD step, finite loss, grads touch all params."""
    cfg = get_reduced_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    m = build_model(cfg, moe_path="dense", remat=False)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(m.loss, has_aux=True)(
        params, batch)
    assert jnp.isfinite(loss), arch
    assert loss.shape == ()
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert gnorm > 0
    new = jax.tree_util.tree_map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2, _ = m.loss(new, batch)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_logits_shape(arch):
    cfg = get_reduced_config(arch)
    m = build_model(cfg, moe_path="dense", remat=False)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux, h = m.forward(params, batch["tokens"], batch)
    S_total = batch["tokens"].shape[1] + (cfg.vision_patches
                                          if cfg.vision_stub else 0)
    if cfg.n_codebooks:
        assert logits.shape == (2, S_total, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (2, S_total, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_abstract(arch):
    """Full (production) configs build abstractly with the exact dims."""
    cfg = get_config(arch)
    m = build_model(cfg)
    ab = m.abstract_params()
    import numpy as np
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(ab))
    expected = {
        "deepseek_v3_671b": 671e9, "mixtral_8x22b": 140e9,
        "qwen3_32b": 32.8e9, "qwen3_14b": 14.8e9, "deepseek_7b": 7e9,
        "tinyllama_1_1b": 1.1e9, "mamba2_130m": 0.13e9,
        "musicgen_large": 3.3e9, "phi_3_vision_4_2b": 3.8e9,
        "hymba_1_5b": 1.6e9,
    }[arch]
    assert abs(n - expected) / expected < 0.25, (arch, n)


def test_tied_embeddings():
    cfg = get_reduced_config("mamba2_130m")
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    assert "head" not in params  # mamba2 ties the LM head
