"""DeepSeek-V2 on one chip's share of its experts: the program's fused round
against the plain reference of ``bench/reference/deepseek_v2.py``, the
share against the uncut expert layer, YaRN rope, dropless dispatch on a
share, and the lane rule that keeps off-grid windows away from Mosaic.

The tiny configuration keeps every mechanism of DeepSeek-V2-Lite at small
widths: no q compression, YaRN, 4 of 16 routed experts held, top-6 of a
softmax over all 16 without renormalisation, 2 shared experts, a dense
layer then 2 expert layers, the sequence-wise balance loss."""
import json
import os
import sys
from collections import Counter
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

from bench import check, spec  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from repro import api  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.models import build_model, layers, moe  # noqa: E402
from repro.models.attention import _mla_scale  # noqa: E402

YARN = dict(type="yarn", factor=40, mscale=0.707, mscale_all_dim=0.707,
            original_max_position_embeddings=4096, beta_fast=32, beta_slow=1)
PROGRAM_MLA = dict(yarn_factor=40.0, yarn_original_max=4096,
                   yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
                   yarn_mscale_all_dim=0.707)

TINY = dict(
    name="deepseek-v2-tiny", reference="deepseek_v2", hidden_size=128,
    intermediate_size=256, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=3, first_k_dense_replace=1, vocab_size=256,
    n_routed_experts=4, num_experts_per_tok=6, moe_intermediate_size=64,
    n_shared_experts=2, scoring_func="softmax", norm_topk_prob=False,
    seq_aux=True, aux_loss_alpha=0.001, q_lora_rank=None, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    rope_scaling=YARN, rms_norm_eps=1e-6, rope_theta=10000.0,
    hidden_act="silu", tie_word_embeddings=False,
    published={"n_routed_experts": 16},
    program={"moe": {"router_experts": 16, "norm_topk_prob": False,
                     "aux_loss": "seq", "aux_loss_weight": 0.001},
             "mla": PROGRAM_MLA})


def _mix():
    with open(os.path.join(spec.BENCH, "traffic", "silo-4x2048.json")) as fh:
        mix = json.load(fh)
    return {**mix, "seqs_per_step": 2, "seq_len": 64}


def _cell(config=TINY):
    return spec.Cell("dsv2-tiny", 1, dict(config), _mix(), {}, [], [])


def test_reference_matches_the_program():
    """The program's fused round through the benchmark's harness against
    the plain reference, as the dense cells are pinned in ``bench/tests``:
    losses and both change norms agree to float32 roundoff, and every leaf
    moves.  Top-k routing is not smooth: once the two sides' float32
    roundoff has grown over three rounds, a near-tied choice can flip and
    move ``step3_change`` far past 1e-5 on a few seeds in ten at these
    widths (a float32 reference differs from a float64 one by as much), so
    the pin is one fixed seed's deterministic CPU arithmetic."""
    from bench.run import Harness
    h = Harness(_cell())
    assert h.fed.use_fused
    trainer, feed, s32 = h.start(2**32 + 23)
    prog, batches = h.checked_rounds(trainer, feed, s32)
    ref_readings = h.reference(s32, batches)
    assert np.isfinite(prog.losses).all()
    numbers = check.compare(prog, ref_readings)
    assert numbers["loss"] < 1e-5, numbers
    assert numbers["step1_change"] < 1e-5, numbers
    assert numbers["step3_change"] < 1e-5, numbers
    assert check.moved_leaves(ref_readings) == sorted(ref_readings.step1)
    assert "moe_layers/attn/wq" in prog.step1
    assert not any("w_dq" in k or "q_norm" in k for k in prog.step1)


def _layer_params(key, D, E, F, n_shared, n_router):
    ks = jax.random.split(key, 7)
    n = jax.random.normal
    return {"router": n(ks[0], (D, n_router)) / np.sqrt(D),
            "w_gate": n(ks[1], (E, D, F)) / np.sqrt(D),
            "w_up": n(ks[2], (E, D, F)) / np.sqrt(D),
            "w_down": n(ks[3], (E, F, D)) / np.sqrt(F),
            "shared": {"w_gate": n(ks[4], (D, n_shared * F)) / np.sqrt(D),
                       "w_up": n(ks[5], (D, n_shared * F)) / np.sqrt(D),
                       "w_down": n(ks[6], (n_shared * F, D))
                       / np.sqrt(n_shared * F)}}


def test_the_shares_add_up_to_the_uncut_layer():
    """At one input, the routed outputs of the 4 shares of a 16-expert
    layer, each with its own 4 experts and the whole router, plus the
    shared experts once, are the uncut layer's output.  Share s is the
    program's layer holding experts 4s .. 4s+3 first: the router's columns
    rolled so that they lead, which routing cannot tell from holding
    them."""
    cfg = _cell().model_config()
    D, F, E = cfg.d_model, cfg.moe.d_ff, cfg.moe.n_router
    held = cfg.moe.n_experts
    full = _layer_params(jax.random.PRNGKey(3), D, E, F, cfg.moe.n_shared, E)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, D))
    d = ref.dims({**TINY, "n_routed_experts": E})
    want, want_aux = ref.experts(full, x, 0, d)
    total, auxes = 0.0, []
    for s in range(E // held):
        cut = slice(held * s, held * (s + 1))
        p = {"router": jnp.roll(full["router"], -held * s, axis=1),
             "w_gate": full["w_gate"][cut], "w_up": full["w_up"][cut],
             "w_down": full["w_down"][cut],
             "shared": jax.tree_util.tree_map(
                 lambda w: w if s == 0 else jnp.zeros_like(w),
                 full["shared"])}
        out, aux = moe.moe_apply(p, x, cfg)
        total = total + out
        auxes.append(float(aux))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # the balance loss is over the whole router: every share reads it alike
    np.testing.assert_allclose(auxes, float(want_aux), rtol=1e-5)


def test_yarn_range_frequencies_and_scale():
    assert layers.yarn_correction_range(64, 10000.0, 32, 1, 4096) == (10, 23)
    assert ref.yarn_range(64, 10000.0, 32, 1, 4096) == (10, 23)
    with open(os.path.join(spec.BENCH, "configs",
                           "deepseek-v2-lite.json")) as fh:
        published = json.load(fh)
    cfg = _cell(published).model_config()
    # mscale = 0.1 * 0.707 * ln 40 + 1 = 1.260804; 1.260804^2 / sqrt(192)
    assert _mla_scale(cfg) == pytest.approx(0.1147214, abs=1e-7)
    assert ref.dims(published).softmax_scale == pytest.approx(0.1147214,
                                                              abs=1e-7)
    freqs = layers.yarn_freqs(64, 10000.0, 40.0, 32.0, 1.0, 4096)
    want = ref.rope_inv_freq(published)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-6)
    plain = layers.rope_freqs(64, 10000.0)
    # extrapolated below the range, interpolated by the factor above it
    np.testing.assert_array_equal(np.asarray(freqs[:10]),
                                  np.asarray(plain[:10]))
    np.testing.assert_allclose(np.asarray(freqs[23:]),
                               np.asarray(plain[23:]) / 40, rtol=1e-6)
    # no scaling: the rope every other model runs, bit for bit
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    np.testing.assert_array_equal(
        np.asarray(layers.apply_rope(x, pos, 10000.0)),
        np.asarray(layers.apply_rope(x, pos, 10000.0,
                                     freqs=layers.rope_freqs(16, 10000.0))))


def test_dropless_keeps_every_token_under_full_imbalance():
    """Every token's first choice is held expert 0, far past any capacity
    bucket.  A share runs the dense path whatever path the model names, so
    it drops nothing and equals the plain reference layer; a whole layer
    on the dropping path drops, under the same routing."""
    cfg = _cell().model_config()
    D, F, E = cfg.d_model, cfg.moe.d_ff, cfg.moe.n_router
    p = _layer_params(jax.random.PRNGKey(5), D, cfg.moe.n_experts, F,
                      cfg.moe.n_shared, E)
    p["router"] = p["router"].at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 16, D))) + 1.0
    _, idx, _ = moe._route(p["router"], x.reshape(-1, D), cfg)
    assert (np.asarray(idx[:, 0]) == 0).all()
    want, want_aux = ref.experts(p, x, 0, ref.dims(TINY))
    for path in moe.PATHS:
        got, aux = moe.moe_apply(p, x, cfg, path=path)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(aux), float(want_aux), rtol=1e-5)
    whole = replace(cfg, moe=replace(cfg.moe, n_experts=E, router_experts=0))
    p_whole = _layer_params(jax.random.PRNGKey(5), D, E, F, cfg.moe.n_shared,
                            E)
    p_whole["router"] = p_whole["router"].at[:, 0].set(1.0)
    kept, _ = moe.moe_apply(p_whole, x, whole, path="dense")
    dropped, _ = moe.moe_apply(p_whole, x, whole, path="dropping")
    assert not np.allclose(np.asarray(kept), np.asarray(dropped),
                           rtol=1e-3, atol=1e-4)


def test_extract_refuses_a_share():
    """An extracted experts window has lost its expert ids, so the extract
    round refuses a share instead of routing over the wrong experts."""
    cell = _cell()
    model = build_model(cell.model_config(), remat=False)
    scfg = cell.submodel_config()
    fed = api.fed_round(model, scfg, fused_forward="off")
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 1, 2, 16), jnp.int32)
    with pytest.raises(ValueError, match="share"):
        fed.round(params, {"tokens": tokens}, 0, jax.random.PRNGKey(1))


def test_a_whole_layer_keeps_its_sub_zoo():
    """Without a share the router is under the ``experts`` axis, as
    before."""
    config = {**TINY, "program": {"mla": PROGRAM_MLA}}
    config.pop("published")
    cfg = _cell(config).model_config()
    assert not cfg.moe.is_share
    axes = build_model(cfg).axes()
    assert axes["moe_layers"]["moe"]["router"][-1] == "experts"
    share = build_model(_cell().model_config()).axes()
    assert share["moe_layers"]["moe"]["router"][-1] == "router_experts"


@pytest.mark.parametrize("n,win,pallas", [
    (10944, 5472, False),     # the dense layer's half window: 96-lane blocks
    (1408, 704, False),       # an expert's half window: 88-lane blocks
    (64, 32, False),          # narrower than one lane tile
    (11008, 5504, True),      # deepseek-llm-7b's half window: on the grid
    (2816, 1408, True),       # the shared experts' half window
])
def test_off_grid_windows_take_the_counted_jnp_arm(monkeypatch, n, win,
                                                   pallas):
    """A window whose lane block is neither a multiple of 128 nor the whole
    dim takes the jnp arm, forward and dx, and each is counted; one on the
    grid takes Pallas.  Traced, not run: the arm is chosen at trace
    time."""
    monkeypatch.setattr(dispatch, "ORACLE_FALLBACKS", Counter())
    x = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, n), jnp.float32)

    def f(x, w):
        return dispatch.rolling_matmul(x, w, win, win, backend="pallas").sum()

    jax.eval_shape(jax.grad(f, argnums=(0, 1)), x, w)
    want = Counter() if pallas else Counter(rolling_matmul=1,
                                            rolling_matmul_dx=1)
    assert dispatch.ORACLE_FALLBACKS == want
    if not pallas:   # and the counted arm is the oracle's answer
        xv = jax.random.normal(jax.random.PRNGKey(0), (16, 128))
        wv = jax.random.normal(jax.random.PRNGKey(1), (128, n))
        np.testing.assert_array_equal(
            np.asarray(dispatch.rolling_matmul(xv, wv, win, win,
                                               backend="pallas")),
            np.asarray(kref.rolling_matmul_ref(xv, wv, win, win)))


def test_share_config_counts_the_whole_router():
    cfg = _cell().model_config()
    whole = replace(cfg, moe=replace(cfg.moe, router_experts=0))
    assert cfg.n_params() - whole.n_params() == 2 * cfg.d_model * (16 - 4)
