"""The plain reference against the program's round (``api.fed_round`` through
``api.Trainer``) at a small size on the CPU, for each traffic mix's round
structure."""
import numpy as np
import pytest

from bench import check
from bench.reference import round as ref_round
from bench.run import Harness
from bench.tests import tiny


@pytest.mark.parametrize("workload", ["ds7b-silo", "phi3-partition"])
def test_reference_matches_the_program(workload):
    h = Harness(tiny.cell(workload))
    trainer, feed, s32 = h.start(2**32 + 17)
    prog, batches = h.checked_rounds(trainer, feed, s32)
    ref = h.reference(s32, batches)
    assert np.isfinite(prog.losses).all()
    numbers = check.compare(prog, ref)
    assert numbers["loss"] < 1e-5, numbers
    assert numbers["step1_change"] < 1e-5, numbers
    assert numbers["step3_change"] < 1e-5, numbers
    # every leaf moves: the gradient of no leaf is nought
    assert check.moved_leaves(ref) == sorted(ref.step1)


def test_expert_latent_round_runs_through_the_harness():
    """The tiny expert and latent-attention configuration runs the timed
    path's first rounds (the program's fused round) and moves every leaf;
    its own plain reference comes with the configuration that uses it."""
    from bench import spec
    silo = spec.load_cell("ds7b-silo")
    cell = spec.Cell("moe-mla", 1, dict(tiny.MOE_MLA),
                     {**silo.mix, "seq_len": 64}, silo.limits, [], [])
    h = Harness(cell)
    assert h.fed.use_fused
    trainer, feed, s32 = h.start(2**31 + 9)
    prog, batches = h.checked_rounds(trainer, feed, s32)
    assert prog.losses.shape == (3, 2, 1) and np.isfinite(prog.losses).all()
    assert {k for k in prog.step1 if k.startswith("moe_layers/moe/")} == {
        f"moe_layers/moe/{k}" for k in ("router", "w_gate", "w_up", "w_down",
                                        "shared/w_gate", "shared/w_up",
                                        "shared/w_down")}
    assert min(prog.step1.values()) > 0


@pytest.mark.parametrize("stagger", [False, True])
@pytest.mark.parametrize("n_ff,n_kv,clients", [(11008, 32, 1), (8192, 32, 2),
                                                (256, 4, 4), (300, 6, 3)])
def test_window_offsets_match_the_programs_schedule(stagger, n_ff, n_kv,
                                                    clients):
    from repro.configs.base import SubmodelConfig
    from repro.core.masking import make_scheme
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, stagger=stagger,
                          clients_per_round=clients, seed=0)
    scheme = make_scheme(scfg, {("d_ff", n_ff): None,
                                ("kv_heads", n_kv): None,
                                ("heads", n_kv): None})
    config = {"intermediate_size": n_ff, "num_key_value_heads": n_kv,
              "num_attention_heads": n_kv}
    plan = ref_round.plan(config, 0.5)
    assert plan.ff_win == scheme.sizes[("d_ff", n_ff)]
    assert plan.kv_win == scheme.sizes[("kv_heads", n_kv)]
    for r in range(7):
        want = scheme.offsets(None, r, clients)
        ff, kv = ref_round.offsets(plan, 0, r, clients, stagger)
        np.testing.assert_array_equal(ff, np.asarray(want[("d_ff", n_ff)]))
        np.testing.assert_array_equal(kv,
                                      np.asarray(want[("kv_heads", n_kv)]))
        np.testing.assert_array_equal(kv, np.asarray(want[("heads", n_kv)]))


def test_extract_and_scatter_are_inverse():
    import jax.numpy as jnp
    plan = ref_round.Plan(d_ff=8, ff_win=4, kv=4, kv_win=2, group=1,
                          n_windows=2)
    params = {"layers": {"mlp": {"w_gate": jnp.arange(2 * 3 * 8.0)
                                 .reshape(2, 3, 8)},
                         "attn": {"wo": jnp.ones((2, 4, 2, 3))}},
              "embed": jnp.ones((5, 3))}
    sub = ref_round.extract(params, 4, 2, plan)
    assert sub["layers"]["mlp"]["w_gate"].shape == (2, 3, 4)
    assert sub["layers"]["attn"]["wo"].shape == (2, 2, 2, 3)
    zeros = {"layers": {"mlp": {"w_gate": jnp.zeros((2, 3, 8))},
                        "attn": {"wo": jnp.zeros((2, 4, 2, 3))}},
             "embed": jnp.zeros((5, 3))}
    back = ref_round.add_scattered(zeros, sub, 4, 2, plan)
    g = np.asarray(back["layers"]["mlp"]["w_gate"])
    np.testing.assert_array_equal(g[..., 4:],
                                  np.asarray(params["layers"]["mlp"]
                                             ["w_gate"])[..., 4:])
    assert (g[..., :4] == 0).all()
    assert float(back["layers"]["attn"]["wo"][:, :2].sum()) == 0.0
