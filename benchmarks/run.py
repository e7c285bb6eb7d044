"""Benchmark harness — one entry per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--rounds N]
    [--full]

Paper artifacts (CPU-feasible scale of §5's protocol):
  fig1_heterogeneity   rolling vs random masking, high data heterogeneity
  fig2_low_hetero      same, low heterogeneity (L=5)
  fig3_capacity        model-homogeneous beta=1 vs beta=1/16 bounds
  tab1_generalization  train-test gap: random masking vs full model
  tab4_heterofl        rolling vs static (HeteroFL) masking
  thm1_residual        convergence residual vs capacity on the quadratic
                       (validates the Theorem-1 residual structure)
  thm5_stability       neighboring-dataset stability, masked vs full

System benches:
  kernels              Pallas kernels vs jnp oracle timings (interpret mode)
  fed_round            window-mode fed round wall time (reduced arch)
  fed_round_async      FedBuff async server (repro.fleet) vs the sync
                       barrier: bitwise M=N anchor + rounds/virtual-sec
                       under straggler fractions {0, 0.25, 0.5}
  fed_round_mesh       shard_map round on a forced-host-device mesh:
                       bitwise gate vs single device + 2k-client scale arm

Prints ``name,metric,value`` CSV rows and writes
experiments/bench_results.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

RESULTS = {}
ROWS = []


def emit(name, metric, value):
    ROWS.append(f"{name},{metric},{value}")
    RESULTS.setdefault(name, {})[metric] = value
    print(f"{name},{metric},{value}", flush=True)


def _interleaved_median_ms(steps, args, n=5):
    """Median per-call wall time (ms) for each jitted step, reps
    INTERLEAVED round-robin across the arms: a machine-load spike lands on
    the same rep of every arm instead of biasing whichever arm happened to
    run during it, so the arm-to-arm RATIO (what the speedup gates consume)
    stays stable even when absolute times wobble.  Each rep blocks until
    ready — per-call latency, not pipelined throughput."""
    import jax

    outs, times = {}, {name: [] for name in steps}
    for name, step in steps.items():  # compile outside the timed region
        outs[name] = step(*args)
        jax.block_until_ready(jax.tree_util.tree_leaves(outs[name])[0])
    for _ in range(n):
        for name, step in steps.items():
            t0 = time.time()
            out = step(*args)
            jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
            times[name].append(time.time() - t0)
    med = {name: float(np.median(ts)) * 1e3 for name, ts in times.items()}
    return med, outs


# ---------------------------------------------------------------------------
# Paper experiments
# ---------------------------------------------------------------------------


def _experiment(labels_per_client, rounds, seed=0, **kw):
    from repro.core.paper_protocol import PaperExperiment
    return PaperExperiment(n_clients=10, participate=4,
                           labels_per_client=labels_per_client,
                           n_train=1500, n_test=400, mb=8, seed=seed, **kw)


def fig1_heterogeneity(rounds):
    exp = _experiment(2, rounds)
    for scheme in ("rolling", "random"):
        r = exp.run(scheme, rounds=rounds)
        emit("fig1_heterogeneity", f"{scheme}_final_test_loss",
             round(r["final"]["test_loss"], 4))
        emit("fig1_heterogeneity", f"{scheme}_final_test_acc",
             round(r["final"]["test_acc"], 4))
        RESULTS.setdefault("curves", {})[f"fig1_{scheme}"] = r["curve"]


def fig2_low_hetero(rounds):
    exp = _experiment(5, rounds)
    for scheme in ("rolling", "random"):
        r = exp.run(scheme, rounds=rounds)
        emit("fig2_low_hetero", f"{scheme}_final_test_loss",
             round(r["final"]["test_loss"], 4))
        emit("fig2_low_hetero", f"{scheme}_final_test_acc",
             round(r["final"]["test_acc"], 4))


def fig3_capacity(rounds):
    exp = _experiment(2, rounds)
    for beta, tag in ((1.0, "beta1"), (0.0625, "beta1_16")):
        r = exp.run("rolling", rounds=rounds, uniform_cap=beta)
        emit("fig3_capacity", f"{tag}_final_test_acc",
             round(r["final"]["test_acc"], 4))


def tab1_generalization(rounds):
    exp = _experiment(2, rounds)
    for scheme in ("random", "full"):
        r = exp.run(scheme, rounds=rounds)
        emit("tab1_generalization", f"{scheme}_loss_gap",
             round(r["gap"]["loss_gap"], 4))
        emit("tab1_generalization", f"{scheme}_acc_gap",
             round(r["gap"].get("acc_gap", 0.0), 4))


def tab4_heterofl(rounds):
    exp = _experiment(2, rounds)
    for scheme in ("rolling", "static"):
        r = exp.run(scheme, rounds=rounds)
        emit("tab4_heterofl", f"{scheme}_final_test_acc",
             round(r["final"]["test_acc"], 4))
        emit("tab4_heterofl", f"{scheme}_final_test_loss",
             round(r["final"]["test_loss"], 4))


def thm1_residual(rounds):
    """Masked training's excess suboptimality grows as capacity falls,
    tracking the Theorem-1 residual term."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import SubmodelConfig
    from repro.core.theory import QuadraticProblem, thm1_residual as resid

    prob = QuadraticProblem.make(n_clients=4, m=64, d=16, hetero=0.3, seed=0)
    consts = prob.constants()
    w_star = prob.w_star()
    f_star = prob.global_loss(jnp.asarray(w_star, jnp.float32))
    rng = np.random.default_rng(0)

    def loss(w, batch):
        A = prob.A.reshape(-1, prob.dim)[batch["idx"]]
        b = prob.b.reshape(-1)[batch["idx"]]
        r = A @ w["w"] - b
        return 0.5 * jnp.mean(r * r), {}

    def batches():
        while True:
            yield {"idx": jnp.asarray(rng.integers(0, 4 * 64, (2, 4, 16)))}

    ab = {"w": jax.ShapeDtypeStruct((prob.dim,), jnp.float32)}
    excesses = {}
    for p in (1.0, 0.7, 0.4):
        scfg = SubmodelConfig(scheme="bernoulli", capacity=p, local_steps=2,
                              clients_per_round=4, client_lr=0.05)
        fed = api.fed_round((loss, ab, {"w": ("d_model",)}), scfg,
                            capacities=np.full(4, p))
        trainer = api.Trainer(fed, {"w": jnp.zeros(prob.dim)},
                              rng=jax.random.PRNGKey(1))
        params, _ = trainer.run(batches(), rounds * 10)
        excess = prob.global_loss(params["w"]) - f_star
        excesses[p] = float(excess)
        bound = resid(consts["L"], consts["mu"], G=2.0, W=2.0, d=prob.dim,
                      probs=np.full(4, p))
        emit("thm1_residual", f"excess_p{p}", round(float(excess), 5))
        emit("thm1_residual", f"bound_p{p}", round(bound, 3))
    emit("thm1_residual", "monotone_in_masking",
         int(excesses[0.4] >= excesses[0.7] >= excesses[1.0] - 1e-6))


def thm5_stability(rounds):
    """E||A(S)-A(S')|| on neighboring datasets: masked vs full training."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import SubmodelConfig
    from repro.core.stability import stability_experiment

    d, n_per = 16, 32
    rng = np.random.default_rng(0)
    Xs = rng.standard_normal((4, n_per, d)).astype(np.float32)
    w_true = rng.standard_normal(d).astype(np.float32)
    ys = (Xs @ w_true + 0.1 * rng.standard_normal((4, n_per))).astype(
        np.float32)
    ab = {"w": jax.ShapeDtypeStruct((d,), jnp.float32)}

    def make_batches(X, y):
        brng = np.random.default_rng(42)

        def gen():
            while True:
                idx = brng.integers(0, n_per, (2, 4, 8))
                xb = np.stack([[X[c][idx[k, c]] for c in range(4)]
                               for k in range(2)])
                yb = np.stack([[y[c][idx[k, c]] for c in range(4)]
                               for k in range(2)])
                yield {"x": jnp.asarray(xb), "y": jnp.asarray(yb)}
        return gen()

    def loss(w, b):
        r = jnp.einsum("md,d->m", b["x"], w["w"]) - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    dists = {}
    for p, tag in ((1.0, "full"), (0.5, "masked")):
        scfg = SubmodelConfig(scheme="bernoulli", capacity=p, local_steps=2,
                              clients_per_round=4, client_lr=0.02)

        def batches_fn(perturbed, seed, p=p):
            Xp, yp = np.copy(Xs), np.copy(ys)
            if perturbed:
                prng = np.random.default_rng(123 + seed)
                Xp[0, 0] = prng.standard_normal(d)
                yp[0, 0] = prng.standard_normal()
            return make_batches(Xp, yp)

        def make_fed(p=p, scfg=scfg):
            return api.fed_round((loss, ab, {"w": ("d_model",)}), scfg,
                                 capacities=np.full(4, p))

        # Theorem-5 regime: small steps, early stopping — path stability,
        # not the (algorithm-independent) optimum shift, dominates.
        dist, _ = stability_experiment(make_fed, {"w": jnp.zeros(d)},
                                       batches_fn, rounds,
                                       jax.random.PRNGKey(0), n_pairs=2)
        dists[tag] = dist
        emit("thm5_stability", f"{tag}_distance", round(dist, 6))
    emit("thm5_stability", "masked_more_stable",
         int(dists["masked"] <= dists["full"] + 1e-9))


# ---------------------------------------------------------------------------
# System benches
# ---------------------------------------------------------------------------


def kernels(rounds):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.masked_update import masked_sgd_2d
    from repro.kernels.rolling_matmul import rolling_matmul

    p = jax.random.normal(jax.random.PRNGKey(0), (512, 1024))
    m = (jax.random.uniform(jax.random.PRNGKey(1), p.shape) > 0.5).astype(
        jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), p.shape)

    for name, fn in (
        ("masked_sgd_pallas", lambda: masked_sgd_2d(p, m, g, 0.1)),
        ("masked_sgd_ref", lambda: ref.masked_sgd_ref(p, m, g, 0.1)),
    ):
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn())  # warmup/compile
        t0 = time.time()
        for _ in range(5):
            jax.block_until_ready(jfn())
        emit("kernels", f"{name}_us", round((time.time() - t0) / 5 * 1e6, 1))

    x = jax.random.normal(jax.random.PRNGKey(3), (256, 512))
    w = jax.random.normal(jax.random.PRNGKey(4), (512, 1024))
    err = float(jnp.max(jnp.abs(
        rolling_matmul(x, w, 128, 256)
        - ref.rolling_matmul_ref(x, w, 128, 256))))
    emit("kernels", "rolling_matmul_maxerr", f"{err:.2e}")

    from repro.kernels import dispatch
    emit("kernels", "auto_backend", dispatch.resolve_backend())
    derr = float(jnp.max(jnp.abs(
        dispatch.rolling_matmul(x, w, 128, 256, backend="pallas")
        - dispatch.rolling_matmul(x, w, 128, 256, backend="jnp"))))
    emit("kernels", "dispatch_rolling_maxerr", f"{derr:.2e}")


def fed_round(rounds):
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import SubmodelConfig, get_reduced_config
    from repro.data.synthetic import lm_batches
    from repro.models import build_model

    cfg = get_reduced_config("tinyllama_1_1b")
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.05,
                          axes=("d_ff", "heads", "kv_heads"))
    fed = api.fed_round(m, scfg)
    it = lm_batches(cfg.vocab, (2, 4, 2), 64)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    # timing microbench: step the jitted round directly so the n rounds
    # dispatch asynchronously and sync once (Trainer's per-round metrics
    # record would force a host round-trip into the measurement).
    step = jax.jit(fed.round)
    params, _ = step(params, batch, 0, jax.random.PRNGKey(1))  # compile
    t0 = time.time()
    n = 3
    for r in range(n):
        params, metrics = step(params, batch, r + 1, jax.random.PRNGKey(r))
    jax.block_until_ready(jax.tree_util.tree_leaves(params)[0])
    emit("fed_round", "window_round_ms",
         round((time.time() - t0) / n * 1e3, 1))
    emit("fed_round", "tokens_per_round", 2 * 4 * 2 * 64)


def fed_round_pallas(rounds):
    """Both dispatch arms of a full MaskFedAvg.round on one model: the
    Pallas-kernel arm must match the jnp-oracle arm (max|Δ| < 1e-5 fp32),
    plus per-round timings and the fused window projection vs the
    extract-then-matmul oracle."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import SubmodelConfig
    from repro.kernels import dispatch
    from repro.models.layers import mlp_apply, mlp_apply_rolling

    # Small two-layer MLP regression: ragged leaf shapes exercise the
    # flatten/pad path of the tree-level kernels.
    d_in, d_h, C, K = 24, 33, 4, 2
    kp = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(kp, (d_in, d_h)) * 0.3,
              "b1": jnp.zeros((d_h,)),
              "w2": jax.random.normal(jax.random.fold_in(kp, 1),
                                      (d_h,)) * 0.3}
    ab = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    axes = {"w1": ("d_model", "d_ff"), "b1": ("d_ff",), "w2": ("d_ff",)}

    def loss(w, b):
        h = jnp.tanh(b["x"] @ w["w1"] + w["b1"])
        r = h @ w["w2"] - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    rngb = np.random.default_rng(0)
    batch = {"x": jnp.asarray(rngb.standard_normal((K, C, 8, d_in)),
                              jnp.float32),
             "y": jnp.asarray(rngb.standard_normal((K, C, 8)), jnp.float32)}
    scfg = SubmodelConfig(scheme="bernoulli", capacity=0.5, local_steps=K,
                          clients_per_round=C, client_lr=0.05)

    outs, times = {}, {}
    for backend in ("jnp", "pallas"):
        fed = api.fed_round((loss, ab, axes), scfg, mode="mask",
                            capacities=np.full(C, 0.5),
                            kernel_backend=backend)
        # repeated-step microbench (same params every call, arms compared
        # bit-for-bit) — steps the round directly rather than chaining a
        # Trainer loop.
        step = jax.jit(fed.round)
        new, _ = step(params, batch, 0, jax.random.PRNGKey(7))  # compile
        jax.block_until_ready(jax.tree_util.tree_leaves(new)[0])
        t0 = time.time()
        n = 5
        for r in range(n):
            new, _ = step(params, batch, 0, jax.random.PRNGKey(7))
        jax.block_until_ready(jax.tree_util.tree_leaves(new)[0])
        outs[backend] = new
        times[backend] = (time.time() - t0) / n * 1e3
        emit("fed_round_pallas", f"{backend}_round_ms",
             round(times[backend], 2))

    maxdelta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(outs["pallas"]),
        jax.tree_util.tree_leaves(outs["jnp"])))
    emit("fed_round_pallas", "round_maxdelta", f"{maxdelta:.2e}")
    emit("fed_round_pallas", "round_match_1e-5", int(maxdelta < 1e-5))

    # Window projection: fused rolling matmul vs extract-then-matmul oracle.
    D, F, win, off = 128, 512, 256, 128
    p = {"w_gate": jax.random.normal(jax.random.fold_in(kp, 5),
                                     (D, F)) * 0.1,
         "w_up": jax.random.normal(jax.random.fold_in(kp, 2), (D, F)) * 0.1,
         "w_down": jax.random.normal(jax.random.fold_in(kp, 3),
                                     (F, D)) * 0.1}
    x = jax.random.normal(jax.random.fold_in(kp, 4), (64, D))
    sub = {k: jax.lax.dynamic_slice_in_dim(v, off, win,
                                           axis=1 if k != "w_down" else 0)
           for k, v in p.items()}
    oracle = mlp_apply(sub, x)
    for backend in ("jnp", "pallas"):
        y = mlp_apply_rolling(p, x, off, win, backend=backend)
        err = float(jnp.max(jnp.abs(y - oracle)))
        emit("fed_round_pallas", f"rolling_mlp_{backend}_maxerr",
             f"{err:.2e}")
    emit("fed_round_pallas", "note",
         "pallas arm runs in interpret mode off-TPU (emulation, not a "
         "speed win); auto resolves to "
         + dispatch.resolve_backend())


def fed_round_fused(rounds):
    """Fused multi-axis window client phase vs the extract-based round on
    one transformer (full default SubmodelConfig.axes: d_ff + GQA-coupled
    heads/kv_heads here): the two must be bitwise-equal on f32, and the
    fused client phase must carry each client's cut window, never a
    full-shaped per-client tree (checked in the compiled HLO at both
    capacities).

    Both arms cut each client's windows once a round and carry the cut
    tree (on this dense model nothing stays whole), so the two are the
    same program: the round times at capacities 0.5 and 0.75
    (``extract_over_fused_cap50``, ``extract_over_fused_speedup``) are a
    profile, near 1, and gate nothing.  A STAGGERED arm pins the same
    bitwise contract for per-client windows (one cut per client)."""
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from repro import api
    from repro.configs.base import SubmodelConfig, get_reduced_config
    from repro.data.synthetic import lm_batches
    from repro.models import build_model

    # head_dim=16 keeps the flattened head layout (H*hd) from colliding
    # with the d_ff window size in the HLO shape-string count below.
    # layer_unroll=True inlines the 2-layer scan in BOTH arms: the rolled
    # scan's per-layer carry copies and weight-stack layout round-trips
    # dominate the fused arm's cost, and inlining is what puts fused
    # ahead of extract above the capacity crossover.
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2,
                  head_dim=16)
    m = build_model(cfg, remat=False, layer_unroll=True)
    params = m.init(jax.random.PRNGKey(0))
    # full default axes tuple — the multi-axis fused arm is the whole point
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.05)
    feds = {"fused": api.fed_round(m, scfg, fused_forward="on"),
            "extract": api.fed_round(m, scfg, fused_forward="off")}
    emit("fed_round_fused", "windowed_axes",
         " ".join(sorted(k[0] for k in feds["fused"]._fused_keys)))
    it = lm_batches(cfg.vocab, (2, 4, 2), 64)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}

    steps = {name: jax.jit(fed.round) for name, fed in feds.items()}
    times, raw = _interleaved_median_ms(
        steps, (params, batch, 0, jax.random.PRNGKey(1)), n=7)
    outs = {name: out[0] for name, out in raw.items()}
    for name in feds:
        emit("fed_round_fused", f"{name}_round_ms", round(times[name], 1))

    maxdelta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(outs["fused"]),
        jax.tree_util.tree_leaves(outs["extract"])))
    emit("fed_round_fused", "round_maxdelta", f"{maxdelta:.2e}")
    emit("fed_round_fused", "extract_over_fused_cap50",
         round(times["extract"] / times["fused"], 3))

    # -- capacity 0.75: bitwise equality is gated jointly with the
    # capacity-0.5 arm above.
    scfg75 = replace(scfg, capacity=0.75)
    feds75 = {"fused": api.fed_round(m, scfg75, fused_forward="on"),
              "extract": api.fed_round(m, scfg75, fused_forward="off")}
    steps75 = {name: jax.jit(fed.round) for name, fed in feds75.items()}
    times75, raw75 = _interleaved_median_ms(
        steps75, (params, batch, 0, jax.random.PRNGKey(1)), n=7)
    for name in feds75:
        emit("fed_round_fused", f"{name}_round_ms_cap75",
             round(times75[name], 1))
    maxdelta75 = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(raw75["fused"][0]),
        jax.tree_util.tree_leaves(raw75["extract"][0])))
    emit("fed_round_fused", "round_maxdelta_cap75", f"{maxdelta75:.2e}")
    emit("fed_round_fused", "round_bitwise_equal",
         int(maxdelta == 0.0 and maxdelta75 == 0.0))
    emit("fed_round_fused", "extract_over_fused_speedup",
         round(times75["extract"] / times75["fused"], 3))

    # -- bf16 uplink-delta compression on the fused aggregation path: half
    # the client->server delta bytes, f32 accumulation, ONE rounding per
    # delta.  Must stay close to the exact round (bf16 delta roundoff),
    # and must not be slower than the exact fused round's aggregation.
    bfed = api.fed_round(m, scfg, fused_forward="on",
                         uplink_compression="bf16")
    bstep = jax.jit(bfed.round)
    btimes, braw = _interleaved_median_ms(
        {"bf16": bstep}, (params, batch, 0, jax.random.PRNGKey(1)), n=5)
    emit("fed_round_fused", "bf16_uplink_round_ms",
         round(btimes["bf16"], 1))
    bmax = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(braw["bf16"][0]),
        jax.tree_util.tree_leaves(outs["fused"])))
    emit("fed_round_fused", "bf16_uplink_maxdelta", f"{bmax:.2e}")
    emit("fed_round_fused", "bf16_uplink_close", int(bmax < 1e-2))
    emit("fed_round_fused", "bf16_uplink_bytes_saved_frac", 0.5)

    # Client-phase HLO: both arms carry per-client compact W_sub stacks
    # [C, L, D, win]; the fused arm must carry no full-shaped per-client
    # stack [C, L, D, d_ff].  Only the MLP shapes are counted — the
    # attention sub stack [C, L, D, hwin, hd] is indistinguishable from
    # the FULL wk/wv tensors whenever hwin == n_kv_heads (capacity 1/G),
    # so a string count over it cannot witness anything.
    from repro.analysis import hlo_check

    C, L, D = scfg.clients_per_round, cfg.n_layers, cfg.d_model

    def client_hlo(fed, fused):
        def f(p, b, rng):
            offsets = fed._client_offsets(p, 0, rng)
            phase = (fed._client_phase_fused if fused
                     else fed._client_phase)
            return phase(p, b, offsets)[1]
        return hlo_check.compiled_text(f, params, batch,
                                       jax.random.PRNGKey(1))

    carries_window = 1
    full_shape = hlo_check.stacked_shape("f32", C, L, D, cfg.d_ff)
    for tag, arm_feds in (("", feds), ("_cap75", feds75)):
        win = arm_feds["fused"].scheme.sizes[("d_ff", cfg.d_ff)]
        sub_shapes = [hlo_check.stacked_shape("f32", C, L, D, win)]
        hlo_extract = client_hlo(arm_feds["extract"], False)
        hlo_fused = client_hlo(arm_feds["fused"], True)
        emit("fed_round_fused", f"extract_client_wsub_stacks{tag}",
             hlo_check.count(hlo_extract, sub_shapes))
        emit("fed_round_fused", f"fused_client_wsub_stacks{tag}",
             hlo_check.count(hlo_fused, sub_shapes))
        carries_window &= int(hlo_check.count(hlo_fused, sub_shapes) > 0
                              and hlo_check.absent(hlo_fused, full_shape))
    emit("fed_round_fused", "fused_carries_window", carries_window)

    # -- staggered arm: per-client windows, one cut per client.  Same
    # bitwise contract as the shared-window arm (the CI gate checks both).
    sscfg = replace(scfg, stagger=True)
    sfeds = {"staggered_fused": api.fed_round(m, sscfg, fused_forward="on"),
             "staggered_extract": api.fed_round(m, sscfg,
                                                fused_forward="off")}
    assert not sfeds["staggered_fused"].shared_window
    ssteps = {name: jax.jit(fed.round) for name, fed in sfeds.items()}
    stimes, sraw = _interleaved_median_ms(
        ssteps, (params, batch, 0, jax.random.PRNGKey(1)), n=5)
    souts = {name: out[0] for name, out in sraw.items()}
    for name in sfeds:
        emit("fed_round_fused", f"{name}_round_ms",
             round(stimes[name], 1))

    smax = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(souts["staggered_fused"]),
        jax.tree_util.tree_leaves(souts["staggered_extract"])))
    emit("fed_round_fused", "staggered_round_maxdelta", f"{smax:.2e}")
    emit("fed_round_fused", "staggered_round_bitwise_equal",
         int(smax == 0.0))


def fed_round_async(rounds):
    """The async FedBuff server (repro.fleet) vs the synchronous barrier.

    Two arms:

    * anchor — with M = N and a zero-spread fleet the async round
      sequence must be bitwise-equal to the ``api.Trainer`` loop
      (``async_sync_equiv`` gates CI bench-smoke);
    * throughput — rounds per *virtual* second at straggler fractions
      {0, 0.25, 0.5} (10x-slow stragglers): the buffered server keeps
      aggregating off the fast clients while the sync barrier waits for
      the slowest participant every round, so async throughput must
      degrade strictly less (``async_degrades_less``).
    """
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.configs.base import SubmodelConfig

    d_in, d_h, C, K = 16, 32, 8, 2
    kp = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(kp, (d_in, d_h)) * 0.3,
              "b1": jnp.zeros((d_h,)),
              "w2": jax.random.normal(jax.random.fold_in(kp, 1),
                                      (d_h,)) * 0.3}
    ab = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    axes = {"w1": ("d_model", "d_ff"), "b1": ("d_ff",), "w2": ("d_ff",)}

    def loss(w, b):
        h = jnp.tanh(b["x"] @ w["w1"] + w["b1"])
        r = h @ w["w2"] - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=K,
                          clients_per_round=C, client_lr=0.05)
    fed = api.fed_round((loss, ab, axes), scfg)

    def stream():
        rng = np.random.default_rng(0)
        while True:
            yield {"x": rng.standard_normal((K, C, 4, d_in)).astype(
                       np.float32),
                   "y": rng.standard_normal((K, C, 4)).astype(np.float32)}

    # -- arm 1: the bitwise sync-equivalence anchor --------------------------
    n_anchor = 6
    tr = api.Trainer(fed, params, rng=jax.random.PRNGKey(5))
    p_sync, _ = tr.run(stream(), n_anchor)
    at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(5))
    p_async, _ = at.run(stream(), n_anchor)
    maxdelta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(p_sync),
        jax.tree_util.tree_leaves(p_async)))
    emit("fed_round_async", "anchor_maxdelta", f"{maxdelta:.2e}")
    emit("fed_round_async", "async_sync_equiv", int(maxdelta == 0.0))

    # -- arm 2: rounds per virtual second vs the barrier ---------------------
    n_r = max(rounds, 12)
    fleet_n, M = 16, 4
    rel = {}
    for frac in (0.0, 0.25, 0.5):
        lat = api.LatencyModel(straggler_frac=frac, straggler_mult=10.0,
                               seed=0)
        at = api.AsyncTrainer(fed, params, rng=jax.random.PRNGKey(1),
                              buffer_size=M,
                              fleet=api.FleetSimulator(fleet_n, lat))
        _, hist = at.run(stream(), n_r)
        async_rps = n_r / float(hist[-1]["virtual_time"])
        sync_secs = api.FleetSimulator(fleet_n, lat).simulate_sync(
            api.EpochPermutationSampler(fleet_n, seed=0), n_r, cohort=C)
        sync_rps = n_r / sync_secs
        tag = f"f{frac:g}"
        emit("fed_round_async", f"async_rounds_per_vsec_{tag}",
             round(async_rps, 4))
        emit("fed_round_async", f"sync_rounds_per_vsec_{tag}",
             round(sync_rps, 4))
        emit("fed_round_async", f"mean_staleness_{tag}",
             round(float(np.mean([h["staleness"] for h in hist])), 3))
        rel[frac] = (async_rps, sync_rps)

    # throughput retained relative to the straggler-free fleet: the async
    # server must lose strictly less of it than the barrier at every F > 0
    a0, s0 = rel[0.0]
    degrades_less = all(rel[f][0] / a0 > rel[f][1] / s0
                        for f in (0.25, 0.5))
    emit("fed_round_async", "async_degrades_less", int(degrades_less))


def fed_round_mesh(rounds):
    """The fed round under shard_map on a clients x model host mesh.

    Two arms:

    * correctness — the fused transformer round on the mesh must be
      bitwise-equal to the single-device round (``mesh_round_bitwise_equal``
      gates CI, together with the scale arm's gather check);
    * scale — 2048 simulated clients on a staggered-rolling MLP triple,
      vmap (single device) vs shard_map gather vs shard_map psum round
      times, inputs pre-placed with ``sharding.policy.round_input_shardings``.

    Run under forced host devices (main() forces 8 when this bench is
    selected; REPRO_HOST_DEVICES overrides the count).
    """
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    from repro import api
    from repro.configs.base import SubmodelConfig, get_reduced_config
    from repro.data.synthetic import lm_batches
    from repro.launch.mesh import host_mesh
    from repro.models import build_model
    from repro.sharding.policy import round_input_shardings

    n_dev = len(jax.devices())
    mesh = host_mesh(str(n_dev))
    emit("fed_round_mesh", "devices", n_dev)

    def time_round(fed, params, batch, n=3, **kw):
        step = jax.jit(fed.round)
        new, _ = step(params, batch, 0, jax.random.PRNGKey(1), **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(new)[0])
        t0 = time.time()
        for _ in range(n):
            new, _ = step(params, batch, 0, jax.random.PRNGKey(1), **kw)
        jax.block_until_ready(jax.tree_util.tree_leaves(new)[0])
        return new, (time.time() - t0) / n * 1e3

    def maxdelta(t1, t2):
        return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)))

    # -- arm 1: fused transformer round, mesh == single device bitwise -------
    cfg = replace(get_reduced_config("tinyllama_1_1b"), n_layers=2,
                  head_dim=16)
    m = build_model(cfg, remat=False)
    params = m.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=8, client_lr=0.05, stagger=True)
    it = lm_batches(cfg.vocab, (2, 8, 2), 64)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    single = api.fed_round(m, scfg, fused_forward="on")
    sharded = api.fed_round(m, scfg, fused_forward="on", mesh=mesh)
    out_s, _ = time_round(single, params, batch, n=1)
    out_m, _ = time_round(sharded, params, batch, n=1)
    fused_delta = maxdelta(out_s, out_m)
    emit("fed_round_mesh", "fused_round_maxdelta", f"{fused_delta:.2e}")

    # -- arm 2: 2048 simulated clients, vmap vs gather vs psum ---------------
    C = 2048 if C_OVERRIDE is None else C_OVERRIDE
    d_in, d_h = 32, 1024
    kp = jax.random.PRNGKey(3)
    tparams = {"w1": jax.random.normal(kp, (d_in, d_h)) * 0.1,
               "b1": jnp.zeros((d_h,)),
               "w2": jax.random.normal(jax.random.fold_in(kp, 1),
                                       (d_h,)) * 0.1}
    ab = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tparams)
    axes = {"w1": ("d_model", "d_ff"), "b1": ("d_ff",), "w2": ("d_ff",)}

    def loss(w, b):
        h = jnp.tanh(b["x"] @ w["w1"] + w["b1"])
        r = h @ w["w2"] - b["y"]
        return 0.5 * jnp.mean(r * r), {}

    rngb = np.random.default_rng(0)
    tbatch = {"x": jnp.asarray(rngb.standard_normal((1, C, 4, d_in)),
                               jnp.float32),
              "y": jnp.asarray(rngb.standard_normal((1, C, 4)), jnp.float32)}
    tscfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=1,
                           clients_per_round=C, client_lr=0.05,
                           stagger=True)
    model = (loss, ab, axes)
    emit("fed_round_mesh", "clients", C)

    vmap_fed = api.fed_round(model, tscfg)
    out_v, t_v = time_round(vmap_fed, tparams, tbatch)
    emit("fed_round_mesh", "vmap_round_ms", round(t_v, 1))

    params_sh, batch_sh = round_input_shardings(mesh, "data", ab, tbatch)
    mparams = jax.device_put(tparams, params_sh)
    mbatch = jax.device_put(tbatch, batch_sh)
    gather_fed = api.fed_round(model, tscfg, mesh=mesh)
    out_g, t_g = time_round(gather_fed, mparams, mbatch)
    emit("fed_round_mesh", "mesh_round_ms", round(t_g, 1))
    emit("fed_round_mesh", "mesh_over_vmap_speedup",
         round(t_v / t_g, 3))
    scale_delta = maxdelta(out_v, out_g)
    emit("fed_round_mesh", "scale_round_maxdelta", f"{scale_delta:.2e}")

    psum_fed = api.fed_round(model, tscfg, mesh=mesh, mesh_agg="psum")
    out_p, t_p = time_round(psum_fed, mparams, mbatch)
    emit("fed_round_mesh", "psum_round_ms", round(t_p, 1))
    emit("fed_round_mesh", "psum_round_maxdelta",
         f"{maxdelta(out_v, out_p):.2e}")

    emit("fed_round_mesh", "mesh_round_bitwise_equal",
         int(fused_delta == 0.0 and scale_delta == 0.0))


C_OVERRIDE = None  # test hook: shrink the scale arm's client count


BENCHES = {
    "fig1_heterogeneity": fig1_heterogeneity,
    "fig2_low_hetero": fig2_low_hetero,
    "fig3_capacity": fig3_capacity,
    "tab1_generalization": tab1_generalization,
    "tab4_heterofl": tab4_heterofl,
    "thm1_residual": thm1_residual,
    "thm5_stability": thm5_stability,
    "kernels": kernels,
    "fed_round": fed_round,
    "fed_round_pallas": fed_round_pallas,
    "fed_round_fused": fed_round_fused,
    "fed_round_async": fed_round_async,
    "fed_round_mesh": fed_round_mesh,
}


# ---------------------------------------------------------------------------
# Declared result schema — what each bench is allowed to write into
# experiments/bench_results.json.  ``tests/test_bench_schema.py`` validates
# the artifact against this, so the per-commit perf trajectory CI uploads
# can't silently drift shape.  Metric specs: a type (or tuple of types) the
# value must satisfy after JSON round-trip; "gate" metrics must be 0/1.
# ---------------------------------------------------------------------------

_NUM = (int, float)

BENCH_SCHEMA = {
    "fig1_heterogeneity": {
        "metrics": {"rolling_final_test_loss": _NUM,
                    "rolling_final_test_acc": _NUM,
                    "random_final_test_loss": _NUM,
                    "random_final_test_acc": _NUM},
    },
    "fig2_low_hetero": {
        "metrics": {"rolling_final_test_loss": _NUM,
                    "rolling_final_test_acc": _NUM,
                    "random_final_test_loss": _NUM,
                    "random_final_test_acc": _NUM},
    },
    "fig3_capacity": {
        "metrics": {"beta1_final_test_acc": _NUM,
                    "beta1_16_final_test_acc": _NUM},
    },
    "tab1_generalization": {
        "metrics": {"random_loss_gap": _NUM, "random_acc_gap": _NUM,
                    "full_loss_gap": _NUM, "full_acc_gap": _NUM},
    },
    "tab4_heterofl": {
        "metrics": {"rolling_final_test_acc": _NUM,
                    "rolling_final_test_loss": _NUM,
                    "static_final_test_acc": _NUM,
                    "static_final_test_loss": _NUM},
    },
    "thm1_residual": {
        "metrics": {"monotone_in_masking": int},
        "gates": ["monotone_in_masking"],
    },
    "thm5_stability": {"metrics": {}},
    "kernels": {"metrics": {}},
    "fed_round": {"metrics": {"window_round_ms": _NUM,
                              "tokens_per_round": int}},
    "fed_round_pallas": {
        "metrics": {"jnp_round_ms": _NUM, "pallas_round_ms": _NUM,
                    "rolling_mlp_jnp_maxerr": str,
                    "rolling_mlp_pallas_maxerr": str,
                    "round_match_1e-5": int, "round_maxdelta": str},
        "gates": ["round_match_1e-5"],
    },
    "fed_round_fused": {
        "metrics": {"fused_round_ms": _NUM, "extract_round_ms": _NUM,
                    "round_maxdelta": str, "round_bitwise_equal": int,
                    "extract_over_fused_cap50": _NUM,
                    "fused_round_ms_cap75": _NUM,
                    "extract_round_ms_cap75": _NUM,
                    "round_maxdelta_cap75": str,
                    "extract_over_fused_speedup": _NUM,
                    "bf16_uplink_round_ms": _NUM,
                    "bf16_uplink_maxdelta": str,
                    "bf16_uplink_close": int,
                    "bf16_uplink_bytes_saved_frac": _NUM,
                    "extract_client_wsub_stacks": int,
                    "fused_client_wsub_stacks": int,
                    "extract_client_wsub_stacks_cap75": int,
                    "fused_client_wsub_stacks_cap75": int,
                    "fused_carries_window": int,
                    "staggered_fused_round_ms": _NUM,
                    "staggered_extract_round_ms": _NUM,
                    "staggered_round_maxdelta": str,
                    "staggered_round_bitwise_equal": int,
                    "windowed_axes": str},
        "gates": ["round_bitwise_equal", "fused_carries_window",
                  "staggered_round_bitwise_equal", "bf16_uplink_close"],
    },
    "fed_round_async": {
        "metrics": {"async_sync_equiv": int, "async_degrades_less": int,
                    "anchor_maxdelta": str,
                    **{f"{arm}_f{f}": _NUM
                       for arm in ("async_rounds_per_vsec",
                                   "sync_rounds_per_vsec",
                                   "mean_staleness")
                       for f in ("0", "0.25", "0.5")}},
        "gates": ["async_sync_equiv", "async_degrades_less"],
    },
    "fed_round_mesh": {
        "metrics": {"mesh_round_bitwise_equal": int, "clients": int,
                    "devices": int, "fused_round_maxdelta": str,
                    "mesh_over_vmap_speedup": _NUM, "mesh_round_ms": _NUM,
                    "psum_round_maxdelta": str, "psum_round_ms": _NUM,
                    "scale_round_maxdelta": str, "vmap_round_ms": _NUM},
        "gates": ["mesh_round_bitwise_equal"],
    },
    "curves": {"metrics": {}},
    "paper_protocol": {"metrics": {}},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--rounds", type=int, default=12,
                    help="base round budget (--full for paper-scale curves)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rounds = args.rounds * (5 if args.full else 1)

    names = args.only.split(",") if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choose from "
                 f"{sorted(BENCHES)}")
    if "fed_round_mesh" in names:
        # the mesh bench needs >1 device on CPU; the forcing flag must
        # reach XLA before any bench (lazily) imports jax
        import sys
        if "jax" not in sys.modules:
            n_dev = int(os.environ.get("REPRO_HOST_DEVICES", "8"))
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={n_dev}").strip()
    print("name,metric,value")
    for n in names:
        t0 = time.time()
        BENCHES[n](rounds)
        emit(n, "bench_seconds", round(time.time() - t0, 1))
    os.makedirs("experiments", exist_ok=True)
    # merge-on-write: partial runs (--only) extend earlier sections instead
    # of clobbering them, so CI can gate on several invocations' metrics
    out = {}
    if os.path.exists("experiments/bench_results.json"):
        try:
            with open("experiments/bench_results.json") as f:
                out = json.load(f)
        except (json.JSONDecodeError, OSError):
            out = {}
    for name, metrics in RESULTS.items():
        out.setdefault(name, {}).update(metrics)
    with open("experiments/bench_results.json", "w") as f:
        json.dump(out, f, indent=1, default=str)


if __name__ == "__main__":
    main()
