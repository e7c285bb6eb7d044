"""Smoke run of the federated sub-model round on TPU chips.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the mesh round only

Phase A (correctness, small): one round of the reduced TinyLlama config
(4 clients, K=2, rolling windows at capacity 0.5, align 128) on the Pallas
kernels and on the jnp oracles; the two must agree within the tolerances
below.

Phase B (main path, published widths): ``repro.launch.train.main`` trains
TinyLlama-1.1B (d_model 2048, d_ff 5632, 32/4 heads, vocab 32000, f32) for
3 rounds of 1 client.  Every loss must be finite, the lowered round must
hold compiled kernels (``tpu_custom_call``), and no windowed matmul may
have fallen back to its jnp oracle.

``--chips 4`` runs only the cross-chip phase: the same round with 4
clients under ``--mesh 4``, once with ``--mesh-agg gather`` and once with
``psum``; the two must agree within the tolerance below.

Runs in one process and starts none.  Exits non-zero, without the final
line, when JAX finds no TPU or any phase fails.  The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "tinyllama_1_1b"

# Depth cut for the published-width runs.  One fused round of 1 client at
# all 22 layers does not load on a v5e: the program asks for 9.67G of
# scratch while the parameters in and out (4.10 GiB each) leave 7.55G of
# 15.75G free (RESOURCE_EXHAUSTED).  12 layers run, and the compiler puts the 4-chip
# gather round at 13.72 GiB per chip.  Every width stays as published.
LAYERS = 12
# Local steps, sequences per client step, and tokens per sequence.
STEPS, MB, SEQ = 2, 1, 512

# Phase A tolerances.  The pallas arm multiplies f32 operands in Mosaic's
# dot and the jnp arm in XLA's default-precision f32 dot, which rounds its
# operands to bf16 on the TPU, so the arms differ by bf16-level rounding of
# every matmul, carried through 2 layers, backward and 2 local steps.
# - mean round loss: relative 1e-2 (bf16 has 8 mantissa bits, 4e-3);
# - params: the largest disagreement at most 10% of the largest update the
#   round made.  A wrong window or offset disagrees by the whole update.
LOSS_RTOL = 1e-2
PARAM_UPDATE_FRAC = 0.1

# gather vs psum: the same f32 client deltas, summed in another order
# (psum reduces across chips, gather replays the single-device scan), so
# they differ by f32 reassociation of 4 terms: well below 1e-6 on weights
# of magnitude <= 1.
MESH_ATOL = 1e-6


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    diffs = jax.tree_util.tree_map(
        lambda x, y: jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))), a, b)
    return max(float(d) for d in jax.tree_util.tree_leaves(diffs))


def custom_calls(fed, params, batch) -> int:
    """Compiled Pallas kernels in the lowered round (0 = none ran)."""
    import jax
    lowered = jax.jit(fed.round).lower(params, batch, 0,
                                       jax.random.PRNGKey(0))
    return lowered.as_text().count("tpu_custom_call")


def phase_a():
    import jax
    from repro import api
    from repro.configs.base import SubmodelConfig, get_reduced_config
    from repro.data.synthetic import lm_batches
    from repro.kernels import dispatch
    from repro.models import build_model

    cfg = get_reduced_config(ARCH)
    model = build_model(cfg, moe_path="dense", remat=False)
    params0 = model.init(jax.random.PRNGKey(0))
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, local_steps=2,
                          clients_per_round=4, client_lr=0.05, align=128)
    batch = next(lm_batches(cfg.vocab, (2, 4, 2), 128, seed=0))
    out = {}
    for backend in ("pallas", "jnp"):
        dispatch.ORACLE_FALLBACKS.clear()
        fed = api.fed_round(model, scfg, kernel_backend=backend)
        trainer = api.Trainer(fed, params0, rng=1)
        loss = float(trainer.step(batch)["loss"])
        print(f"phase A: {backend:6s} loss {loss!r}", flush=True)
        out[backend] = (loss, trainer.params)
        if backend == "pallas":
            if dispatch.ORACLE_FALLBACKS:
                fail(f"phase A oracle fallbacks: "
                     f"{dict(dispatch.ORACLE_FALLBACKS)}")
            n = custom_calls(fed, params0, batch)
            print(f"phase A: pallas tpu_custom_call {n}", flush=True)
            if n == 0:
                fail("phase A pallas round holds no tpu_custom_call")
    (lp, pp), (lj, pj) = out["pallas"], out["jnp"]
    dparams = max_abs_diff(pp, pj)
    update = max_abs_diff(pj, params0)
    print(f"phase A: |dloss| {abs(lp - lj)!r} max|dparams| {dparams!r} "
          f"max|update| {update!r}", flush=True)
    if not (math.isfinite(lp) and math.isfinite(lj)):
        fail("phase A loss is not finite")
    if abs(lp - lj) > LOSS_RTOL * abs(lj):
        fail(f"phase A loss differs by more than {LOSS_RTOL} relative")
    if not dparams <= PARAM_UPDATE_FRAC * update:
        fail(f"phase A params differ by more than {PARAM_UPDATE_FRAC} of "
             "the round's update")


def train_run(phase, clients, rounds, extra=()):
    """One ``train.main`` run of ``LAYERS`` layers at published widths;
    checks losses, kernels and fallbacks."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch
    from repro.launch import train

    dispatch.ORACLE_FALLBACKS.clear()
    t0 = time.perf_counter()
    trainer = train.main([
        "--arch", ARCH, "--layers", str(LAYERS), "--clients", str(clients),
        "--mb", str(MB), "--seq", str(SEQ), "--local-steps", str(STEPS),
        "--rounds", str(rounds), "--log-every", "1",
        "--kernel-backend", "auto", *extra])
    print(f"{phase}: train.main {time.perf_counter() - t0:.1f}s "
          "(compile included)", flush=True)
    losses = trainer.losses
    print(f"{phase}: losses {losses!r}", flush=True)
    if not losses or not all(math.isfinite(x) for x in losses):
        fail(f"{phase}: a loss is not finite")
    fallbacks = dict(dispatch.ORACLE_FALLBACKS)
    print(f"{phase}: oracle fallbacks {sum(fallbacks.values())} "
          f"{fallbacks}", flush=True)
    if fallbacks:
        fail(f"{phase}: windowed matmuls fell back to the jnp oracle")
    tokens = jax.ShapeDtypeStruct((STEPS, clients, MB, SEQ), jnp.int32)
    n = custom_calls(trainer.fed, trainer.params, {"tokens": tokens})
    print(f"{phase}: tpu_custom_call {n}", flush=True)
    if n == 0:
        fail(f"{phase}: the lowered round holds no tpu_custom_call")
    return trainer


def print_depth_cut(phase):
    from repro.configs.base import get_config
    full = get_config(ARCH).n_layers
    print(f"{phase}: depth cut {full} -> {LAYERS} layers, published "
          "widths (the full depth does not fit one v5e's HBM; see LAYERS)",
          flush=True)


def phase_b(device):
    print_depth_cut("phase B")
    train_run("phase B", clients=1, rounds=3)
    stats = device.memory_stats() or {}
    print(f"phase B: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)


def phase_mesh():
    print_depth_cut("mesh")
    params = {}
    for agg in ("gather", "psum"):
        trainer = train_run(f"mesh {agg}", clients=4, rounds=1,
                            extra=("--mesh", "4", "--mesh-agg", agg))
        params[agg] = trainer.params
        del trainer
    d = max_abs_diff(params["gather"], params["psum"])
    print(f"mesh: gather vs psum max|dparams| {d!r}", flush=True)
    if not d <= MESH_ATOL:
        fail(f"mesh: gather and psum differ by more than {MESH_ATOL}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"JAX found no TPU (platform {d0.platform!r}); this smoke run "
             "needs the chip")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")
    print(f"device: {d0.device_kind} x{len(devices)}, jax {jax.__version__}",
          flush=True)

    from repro.kernels import dispatch
    from repro.launch import train
    if dispatch.resolve_backend("auto") != "pallas":
        fail("kernel backend 'auto' does not resolve to pallas on the TPU")

    cache_dir = train.enable_compile_cache()
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event])
        if event.startswith("/jax/compilation_cache/") else None)
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)",
          flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh()
    else:
        phase_a()
        phase_b(d0)
    hits = cache_events["/jax/compilation_cache/cache_hits"]
    misses = cache_events["/jax/compilation_cache/cache_misses"]
    print(f"compile cache: {hits} hits, {misses} misses; total "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
