"""Training launcher — distributed sub-model training (the paper's
algorithms) on real devices, through the ``repro.api`` facade.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama_1_1b \
        --reduced --rounds 50 --scheme rolling --capacity 0.5 \
        [--clients 4 --local-steps 2 --mb 2 --seq 128] \
        [--client-opt momentum --server-opt adam]

On a CPU host use --reduced (smoke-scale config); on a TPU the same entry
point drives the full config (``chip_smoke.py`` runs it at TinyLlama-1.1B
published widths on one v5e chip).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time


def _force_host_devices():
    """--devices N must reach XLA before the backend initializes, which
    happens at (transitive) ``import jax`` below — so pre-scan sys.argv
    here instead of waiting for argparse.
    """
    if "jax" in sys.modules:        # backend may already be up; too late
        return
    argv = sys.argv
    for i, a in enumerate(argv):
        n = None
        if a == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif a.startswith("--devices="):
            n = a.split("=", 1)[1]
        if n is not None:
            flag = f"--xla_force_host_platform_device_count={int(n)}"
            os.environ["XLA_FLAGS"] = \
                (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
            return


_force_host_devices()

import jax

from repro import api
from repro.checkpoint.checkpoint import save as ckpt_save
from repro.configs.base import SubmodelConfig, get_config, get_reduced_config
from repro.data.synthetic import lm_batches
from repro.kernels import dispatch
from repro.launch.mesh import host_mesh
from repro.models import build_model

#: Root of the checkout (``src/repro/launch/train.py`` -> ``.``).
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache across runs; returns its
    directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
    fixed path, so the next run in this checkout finds what this one
    compiled.  Call it from an entry point's ``main`` before the first
    compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, print the JSON
    summary, and return the :class:`api.Trainer` (its ``losses``, ``fed``
    and ``params`` are the run's result)."""
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers: a depth cut that leaves "
                         "every width as published, to fit one chip "
                         "(default: all layers of the config)")
    ap.add_argument("--scheme", default="rolling",
                    choices=["rolling", "random", "static", "full",
                             "bernoulli", "importance"])
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "window", "mask"],
                    help="round form: auto derives it from the scheme "
                         "(bernoulli -> mask, else window)")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["auto", "pallas", "jnp"],
                    help="fed-round kernel arm: fused Pallas kernels, jnp "
                         "oracles, or auto (Pallas iff on TPU). Default: "
                         "the REPRO_KERNEL_BACKEND env var, else auto")
    ap.add_argument("--fused-forward", default="auto",
                    choices=["auto", "on", "off"],
                    help="window mode: run the fused multi-axis window "
                         "client phase (each client's window cut once a "
                         "round and carried; a share's experts window "
                         "whole, narrowed by the window-aware forward) "
                         "when every windowed axis has a fused arm (d_ff, "
                         "GQA-coupled heads/kv_heads, MLA standalone "
                         "heads, experts, moe_d_ff, ssm_heads); 'on' "
                         "forces it, 'off' keeps the extract-based client "
                         "phase (see the README fused-coverage matrix)")
    ap.add_argument("--kernel-block", default=None, metavar="BMxBNxBK",
                    help="override the rolling-matmul block autotuner with "
                         "a fixed (bm, bn, bk) triple, e.g. 128x128x64 "
                         "(also accepts comma-separated), read by dx with "
                         "bn over K and bk over the window; default: "
                         "deterministic autotune per role (forward, dx) "
                         "from the operand-dim divisors and the VMEM "
                         "budget, cached per (shape, dtype, backend)")
    ap.add_argument("--layer-unroll", default=None, metavar="N|full",
                    help="unroll the model's layer scan (N layers per "
                         "iteration, or 'full' to inline it).  Inlining "
                         "removes the rolled scan's per-layer carry "
                         "copies and weight-layout round-trips — the CPU "
                         "lever behind the fused round's bench win — at "
                         "the cost of larger HLO and, for MoE archs, "
                         "~1-ulp output moves vs the rolled program. "
                         "Default: rolled")
    ap.add_argument("--uplink-compression", default=None,
                    choices=["bf16"],
                    help="window mode: round each client delta to bf16 on "
                         "the simulated uplink (half the client->server "
                         "bytes; f32 accumulation, one final rounding). "
                         "Default: exact f32 uplink, bitwise fused==extract")
    ap.add_argument("--client-opt", default="sgd",
                    choices=sorted(api.CLIENT_OPTS),
                    help="local-step optimizer (paper: sgd)")
    ap.add_argument("--server-opt", default="none",
                    choices=["none"] + sorted(api.SERVER_OPTS),
                    help="stateful server optimizer on the mean delta "
                         "(paper: none = plain averaging)")
    # The env var is only a default here (baseline-repro knob); the round
    # itself reads SubmodelConfig.shared_window, resolved at construction.
    ap.add_argument("--no-shared-window", action="store_true",
                    default=bool(os.environ.get("REPRO_NO_SHARED_WINDOW")),
                    help="force the per-client scatter aggregation even "
                         "when every client trains the same window "
                         "(default: the REPRO_NO_SHARED_WINDOW env var)")
    ap.add_argument("--axes", nargs="+", default=None,
                    help="semantic axes to window (default: the "
                         "SubmodelConfig default tuple — fully fused "
                         "across the model zoo, incl. ssm_heads and MLA "
                         "standalone heads)")
    ap.add_argument("--stagger", action="store_true",
                    help="rotate the rolling/importance window per client "
                         "(full axis coverage every round; fused via the "
                         "batched-offset rolling matmul)")
    ap.add_argument("--mesh", default=None, metavar="DATA[xMODEL]",
                    help="run the round under shard_map on a "
                         "(data, model) mesh, clients split over the data "
                         "axis — e.g. '4' or '4x2'; --clients must be "
                         "divisible by DATA")
    ap.add_argument("--mesh-agg", default="gather",
                    choices=["gather", "psum"],
                    help="cross-shard aggregation: gather is bitwise-"
                         "equal to the single-device round; psum trades "
                         "that for O(model) comm at scale")
    ap.add_argument("--devices", type=int, default=None,
                    help="force this many XLA host-platform devices "
                         "(CPU mesh testing; must be the first jax init "
                         "in the process)")
    # Async fleet (repro.fleet): 0 = the synchronous barrier Trainer;
    # M > 0 aggregates once M of the in-flight --clients report
    # (FedBuff-style, staleness-discounted).  --async-buffer equal to
    # --clients with a zero-spread fleet replays the sync loop bitwise.
    ap.add_argument("--async-buffer", type=int, default=0, metavar="M",
                    help="aggregate once M in-flight clients report "
                         "(0 = synchronous barrier rounds)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="virtual fleet size (0 = --clients)")
    ap.add_argument("--straggler-frac", type=float, default=0.0,
                    help="fraction of the fleet running "
                         "--straggler-mult x slower")
    ap.add_argument("--straggler-mult", type=float, default=10.0)
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-dispatch client fault probability")
    ap.add_argument("--timeout", type=float, default=None,
                    help="virtual seconds before a slot abandons its "
                         "client and redispatches")
    ap.add_argument("--staleness-policy", default="inverse_sqrt",
                    choices=sorted(api.STALENESS_POLICIES),
                    help="weight w(tau) on a delta computed tau rounds "
                         "ago (w(0)=1)")
    ap.add_argument("--server-lr-schedule", default="constant",
                    choices=sorted(api.SERVER_LR_SCHEDULES),
                    help="server stepsize multiplier per round "
                         "(2201.11066's server-lr arm)")
    ap.add_argument("--capacity", type=float, default=0.5)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    if args.kernel_block:
        blocks = args.kernel_block.replace("x", ",").split(",")
        if len(blocks) != 3:
            raise SystemExit("--kernel-block expects BMxBNxBK, e.g. "
                             "128x128x64")
        dispatch.set_block_override(tuple(int(b) for b in blocks))

    cfg = get_reduced_config(args.arch) if args.reduced \
        else get_config(args.arch)
    if args.layers is not None:
        if not 1 <= args.layers <= cfg.n_layers:
            raise SystemExit(f"--layers must be in [1, {cfg.n_layers}] "
                             f"for {cfg.name}; got {args.layers}")
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    unroll_kw = {}
    if args.layer_unroll:
        unroll_kw["layer_unroll"] = (True if args.layer_unroll == "full"
                                     else int(args.layer_unroll))
    model = build_model(cfg, moe_path="dense" if args.reduced else "dropping",
                        remat=not args.reduced, **unroll_kw)
    params = model.init(jax.random.PRNGKey(args.seed))
    axes_kw = {"axes": tuple(args.axes)} if args.axes else {}
    scfg = SubmodelConfig(scheme=args.scheme, capacity=args.capacity,
                          local_steps=args.local_steps,
                          clients_per_round=args.clients,
                          client_lr=args.lr, seed=args.seed,
                          stagger=args.stagger,
                          shared_window=False if args.no_shared_window
                          else None, **axes_kw)
    mesh = host_mesh(args.mesh) if args.mesh else None
    fed = api.fed_round(model, scfg, mode=args.mode,
                        client_opt=args.client_opt,
                        server_opt=args.server_opt,
                        kernel_backend=args.kernel_backend,
                        mesh=mesh, mesh_agg=args.mesh_agg,
                        fused_forward=args.fused_forward,
                        uplink_compression=args.uplink_compression)

    vision = (cfg.vision_patches, cfg.vision_d) if cfg.vision_stub else None
    it = lm_batches(cfg.vocab, (args.local_steps, args.clients, args.mb),
                    args.seq, seed=args.seed, codebooks=cfg.n_codebooks,
                    vision=vision)
    t0 = time.time()
    if args.async_buffer:
        if mesh is not None:
            raise SystemExit("--async-buffer owns the client axis; "
                             "drop --mesh")
        fleet = api.FleetSimulator(
            args.fleet or args.clients,
            api.LatencyModel(straggler_frac=args.straggler_frac,
                             straggler_mult=args.straggler_mult,
                             dropout=args.dropout, timeout=args.timeout,
                             seed=args.seed))
        trainer = api.AsyncTrainer(
            fed, params, rng=jax.random.PRNGKey(args.seed + 1),
            buffer_size=args.async_buffer, fleet=fleet,
            staleness=args.staleness_policy,
            server_lr_schedule=args.server_lr_schedule,
            log_every=args.log_every,
            log_fn=lambda s: print(
                f"{s} ({(time.time() - t0) / (trainer.round_idx or 1):.2f}"
                "s/round)", flush=True))
    else:
        trainer = api.Trainer(
            fed, params, rng=jax.random.PRNGKey(args.seed + 1),
            log_every=args.log_every,
            log_fn=lambda s: print(
                f"{s} ({(time.time() - t0) / (trainer.round_idx or 1):.2f}"
                "s/round)", flush=True))
    # The trainer now holds the only reference to the initial weights, so
    # they are freed after the first round instead of pinning a second
    # copy of the model in device memory for the whole run.
    del params
    params, history = trainer.run(it, args.rounds)
    losses = trainer.losses  # history keeps device arrays; sync once here
    if args.ckpt:
        ckpt_save(args.ckpt, params,
                  {"arch": args.arch, "rounds": args.rounds,
                   "scheme": args.scheme, "history": losses})
        print("checkpoint ->", args.ckpt)
    out = {"first_loss": losses[0], "last_loss": losses[-1]}
    if args.async_buffer:
        vt = history[-1]["virtual_time"]
        out.update(virtual_time=vt,
                   rounds_per_vsec=round(args.rounds / vt, 4) if vt else None,
                   mean_staleness=round(
                       sum(h["staleness"] for h in history) / len(history),
                       3))
    else:
        out["compiles"] = trainer.compiles
    out["block_choices"] = dispatch.block_choices()
    print(json.dumps(out))
    return trainer


if __name__ == "__main__":
    main()
