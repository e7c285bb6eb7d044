"""One run of one cell: set-up, a measured window, the correctness check.

    python -m bench --workload ds7b-silo --seed 7 --seconds 30 --trace 0

Set-up (``setup_s``, from process start): the compile cache, the model
through ``repro.models.build_model`` and the round through
``api.fed_round``, the weights from the seed on the device, one
``api.Trainer``, and its first three rounds, fed by the traffic mix's
generator (``bench/traffic/<generator>.py``); these compile the round and
are the rounds the correctness check compares.  The window then drives the
same trainer for ``--seconds`` in a closed loop: it makes round r's batch
while round r-1 runs, waits on round r-1's loss, then dispatches round r,
so every round's completion time is known and no round is queued ahead.
The window ends with ``block_until_ready`` on the parameters.  After the
window the device's peak memory is read, the program's state is freed,
and the plain reference recomputes the first three rounds
(``bench.check``).

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the window under the profiler, reduces its trace with ``bench.scopes``
(device time by phase, ``model.*`` scope and Pallas kernel, the program's
host spans) and reports the per-layer metrics (``bench/metrics/<name>.py``,
each reading the :class:`Context`).  The last line of stdout is the result;
the numbers compared, with their limits, are the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

from bench import check, spec, weights


@dataclass
class Context:
    """What the per-layer readers see."""

    cell: Any
    peaks: dict
    rounds: int = 0
    window_s: float = 0.0
    feed_s: float = 0.0
    fallbacks: int = 0
    compiles: int = 0             # compiled or loaded inside the window
    trace: Optional[dict] = None  # bench.scopes.reduce of the window


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def p90(xs):
    if len(xs) < 2:
        return float(xs[0]) if xs else float("nan")
    return statistics.quantiles(xs, n=10, method="inclusive")[8]


def _compile_counter():
    box = {"n": 0}

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return box


def _memory_peak(devices) -> int:
    """Peak device memory of the fullest chip: the allocator's peak plus
    the peak the runtime reserved for programs' scratch, which the
    allocator's own count leaves out (an upper bound: the two peaks need
    not coincide)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


class Harness:
    """The program under test for one cell: model, round, weights from a
    seed, and the trainer and feed of one run."""

    def __init__(self, cell):
        from repro import api
        from repro.models import build_model

        self.cell = cell
        self.devices = jax.devices()[:cell.chips]
        self.cfg = cell.model_config()
        model = build_model(self.cfg, remat=True)
        self.mesh, replicated = None, None
        if cell.mix.get("mesh_agg"):
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch.mesh import host_mesh
            self.mesh = host_mesh(str(cell.chips))
            replicated = NamedSharding(self.mesh, PartitionSpec())
        self.fed = api.fed_round(model, cell.submodel_config(),
                                 mesh=self.mesh,
                                 mesh_agg=cell.mix.get("mesh_agg") or "gather")
        self.abstract = model.abstract_params()
        self.init = weights.make_init(self.abstract, self.cfg.n_layers,
                                      replicated)
        self.norms = weights.make_change_norms(self.init)
        self._ref_init, self._ref_norms, self._refs = None, None, {}

    def start(self, seed: int):
        """``(trainer, feed, seed32)`` of a run from ``seed``."""
        from repro import api
        s32 = weights.seed32(seed)
        trainer = api.Trainer(self.fed, self.init(s32),
                              rng=jax.random.PRNGKey(s32))
        feed = spec.feed_class(self.cell.mix["generator"])(
            self.cfg.vocab, self.cell.mix, seed)
        return trainer, feed, s32

    def checked_rounds(self, trainer, feed, s32):
        """The first rounds, through the window's own trainer, call and
        feed: ``(readings, token batches)``."""
        batches, losses, step1 = [], [], None
        for r in range(check.CHECKED_ROUNDS):
            batch = feed.next()
            rec = trainer.step(batch)
            batches.append(batch["tokens"])
            losses.append(np.asarray(rec["client_loss"], np.float64))
            if r == 0:
                step1 = {k: float(v) for k, v in jax.device_get(
                    self.norms(trainer.params, s32)).items()}
        step3 = {k: float(v) for k, v in jax.device_get(
            self.norms(trainer.params, s32)).items()}
        return check.Readings(np.stack(losses), step1, step3), batches

    def reference(self, s32, batches, dtype=None, fault=None,
                  precision="highest"):
        """Readings of the plain reference, its clients spread over the
        cell's chips (run it once the program's state is freed); ``dtype``
        computes it in another type (the control), ``precision`` at another
        matmul precision, ``fault`` plants a fault
        (``check.reference_readings``)."""
        import jax.numpy as jnp
        Reference = spec.reference_module(self.cell.config).Reference
        if self._ref_init is None:
            self._ref_init = weights.make_init(self.abstract,
                                               self.cfg.n_layers)
            self._ref_norms = weights.make_change_norms(self._ref_init)
        key = (dtype or jnp.float32, precision)
        if key not in self._refs:
            self._refs[key] = Reference(self.cell.config, self.cell.mix,
                                        key[0], self.devices, precision)
        return check.reference_readings(self._refs[key], self._ref_init,
                                        self._ref_norms, s32, batches, fault)


def _profile(tracedir):
    if tracedir is None:
        return contextlib.nullcontext()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(tracedir, profiler_options=opts)


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: dict) -> dict:
    from repro.kernels import dispatch
    from repro.launch.train import enable_compile_cache

    enable_compile_cache()
    compiles = _compile_counter()
    h = Harness(cell)
    dispatch.ORACLE_FALLBACKS.clear()
    trainer, feed, s32 = h.start(seed)
    prog, checked = h.checked_rounds(trainer, feed, s32)
    fallbacks = sum(dispatch.ORACLE_FALLBACKS.values())
    compiles_setup = compiles["n"]
    setup_s = time.perf_counter() - t_start

    # The measured window.
    tracedir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    span = jax.profiler.TraceAnnotation
    window_losses, done, feed_s = [], [], 0.0

    def wait(rec):
        with span("bench.wait"):
            window_losses.append(float(rec["loss"]))
        done.append(time.perf_counter())

    gc_before = [g["collections"] for g in gc.get_stats()]
    with _profile(tracedir):
        with span("bench.window"):
            t0 = time.perf_counter()
            prev, rounds = None, 0
            while True:
                with span("bench.feed"):
                    f0 = time.perf_counter()
                    batch = feed.next()
                    feed_s += time.perf_counter() - f0
                if prev is not None:
                    wait(prev)
                with span("bench.dispatch"):
                    prev = trainer.step(batch)
                rounds += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            wait(prev)
            with span("bench.wait"):
                jax.block_until_ready(trainer.params)
            t_end = time.perf_counter()
    compiles_window = compiles["n"] - compiles_setup
    gc_window = [g["collections"] - b
                 for g, b in zip(gc.get_stats(), gc_before)]
    window_s = t_end - t0
    intervals = list(np.diff(done))
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    memory_peak = _memory_peak(h.devices)

    ctx = Context(cell=cell, peaks=peaks, rounds=rounds, window_s=window_s,
                  feed_s=feed_s, fallbacks=fallbacks,
                  compiles=compiles_window)
    if trace:
        from bench import scopes
        from bench.trace import find_xplane
        ctx.trace = scopes.reduce(find_xplane(tracedir))
        shutil.rmtree(tracedir, ignore_errors=True)

    # Free the program's state, then the reference.
    del trainer, prev
    gc.collect()
    r0 = time.perf_counter()
    ref = h.reference(s32, checked)
    ref_s = time.perf_counter() - r0
    numbers = check.compare(prog, ref)
    ok, checks = check.judge(numbers, cell.limits)
    correct = bool(ok and rounds > 0 and failed == 0)

    log(f"setup {setup_s:.3f}s ({compiles_setup} compiles), window "
        f"{window_s:.3f}s, {rounds} rounds, {compiles_window} compiles "
        f"in the window, first round {done[0] - t0:.4f}s, round p90 "
        f"{p90(intervals):.4f}s max {max(intervals, default=0):.4f}s, "
        f"garbage collections {gc_window}, host feed "
        f"{feed_s:.3f}s, oracle fallbacks {fallbacks}, reference "
        f"{ref_s:.1f}s, losses {prog.losses.mean(axis=(1, 2)).tolist()} "
        f"(reference {ref.losses.mean(axis=(1, 2)).tolist()})")
    if compiles_window:
        log(f"warning: {compiles_window} compiles inside the window")
    if trace:
        log(f"trace: {ctx.trace['kernel_events']} rolling-matmul kernel "
            f"events, busy {ctx.trace['busy_s']:.4f}s of "
            f"{ctx.trace['window_s']:.4f}s")

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "tokens_per_s": rounds * cell.tokens_per_round / window_s,
               "round_p90_s": p90(intervals)}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    d0 = h.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": rounds, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        out["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                            "idle_gaps": ctx.trace["idle_gaps"]}
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out


def main(argv=None, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="python -m bench",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform!r}); the "
            "benchmark runs only on the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} chips; JAX sees "
            f"{len(devices)}")
        return 2
    from bench.peaks import peaks
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   peaks(devices[0].device_kind))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
