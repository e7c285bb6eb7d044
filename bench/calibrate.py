"""Readings that set a cell's correctness limits, many seeds in one process.

    python -m bench.calibrate --workload ds7b-silo --seeds 1 2 3 ... \
        --control-seeds 1 2 3 [--kinds control half_batch ...]

For every seed: the program's first three rounds through the benchmark's
own trainer and feed, and the plain float32 reference; the numbers of
``bench.check`` compare them (the lower readings).  For each control seed
also, each compared with the float32 reference (the upper readings):

- ``control``: the reference in the next precision below the configuration's
  float32, bfloat16, put in the program's place;
- ``half_batch``: the reference with half of each step's rows left out and
  the mean taken over the rest;
- ``no_exchange`` (cells whose clients are sharded over chips): the
  reference with only one shard's deltas reaching the server;
- ``bf16_operands``: no control, a reading of the precision the
  configurations state (``"matmul_precision": "default"``): the reference
  with every matmul at one bfloat16 pass of its operands and float32
  accumulation (on a TPU), float32 weights and updates.  The limits are
  meant to pass it.

``--kinds`` takes only those readings on the control seeds.

A round that returns its state unchanged reads 1 on both change numbers by
their definition and needs no run.  Prints one JSON line per seed and a
summary line: per number the largest program reading and the smallest and
largest reading of each control, fault and other reading.  Runs on the chip; exits with code 2
without one.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m bench.calibrate",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--kinds", nargs="*", default=None)
    args = ap.parse_args(argv)

    from bench import spec
    from bench.run import Harness, log
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} TPU chips; JAX sees "
            f"{len(devices)} {devices[0].platform} device(s)")
        return 2
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()

    rows = readings(Harness(cell), args.seeds, args.control_seeds,
                    on_row=lambda row: print(json.dumps(row), flush=True),
                    kinds=args.kinds)
    print(json.dumps({"summary": summarize(rows), "workload": cell.name,
                      "seeds": len(rows)}), flush=True)
    return 0


def kinds_of(cell):
    """The readings taken on a control seed of ``cell``."""
    return (["control", "half_batch"]
            + (["no_exchange"] if cell.mix.get("mesh_agg") else [])
            + ["bf16_operands"])


def readings(h, seeds, control_seeds=(), on_row=None, kinds=None):
    """One row per seed: the program's numbers, and for control seeds the
    readings ``kinds`` (all of ``kinds_of``; see the module's doc)."""
    import jax.numpy as jnp

    from bench import check
    kw = {"control": dict(dtype=jnp.bfloat16),
          "half_batch": dict(fault="half_batch"),
          "no_exchange": dict(fault="no_exchange"),
          "bf16_operands": dict(precision="bfloat16")}
    kinds = kinds_of(h.cell) if kinds is None else kinds
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, feed, s32 = h.start(seed)
        prog, batches = h.checked_rounds(trainer, feed, s32)
        del trainer
        gc.collect()
        ref = h.reference(s32, batches)
        row = {"seed": seed, "program": check.compare(prog, ref)}
        if seed in control_seeds:
            for kind in kinds:
                row[kind] = check.compare(
                    h.reference(s32, batches, **kw[kind]), ref)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        if on_row:
            on_row(row)
    return rows


def summarize(rows):
    """Per number: the largest program reading, the smallest and largest
    of each control, fault and other reading."""
    from bench import check
    kinds = sorted({k for r in rows for k in r} - {"seed", "seconds",
                                                   "program"})
    out = {}
    for k in check.NUMBERS:
        out[k] = {"program_max": max(r["program"][k] for r in rows)}
        for kind in kinds:
            vals = [r[kind][k] for r in rows if kind in r]
            out[k][kind + "_min"] = min(vals)
            out[k][kind + "_max"] = max(vals)
    return out


if __name__ == "__main__":
    sys.exit(main())
