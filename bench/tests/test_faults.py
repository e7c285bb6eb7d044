"""A whole run with the chip check skipped, on the CPU at a small size: a
sound program comes out ``correct``, and each fault planted in the timed
path underneath (the program's round) comes out not correct."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import run
from bench.peaks import PEAKS
from bench.tests import tiny
from repro.core.fedavg import WindowFedAvg

V5E = PEAKS["TPU v5 lite"]
_ROUND = WindowFedAvg.round


def unchanged(self, params, batch, round_idx, rng=None):
    """A round that returns its state unchanged."""
    _, metrics = _ROUND(self, params, batch, round_idx, rng)
    return params, metrics


def half_batch(self, params, batch, round_idx, rng=None):
    """A round that leaves out half of each step's rows and takes the mean
    over the rest."""
    t = batch["tokens"]
    K, C, B, S = t.shape
    rows = t.reshape(K, C * B, S)
    h = (C * B) // 2
    rows = rows.at[:, C * B - h:].set(rows[:, :h])
    return _ROUND(self, params, {"tokens": rows.reshape(K, C, B, S)},
                  round_idx, rng)


def _run(workload, seed=2**31 + 3):
    cell = tiny.cell(workload)
    return run.run_cell(cell, seed, 0.5, False, time.perf_counter(), V5E)


@pytest.fixture(autouse=True)
def _no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.mark.parametrize("workload", ["ds7b-silo", "phi3-partition"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s", "round_p90_s"}


@pytest.mark.parametrize("fault", [unchanged, half_batch],
                         ids=["unchanged_state", "half_batch"])
@pytest.mark.parametrize("workload", ["ds7b-silo", "phi3-partition"])
def test_fault_is_not_correct(workload, fault, monkeypatch):
    monkeypatch.setattr(WindowFedAvg, "round", fault)
    out = _run(workload)
    assert not out["correct"], out["checks"]


MESH = """
import json, sys, time
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_enable_compilation_cache", False)
from bench import run
from bench.peaks import PEAKS
from bench.tests import tiny
if {drop!r}:
    jax.lax.psum = lambda x, axis, **kw: x   # the exchange left out
cell = tiny.cell("ds7b-mesh4-psum")
out = run.run_cell(cell, 99, 0.5, False, time.perf_counter(),
                   PEAKS["TPU v5 lite"])
print(json.dumps(out))
"""


@pytest.mark.parametrize("drop", [False, True], ids=["sound", "no_exchange"])
def test_mesh_exchange(drop):
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c",
                           MESH.format(root=root, drop=drop)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (not drop), out["checks"]
