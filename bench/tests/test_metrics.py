"""The per-layer readers (``bench/metrics/``) on the recorded traces of
``data/``: each reader that the benchmark had before the window was
reduced by ``bench.scopes`` reads the same from ``bench.scopes.reduce`` as
from ``bench.trace.reduce``, and the readers of the program's scopes and
spans add up to what they divide."""
import os

import pytest

from bench import scopes as sc
from bench import spec
from bench import trace as tr
from bench.peaks import PEAKS
from bench.run import Context

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURES = ["fixture.xplane.pb", "scoped.xplane.pb"]
READ_BEFORE = ["host_feed_ms", "device_idle_pct", "round_mfu_pct",
               "rolling_matmul_ms", "rolling_matmul_roofline_pct",
               "oracle_fallbacks", "collective_ms", "collective_exposed_pct"]
ROUNDS = 6          # in each recorded window


def _ctx(workload, trace):
    return Context(cell=spec.load_cell(workload), peaks=PEAKS["TPU v5 lite"],
                   rounds=ROUNDS, window_s=trace["window_s"], feed_s=0.012,
                   fallbacks=0, trace=trace)


@pytest.mark.parametrize("fixture", FIXTURES)
@pytest.mark.parametrize("workload", ["ds7b-silo", "ds7b-mesh4-psum"])
def test_readers_read_as_before(fixture, workload):
    path = os.path.join(DATA, fixture)
    old, new = _ctx(workload, tr.reduce(path)), _ctx(workload,
                                                     sc.reduce(path))
    for name in READ_BEFORE:
        read = spec.metric_reader(name)
        assert read(new) == read(old), name


@pytest.fixture(scope="module")
def scoped():
    return _ctx("ds7b-silo", sc.reduce(os.path.join(DATA,
                                                    "scoped.xplane.pb")))


def test_phase_readers_add_up_to_busy(scoped):
    t = scoped.trace
    client = spec.metric_reader("client_phase_ms")(scoped)
    aggregate = spec.metric_reader("aggregate_ms")(scoped)
    rest = sum(v for k, v in t["phase_s"].items()
               if k not in ("fed.client_phase", "fed.aggregate"))
    assert client > 0 and aggregate > 0
    assert (client + aggregate) * ROUNDS / 1e3 + rest == pytest.approx(
        t["busy_s"], rel=1e-12)


def test_span_and_counter_readers(scoped):
    assert spec.metric_reader("round_host_ms")(scoped) == pytest.approx(
        1e3 * scoped.trace["round_host_s"] / ROUNDS)
    assert spec.metric_reader("round_recompiles")(scoped) == 0.0
    scoped_again = Context(**{**vars(scoped), "compiles": 2})
    assert spec.metric_reader("round_recompiles")(scoped_again) == 2.0


def test_attention_reader_reads_the_scope(scoped):
    # the recorded round has no attention: the reader reports nothing
    assert spec.metric_reader("attention_ms")(scoped) is None
    trace = {**scoped.trace, "model_scope_s": {"model.attention": 0.03}}
    ctx = Context(**{**vars(scoped), "trace": trace})
    assert spec.metric_reader("attention_ms")(ctx) == pytest.approx(5.0)
