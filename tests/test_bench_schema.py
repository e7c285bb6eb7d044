"""Schema validation for the benchmark artifact.

``benchmarks/run.py`` merges every bench's metrics into
``experiments/bench_results.json`` — the artifact CI uploads per commit for
the perf trajectory.  ``BENCH_SCHEMA`` (declared next to the benches)
pins each entry's metric names, value types, and 0/1 gate metrics; this
module validates the artifact against it so a bench rename, a dropped
gate, or a type drift (e.g. a formatted string where a number belongs)
fails instead of silently reshaping the trajectory data.

The artifact is generated, not committed (``experiments/`` is
gitignored): the schema-consistency tests always run, while the
artifact-backed ones skip when the file is absent and run for real in
the ``bench-smoke`` CI job right after the benches regenerate it.
"""
import json
import os
import sys

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(REPO))

from benchmarks.run import BENCHES, BENCH_SCHEMA  # noqa: E402

ARTIFACT = os.path.join(REPO, "experiments", "bench_results.json")

#: Metrics every bench may emit regardless of its declared schema.
UNIVERSAL = {"bench_seconds": (int, float), "note": str}


@pytest.fixture(scope="module")
def results():
    if not os.path.exists(ARTIFACT):
        pytest.skip("experiments/bench_results.json not generated — run "
                    "`python -m benchmarks.run` (bench-smoke does in CI)")
    with open(ARTIFACT) as f:
        return json.load(f)


def test_every_bench_has_a_schema_entry():
    missing = sorted(set(BENCHES) - set(BENCH_SCHEMA))
    assert not missing, f"benches without a schema entry: {missing}"


def test_schema_gates_are_declared_metrics():
    for name, spec in BENCH_SCHEMA.items():
        for gate in spec.get("gates", ()):
            assert gate in spec["metrics"], \
                f"{name}: gate {gate!r} not in declared metrics"


def test_artifact_entries_are_known_benches(results):
    unknown = sorted(set(results) - set(BENCH_SCHEMA))
    assert not unknown, f"artifact entries with no schema: {unknown}"


def test_artifact_metrics_match_schema(results):
    """Entries with a declared metric set must carry exactly those metrics
    (plus the universal extras) with the declared types; entries declared
    open ({} metrics) only get the type check on universal extras."""
    problems = []
    for name, entry in results.items():
        spec = BENCH_SCHEMA[name]
        declared = spec["metrics"]
        for metric, value in entry.items():
            want = declared.get(metric, UNIVERSAL.get(metric))
            if want is None:
                if declared:           # open entries accept anything
                    problems.append(f"{name}.{metric}: undeclared")
                continue
            # JSON has no int/float split guarantee; bools are not numbers
            if isinstance(value, bool) or not isinstance(value, want):
                problems.append(
                    f"{name}.{metric}: {type(value).__name__} != {want}")
        if declared:
            for metric in set(declared) - set(entry):
                problems.append(f"{name}.{metric}: missing from artifact")
    assert not problems, "\n".join(problems)


def test_artifact_gates_hold(results):
    """Every declared gate metric present in the artifact must be exactly 1
    — the artifact is the last-known-good state the bench-smoke CI job
    re-establishes per commit."""
    failed = []
    for name, entry in results.items():
        for gate in BENCH_SCHEMA[name].get("gates", ()):
            if gate in entry and entry[gate] != 1:
                failed.append(f"{name}.{gate} = {entry[gate]!r}")
    assert not failed, f"gates not holding in artifact: {failed}"


def test_fused_bench_speedup_recorded_above_one(results):
    """The fused arm's contract lives in the artifact too: bitwise equal
    to extract, carrying each client's cut window.  Both arms carry the
    same cut tree on the bench's dense model (the same compact stacks at
    both capacities), so their CPU time ratio
    ``extract_over_fused_speedup`` is a profile number near 1, not held
    above 1: only the chip benchmark judges speed."""
    entry = results.get("fed_round_fused")
    if entry is None:
        pytest.skip("fed_round_fused not in artifact")
    for tag in ("", "_cap75"):
        assert (entry[f"fused_client_wsub_stacks{tag}"]
                == entry[f"extract_client_wsub_stacks{tag}"] > 0)
    assert entry["round_bitwise_equal"] == 1
    assert entry["fused_carries_window"] == 1
