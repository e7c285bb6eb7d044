"""The scope reduction (``bench.scopes``): on the recorded chip trace of
``test_trace.py`` (no program scopes), on a hand-made XPlane, on hand-made
intervals, and on ``data/scoped.xplane.pb``, a chip trace of a small round
with the program's scopes, kernel names and spans (``record_scoped.py``)."""
import os

import pytest

from bench import scopes as sc
from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "fixture.xplane.pb")

WHILE = "%while.1 = (f32[8]) while((f32[8]) %t), condition=%c, body=%b"
FUSION = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
ADD = "%add.3 = f32[8]{0} add(f32[8]{0} %y, f32[8]{0} %z)"
CLIENT = "jit(round)/fed.client_phase/while"
ATTN = "jit(round)/transpose(jvp(fed.client_phase))/model.attention/dot"
AGG = "jit(round)/fed.aggregate/add"


def test_read_returns_the_fixture_scopes():
    t = sc.read(FIXTURE)
    ops = [o for o in t.devices[0] if not o.asynchronous]
    assert {o.scope for o in ops} == {"jit(f)/pallas_call",
                                     "jit(f)/dot_general"}
    kernel = [o for o in ops if tr.ROLLING_MATMUL.search(o.name)]
    assert {o.scope for o in kernel} == {"jit(f)/pallas_call"}
    assert {o.phase for o in ops} == {sc.UNSCOPED}


def test_reduce_keeps_every_reading_of_bench_trace():
    old, new = tr.reduce(FIXTURE), sc.reduce(FIXTURE)
    assert {k: new[k] for k in old} == old
    assert sum(new["phase_s"].values()) == pytest.approx(new["busy_s"])
    assert set(new["phase_s"]) == {sc.UNSCOPED}
    assert new["model_scope_s"] == {} and new["round_host_s"] == 0.0
    # the fixture's one kernel, named for its jitted function
    assert new["kernel_s"] == {"f": pytest.approx(new["rolling_matmul_s"])}


def _op(text, start, end, scope="", asynchronous=False):
    return sc.ScopedOp(text, start, end, asynchronous, scope)


def test_hand_counted_phases_attention_and_spans():
    # a client-phase loop [0, 100] holds an attention op [10, 30] and a
    # fusion [40, 60]; the gaps between them are the loop's own time; an
    # aggregation op [100, 120] follows, and an unscoped one [130, 135]
    t = tr.Trace(
        devices={0: [_op(WHILE, 0, 100, CLIENT), _op(FUSION, 10, 30, ATTN),
                     _op(FUSION, 40, 60, CLIENT + "/fusion"),
                     _op(ADD, 100, 120, AGG), _op(ADD, 130, 135),
                     _op(ADD, 0, 500, AGG, asynchronous=True)]},
        spans=[tr.Op("bench.window", 0, 200),
               tr.Op("repro.round", 120, 200),
               tr.Op("repro.round.dispatch", 150, 200),
               tr.Op("bench.dispatch", 140, 200)])
    w = t.window()
    phases = sc.phase_s(t, w)
    assert phases == {"fed.client_phase": pytest.approx(100e-9),
                      "fed.aggregate": pytest.approx(20e-9),
                      sc.UNSCOPED: pytest.approx(5e-9)}
    assert sum(phases.values()) == pytest.approx(tr.busy_s(t, w))
    assert sc.model_scope_s(t, w) == {"model.attention":
                                      pytest.approx(20e-9)}
    assert sc.span_s(t, w, sc.ROUND_SPAN) == pytest.approx(80e-9)
    # the longest gap, [135, 200], lies in repro.round.dispatch at its
    # midpoint: the innermost span of either kind
    assert tr.idle_gaps(t, w)[0] == ["repro.round.dispatch",
                                     pytest.approx(65e-9)]


def test_phase_is_the_outermost_fed_scope():
    op = _op(FUSION, 0, 1, "jit(r)/transpose(jvp(fed.aggregate))/"
                           "fed.client_phase/x")
    assert op.phase == "fed.aggregate"
    assert op.model_scopes == set()
    assert _op(FUSION, 0, 1, ATTN).model_scopes == {"model.attention"}
    nested = _op(FUSION, 0, 1, "jit(r)/model.moe/jvp(model.attention)/x")
    assert nested.model_scopes == {"model.moe", "model.attention"}
    assert _op(FUSION, 0, 1, "jit(r)/model.attentions/x").phase == sc.UNSCOPED


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, events, interned=()):
    """An XPlane whose event metadata carry ``tf_op`` stats: ``events`` is
    ``[(name, path or stat-metadata id of an interned path)]``."""
    stats = [(1, "tf_op")] + list(interned)
    body = _field(2, name)
    for sid, sname in stats:
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                  + _field(2, sname)))
    for eid, (ename, path) in enumerate(events, start=1):
        stat = _field(1, 1) + (_field(7, path) if isinstance(path, int)
                               else _field(5, path))
        meta = _field(1, eid) + _field(2, ename) + _field(5, stat)
        body += _field(4, _field(1, eid) + _field(2, meta))
    return _field(1, body)


def test_metadata_scopes_decode_strings_and_interned_refs(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _plane("/device:TPU:0", [("%a = add", "jit(f)/fed.aggregate/add:"),
                                 ("%b = mul", 9)],
               interned=[(9, "jit(f)/fed.offsets/mul:")])
        + _plane("/host:CPU", [("%a = add", "ignored:")]))
    assert sc.metadata_scopes(str(path)) == {
        ("/device:TPU:0", "%a = add"): "jit(f)/fed.aggregate/add",
        ("/device:TPU:0", "%b = mul"): "jit(f)/fed.offsets/mul"}


def test_metadata_scopes_refuse_one_name_with_two_scopes(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane("/device:TPU:0", [("%a = add", "jit(f)/x:"),
                                              ("%a = add", "jit(g)/y:")]))
    with pytest.raises(ValueError, match="two scopes"):
        sc.metadata_scopes(str(path))


SCOPED = os.path.join(DATA, "scoped.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    """Six rounds of a small windowed round on a TPU v5e, with the
    program's scopes, kernel names and spans (``record_scoped.py``)."""
    return sc.reduce(SCOPED)


def test_scoped_trace_phases_and_unscoped_add_up_to_busy(scoped):
    phases = scoped["phase_s"]
    assert {"fed.offsets", "fed.client_phase", "fed.aggregate"} <= set(
        phases)
    assert sum(phases.values()) == pytest.approx(scoped["busy_s"],
                                                 rel=1e-12)
    assert phases["fed.client_phase"] > 0.5 * scoped["busy_s"]


def test_scoped_trace_gaps_lie_in_program_spans(scoped):
    # the batches were made before the window: the device waits only on
    # the host's round (RNG split, batch upload, dispatch) or on a loss
    labels = [label for label, _ in scoped["idle_gaps"]]
    assert sum(label.startswith(sc.PROGRAM_SPAN_PREFIX)
               for label in labels) >= 6
    assert set(labels) <= {"bench.wait", "repro.round", "repro.round.put",
                           "repro.round.dispatch"}
    assert scoped["round_spans"] == 6 and scoped["round_host_s"] > 0


def test_scoped_trace_pallas_ops_carry_kernel_names():
    t = sc.read(SCOPED)
    pallas = [o for o in t.devices[0] if tr.PALLAS.search(o.name)]
    assert pallas
    for o in pallas:
        assert any(k in o.label for k in ("rolling_matmul_batched_multi",
                                          "sgd_step")), o.label
        assert o.phase == "fed.client_phase"


def test_scoped_trace_kernels_and_model_scopes_add_up(scoped):
    kernels = scoped["kernel_s"]
    # the names of test_scoped_trace_pallas_ops_carry_kernel_names
    assert len(kernels) == 2 and all(
        any(k in name for k in ("rolling_matmul_batched_multi", "sgd_step"))
        for name in kernels)
    rolling = [v for k, v in kernels.items() if "rolling_matmul" in k]
    assert sum(rolling) == pytest.approx(scoped["rolling_matmul_s"],
                                         rel=1e-12)
    assert 0 < sum(kernels.values()) <= scoped["phase_s"]["fed.client_phase"]
    assert sum(scoped["model_scope_s"].values()) <= scoped["busy_s"]


def _split_by_scan(ops, lo, hi):
    """``scopes._split`` as first written, kept as the pin: each rest
    interval looks for its container by a scan of them all."""
    sync = [o for o in ops if not o.asynchronous and o.end > lo
            and o.start < hi]
    leaves = sorted((o for o in sync if o.leaf), key=lambda o: o.start)
    acc, covered, cursor = {}, [], lo
    for o in leaves:
        s, e = max(o.start, cursor), min(o.end, hi)
        if e > s:
            acc[o.phase] = acc.get(o.phase, 0.0) + e - s
            covered.append((s, e))
            cursor = e
    containers = sorted((o for o in sync if not o.leaf),
                        key=lambda o: o.end - o.start)
    for s, e in tr.subtract(tr._busy(sync, lo, hi), tr.union(covered)):
        t = (s + e) / 2
        owner = next((c for c in containers if c.start <= t < c.end), None)
        phase = owner.phase if owner is not None else sc.UNSCOPED
        acc[phase] = acc.get(phase, 0.0) + e - s
    return acc


def test_split_as_by_scan():
    t = sc.read(SCOPED)
    lo, hi = t.window()
    for ops in t.devices.values():
        assert sc._split(ops, lo, hi) == _split_by_scan(ops, lo, hi)
    # nested and overlapping containers, and rest outside any
    ops = [_op(WHILE, 0, 100, CLIENT), _op(WHILE, 10, 50, AGG),
           _op(WHILE, 40, 90, "jit(r)/fed.offsets/while"),
           _op(FUSION, 20, 48, CLIENT), _op(FUSION, 60, 70, CLIENT),
           _op(WHILE, 95, 120, "")]
    assert sc._split(ops, 0, 130) == _split_by_scan(ops, 0, 130)


def test_scoped_trace_keeps_bench_trace_readings(scoped):
    old = tr.reduce(SCOPED)
    assert {k: scoped[k] for k in old if k != "idle_gaps"} == {
        k: v for k, v in old.items() if k != "idle_gaps"}
    # the same gaps, labelled by the innermost span of either kind
    assert [s for _, s in scoped["idle_gaps"]] == [
        s for _, s in old["idle_gaps"]]


def test_pruning_keeps_every_reading(tmp_path, scoped):
    from bench.tests import record_scoped
    out = tmp_path / "again.xplane.pb"
    record_scoped.prune(SCOPED, str(out))
    assert sc.reduce(str(out)) == scoped
