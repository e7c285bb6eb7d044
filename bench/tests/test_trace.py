"""The trace reduction, on a trace recorded on a TPU v5e and on hand-made
intervals.  The recorded trace (``data/fixture.xplane.pb``) holds six
rounds of a small jitted step: one rolling-matmul Pallas kernel and one XLA
fusion each, with a 4 ms ``bench.feed`` sleep before each dispatch, all
inside a ``bench.window`` span."""
import os

import pytest

from bench import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.read(FIXTURE)


def test_recorded_trace_planes_and_spans(recorded):
    assert list(recorded.devices) == [0]
    names = [s.name for s in recorded.spans]
    assert names.count("bench.feed") == 6 and names.count("bench.wait") == 6
    lo, hi = recorded.window()
    ops = [o for o in recorded.devices[0] if not o.asynchronous]
    assert len(ops) == 12
    assert all(lo <= o.start and o.end <= hi for o in ops)


def test_recorded_trace_reduction(recorded):
    r = tr.reduce(FIXTURE)
    ops = [o for o in recorded.devices[0] if not o.asynchronous]
    kernels = [o for o in ops if "custom-call(s32[" in o.name]
    assert r["kernel_events"] == len(kernels) == 6
    # the ops do not overlap: busy time is their sum, kernel time theirs
    assert r["busy_s"] == pytest.approx(
        sum(o.end - o.start for o in ops) * 1e-9)
    assert r["rolling_matmul_s"] == pytest.approx(
        sum(o.end - o.start for o in kernels) * 1e-9)
    assert r["window_s"] == pytest.approx(0.032812038)
    assert r["busy_s"] < 0.01 * r["window_s"]
    assert r["collective_s"] == 0.0
    assert r["device_ops"][0][0] == "rolling_matmul:f"
    assert r["device_ops"][1][0] == "fusion:convolution_tanh_fusion"
    # the device waits on the host's 4 ms feed before every round
    longest = r["idle_gaps"][:6]
    assert all(label == "bench.feed" for label, _ in longest)
    assert all(s > 0.003 for _, s in longest)


def _op(text, start, end, asynchronous=False):
    return tr.Op(text, start, end, asynchronous)


WHILE = "%while.1 = (f32[8]) while((f32[8]) %t), condition=%c, body=%b"
AR = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add"
FUSION = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
KERNEL = ('%closed_call.2 = f32[8,8]{1,0} custom-call(s32[1]{0} %o, f32[8,8]'
          '{1,0} %x, f32[8,16]{1,0} %w), custom_call_target="tpu_custom_call"')
SGD = ('%closed_call.9 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p, '
       'f32[8,128]{1,0} %g), custom_call_target="tpu_custom_call"')


def test_opcode_label_and_kernel_match():
    assert _op(WHILE, 0, 1).opcode == "while" and not _op(WHILE, 0, 1).leaf
    assert _op(AR, 0, 1).opcode == "all-reduce"
    assert _op(KERNEL, 0, 1).label == "rolling_matmul:closed_call"
    assert _op(SGD, 0, 1).label == "pallas:closed_call"
    assert _op(FUSION, 0, 1).label == "fusion:fusion"


def test_hand_counted_busy_collective_and_gaps():
    # device 0: a while loop spans [0, 100]; inside it a fusion [5, 12],
    # an all-reduce [10, 20] and a kernel [30, 40]; device 1 idles from 50
    t = tr.Trace(
        devices={0: [_op(WHILE, 0, 100), _op(FUSION, 5, 12), _op(AR, 10, 20),
                     _op(KERNEL, 30, 40)],
                 1: [_op(FUSION, 0, 50), _op(AR, 20, 35, True)]},
        spans=[tr.Op("bench.window", 0, 200), tr.Op("bench.feed", 100, 160),
               tr.Op("bench.wait", 160, 200)])
    w = t.window()
    assert w == (0, 200)
    # busy: device 0 [0, 100], device 1 [0, 50] -> mean 75 ns
    assert tr.busy_s(t, w) == pytest.approx(75e-9)
    assert tr.matched_s(t, w, tr.ROLLING_MATMUL) == pytest.approx(5e-9)
    coll, exposed = tr.collective_s(t, w)
    # device 0: 10 ns, of which [12, 20] has no other leaf op (the while
    # loop does not count); device 1: 15 ns, all under the fusion
    assert coll == pytest.approx(12.5e-9)
    assert exposed == pytest.approx(4e-9)
    gaps = tr.idle_gaps(t, w)
    assert gaps == [["bench.feed", pytest.approx(100e-9)]]
    top = dict(tr.top_ops(t, w))
    assert "while:while" not in top
    assert top["fusion:fusion"] == pytest.approx((7 + 50) / 2 * 1e-9)


def test_interval_helpers():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == \
        [(0, 2), (4, 8), (22, 30)]
    assert tr.length([(0, 2), (4, 8)]) == 6
