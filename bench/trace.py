"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``.  On a TPU a device plane
(``/device:TPU:<n>``) has a line of XLA operations (``XLA Ops``) and a line
of asynchronous ones (``Async XLA Ops``); each event is one HLO instruction,
named by its text (``%fusion.12 = f32[...] fusion(...)``), with a start and
a duration in nanoseconds on the trace's clock.  A ``while`` loop is an
event of its own that spans the events of its body.  The benchmark's own
host spans (``jax.profiler.TraceAnnotation`` named ``bench.*``) lie on the
host plane on the same clock; ``bench.window`` bounds the measured window.

From these:

- busy time: the union of a device's ``XLA Ops`` intervals inside the
  window; idle is the rest of the window;
- kernel time: the durations of the rolling-matmul kernels.  Pallas calls
  carry no kernel name in the trace (``kernel_metadata={}``); the
  rolling-matmul family (``kernels/rolling_matmul*.py``) is told apart as
  the ``tpu_custom_call`` whose first operand is the prefetched ``s32``
  window offset (the elementwise SGD kernel has none);
- collective time: the durations of collective operations on either line,
  and the part of them during which no other leaf operation runs on that
  device (exposed);
- idle gaps: stretches of the window with no operation on a device,
  labelled by the innermost ``bench.*`` span open at their midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
#: The Pallas kernels of ``kernels/rolling_matmul*.py``: a tpu_custom_call
#: whose first operand is the prefetched s32 offset.
ROLLING_MATMUL = re.compile(r"custom-call\(s32\[.*custom_call_target=\"tpu_custom_call\"")
PALLAS = re.compile(r"custom_call_target=\"tpu_custom_call\"")
#: Operations whose events span other operations' (control flow).
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")

Interval = Tuple[float, float]


@dataclass
class Op:
    name: str             # the instruction's text
    start: float          # ns
    end: float            # ns
    asynchronous: bool = False

    @property
    def opcode(self) -> str:
        m = _OPCODE.search(self.name)
        return m.group(1) if m else self.name

    @property
    def leaf(self) -> bool:
        return self.opcode not in CONTAINERS

    @property
    def label(self) -> str:
        """Short name: the kind of operation and the instruction's name
        without its instance number (``fusion:multiply_reduce_fusion``)."""
        ident = re.sub(r"(\.\d+)+$", "",
                       self.name.split(" = ")[0].lstrip("%"))
        if ROLLING_MATMUL.search(self.name):
            kind = "rolling_matmul"
        elif PALLAS.search(self.name):
            kind = "pallas"
        else:
            kind = self.opcode
        return f"{kind}:{ident}"


@dataclass
class Trace:
    devices: Dict[int, List[Op]] = field(default_factory=dict)
    spans: List[Op] = field(default_factory=list)

    def window(self) -> Interval:
        """(start, end) of the last ``bench.window`` span."""
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[-1].start, w[-1].end


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                out.devices.setdefault(int(m.group(1)), []).extend(
                    Op(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       line.name == ASYNC_LINE)
                    for e in line.events)
            elif not m:
                out.spans.extend(
                    Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    if not out.devices:
        raise ValueError(f"{path}: no device plane with an {OPS_LINE!r} line")
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        elif e > s:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` outside the disjoint
    sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _ops_in(ops: List[Op], lo, hi) -> List[Op]:
    return [o for o in ops if o.end > lo and o.start < hi]


def _busy(ops, lo, hi):
    return union(clip([(o.start, o.end) for o in ops if not o.asynchronous],
                      lo, hi))


def busy_s(trace: Trace, window: Interval) -> float:
    """Busy seconds averaged over the devices."""
    lo, hi = window
    per = [length(_busy(ops, lo, hi)) for ops in trace.devices.values()]
    return sum(per) / len(per) * 1e-9


def matched_s(trace: Trace, window: Interval, pattern) -> float:
    """Seconds of operations matching ``pattern``, averaged over devices."""
    lo, hi = window
    per = [length(union(clip([(o.start, o.end) for o in ops
                              if pattern.search(o.name)], lo, hi)))
           for ops in trace.devices.values()]
    return sum(per) / len(per) * 1e-9


def collective_s(trace: Trace, window: Interval) -> Tuple[float, float]:
    """(collective seconds, exposed collective seconds), averaged over the
    devices.  Exposed: no other operation runs on that device."""
    lo, hi = window
    tot, exp = [], []
    for ops in trace.devices.values():
        coll = union(clip([(o.start, o.end) for o in ops
                           if COLLECTIVE.search(o.opcode)], lo, hi))
        other = union(clip([(o.start, o.end) for o in ops
                            if o.leaf and not o.asynchronous
                            and not COLLECTIVE.search(o.opcode)], lo, hi))
        tot.append(length(coll))
        exp.append(length(subtract(coll, other)))
    n = len(trace.devices)
    return sum(tot) / n * 1e-9, sum(exp) / n * 1e-9


def top_ops(trace: Trace, window: Interval, n: int = 10):
    """``[[label, seconds]]`` of the leaf operations (by :attr:`Op.label`)
    that took the most device time, averaged over the devices."""
    lo, hi = window
    acc: Dict[str, float] = {}
    for ops in trace.devices.values():
        for o in _ops_in(ops, lo, hi):
            if o.asynchronous or not o.leaf:
                continue
            acc[o.label] = (acc.get(o.label, 0.0)
                            + min(o.end, hi) - max(o.start, lo))
    nd = len(trace.devices)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / nd * 1e-9] for k, v in best]


def _label(spans: List[Op], t: float) -> str:
    inner = [s for s in spans if s.start <= t < s.end
             and s.name != WINDOW_SPAN]
    if not inner:
        return "none"
    return min(inner, key=lambda s: s.end - s.start).name


def idle_gaps(trace: Trace, window: Interval, n: int = 10):
    """``[[label, seconds]]`` of the longest idle gaps of device 0 in the
    window, each labelled by the benchmark's host span at its midpoint."""
    lo, hi = window
    busy = _busy(trace.devices[min(trace.devices)], lo, hi)
    gaps = subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(trace.spans, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:n]]


def reduce(path: str) -> dict:
    """Everything the per-layer readers and the result line take from one
    trace file."""
    trace = read(path)
    window = trace.window()
    coll, exposed = collective_s(trace, window)
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": busy_s(trace, window),
        "rolling_matmul_s": matched_s(trace, window, ROLLING_MATMUL),
        "collective_s": coll,
        "collective_exposed_s": exposed,
        "device_ops": top_ops(trace, window),
        "idle_gaps": idle_gaps(trace, window),
        "kernel_events": sum(1 for ops in trace.devices.values()
                             for o in _ops_in(ops, *window)
                             if ROLLING_MATMUL.search(o.name)),
    }
