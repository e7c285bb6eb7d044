"""Phase scopes and program spans of a profiler trace.

    python -m bench.scopes <trace.xplane.pb> [--rounds N]

prints the reduction of one trace as JSON.  It extends ``bench.trace``,
whose readings it keeps: the program names the round's work by
``jax.named_scope`` (``fed.offsets``, ``fed.client_phase``,
``fed.aggregate``, ``fed.server_step``, and ``model.*`` scopes such as
``model.attention``; a program without them reads as unscoped), its
Pallas kernels by ``pallas_call(name=)``, and its host work by
``jax.profiler.TraceAnnotation`` spans (``repro.round``,
``repro.round.put``, ``repro.round.dispatch``, ``repro.sync``).
``bench/run.py`` reduces every traced window with :func:`reduce`.

Where a scope lands.  On a TPU device plane the name-stack path of an
operation, e.g. ``jit(step)/transpose(jvp(fed.client_phase))/...``, is the
``tf_op`` stat of the event's *metadata* (``path:type``, the type empty).
``jax.profiler.ProfileData`` exposes only an event's own stats, so the
metadata is decoded here from the XPlane protobuf wire format, with the
field numbers of ``tsl/profiler/protobuf/xplane.proto`` (as in the
``xplane_pb2.py`` that TensorFlow ships; TensorFlow is not imported), and
joined to ``ProfileData``'s events by (plane, metadata name).

An operation's phase is the outermost ``fed.*`` component of its path, the
``jvp(`` / ``transpose(`` wrappers of ``grad`` stripped; a ``model.*``
scope is counted wherever it appears, as the leaf operations under it.
Device time is split so that the phases and the unscoped rest add up to
the busy time of ``bench.trace``: each instant goes to the leaf operation
running then, or, between the leaves of a loop body, to the innermost loop
or call around it.  A Pallas kernel's time is keyed by its name as the
instruction carries it (:attr:`bench.trace.Op.label` after the colon, e.g.
``rolling_matmul_batched_multi`` or ``sgd_step``).
"""
from __future__ import annotations

import argparse
import functools
import heapq
import json
import re
import sys
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

from bench import trace as tr

PHASE_PREFIX = "fed."
PHASES = ("fed.offsets", "fed.client_phase", "fed.aggregate",
          "fed.server_step")
MODEL_PREFIX = "model."
PROGRAM_SPAN_PREFIX = "repro."
ROUND_SPAN = "repro.round"
UNSCOPED = "unscoped"
_PATH_SEP = re.compile(r"[/()]")

# Field numbers of tsl/profiler/protobuf/xplane.proto.
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_XEVENTMETADATA_NAME, _XEVENTMETADATA_STATS = 2, 5
_XSTATMETADATA_ID, _XSTATMETADATA_NAME = 1, 2
_XSTAT_METADATA_ID, _XSTAT_STR, _XSTAT_REF = 1, 5, 7
_TF_OP = "tf_op"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint or a
    fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield field, value


def _map_entries(entries) -> Iterator[Tuple[int, memoryview]]:
    for entry in entries:
        key, value = 0, memoryview(b"")
        for f, v in _fields(entry):
            if f == _MAP_KEY:
                key = v
            elif f == _MAP_VALUE:
                value = v
        yield key, value


def _plane_scopes(plane) -> Tuple[str, Dict[str, str]]:
    """(plane name, {event metadata name: tf_op path}) of one XPlane."""
    name, events, stats = "", [], []
    for f, v in _fields(plane):
        if f == _XPLANE_NAME:
            name = bytes(v).decode()
        elif f == _XPLANE_EVENT_METADATA:
            events.append(v)
        elif f == _XPLANE_STAT_METADATA:
            stats.append(v)
    if not tr.DEVICE_PLANE.match(name):
        return name, {}
    stat_names = {}
    for _, value in _map_entries(stats):
        sid, sname = 0, ""
        for f, v in _fields(value):
            if f == _XSTATMETADATA_ID:
                sid = v
            elif f == _XSTATMETADATA_NAME:
                sname = bytes(v).decode()
        stat_names[sid] = sname
    tf_op = [k for k, v in stat_names.items() if v == _TF_OP]
    scopes: Dict[str, str] = {}
    if not tf_op:
        return name, scopes
    for _, value in _map_entries(events):
        ename, path = None, None
        for f, v in _fields(value):
            if f == _XEVENTMETADATA_NAME:
                ename = bytes(v).decode()
            elif f == _XEVENTMETADATA_STATS:
                sid, text = None, None
                for sf, sv in _fields(v):
                    if sf == _XSTAT_METADATA_ID:
                        sid = sv
                    elif sf == _XSTAT_STR:
                        text = bytes(sv).decode()
                    elif sf == _XSTAT_REF:
                        text = stat_names.get(sv, "")
                if sid == tf_op[0]:
                    path = text
        if ename is None or path is None:
            continue
        path = path.rpartition(":")[0] if ":" in path else path
        if scopes.setdefault(ename, path) != path:
            raise ValueError(f"{name}: operation {ename[:80]!r} has two "
                             f"scopes, {scopes[ename]!r} and {path!r}")
    return name, scopes


def metadata_scopes(path: str) -> Dict[Tuple[str, str], str]:
    """``{(device plane, event metadata name): scope path}`` of a trace."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf):
        if f == _XSPACE_PLANES:
            name, scopes = _plane_scopes(plane)
            out.update({(name, e): s for e, s in scopes.items()})
    return out


@functools.lru_cache(maxsize=None)
def _from_text(text: str) -> Tuple[str, str]:
    """(opcode, label) of an instruction's text."""
    op = tr.Op(text, 0, 0)
    return op.opcode, op.label


@functools.lru_cache(maxsize=None)
def _from_path(path: str) -> Tuple[str, frozenset]:
    """(phase, ``model.*`` scopes) of a name-stack path."""
    parts = _PATH_SEP.split(path)
    phase = next((p for p in parts if p.startswith(PHASE_PREFIX)), UNSCOPED)
    return phase, frozenset(p for p in parts if p.startswith(MODEL_PREFIX))


@dataclass
class ScopedOp(tr.Op):
    """An operation with its scope.  A window holds about a million events
    of a few thousand instructions and paths, so what is read from their
    text is worked out once per text."""

    scope: str = ""       # the name-stack path (tf_op), "" where none

    @property
    def opcode(self) -> str:
        return _from_text(self.name)[0]

    @property
    def label(self) -> str:
        return _from_text(self.name)[1]

    @property
    def phase(self) -> str:
        """The outermost ``fed.*`` scope of the path, else ``unscoped``."""
        return _from_path(self.scope)[0]

    @property
    def model_scopes(self) -> frozenset:
        """Every ``model.*`` component of the path."""
        return _from_path(self.scope)[1]


def read(path: str) -> tr.Trace:
    """``bench.trace.read``, with each device operation's ``scope`` and the
    program's ``repro.*`` host spans beside the ``bench.*`` ones."""
    from jax.profiler import ProfileData
    scopes = metadata_scopes(path)
    data = ProfileData.from_file(path)
    out = tr.Trace()
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (tr.OPS_LINE, tr.ASYNC_LINE):
                out.devices.setdefault(int(m.group(1)), []).extend(
                    ScopedOp(e.name, e.start_ns, e.start_ns + e.duration_ns,
                             line.name == tr.ASYNC_LINE,
                             scopes.get((plane.name, e.name), ""))
                    for e in line.events)
            elif not m:
                out.spans.extend(
                    tr.Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith((tr.SPAN_PREFIX,
                                          PROGRAM_SPAN_PREFIX)))
    if not out.devices:
        raise ValueError(f"{path}: no device plane with an "
                         f"{tr.OPS_LINE!r} line")
    return out


def _split(ops: List[ScopedOp], lo: float, hi: float) -> Dict[str, float]:
    """Busy ns of one device inside [lo, hi] by phase: each instant to the
    leaf running then, else to the innermost container around it."""
    sync = [o for o in ops if not o.asynchronous and o.end > lo
            and o.start < hi]
    leaves = sorted((o for o in sync if o.leaf), key=lambda o: o.start)
    acc: Dict[str, float] = {}
    covered, cursor = [], lo
    for o in leaves:
        s, e = max(o.start, cursor), min(o.end, hi)
        if e > s:
            acc[o.phase] = acc.get(o.phase, 0.0) + e - s
            covered.append((s, e))
            cursor = e
    # the rest, in time order, goes to the shortest container open at its
    # midpoint: a heap holds the containers begun so far by rank in length,
    # and those that have ended leave from its top
    containers = sorted((o for o in sync if not o.leaf),
                        key=lambda o: o.end - o.start)
    begun = sorted(range(len(containers)), key=lambda i: containers[i].start)
    open_, j = [], 0
    for s, e in tr.subtract(tr._busy(sync, lo, hi), tr.union(covered)):
        t = (s + e) / 2
        while j < len(begun) and containers[begun[j]].start <= t:
            heapq.heappush(open_, begun[j])
            j += 1
        while open_ and containers[open_[0]].end <= t:
            heapq.heappop(open_)
        phase = containers[open_[0]].phase if open_ else UNSCOPED
        acc[phase] = acc.get(phase, 0.0) + e - s
    return acc


def phase_s(trace: tr.Trace, window: tr.Interval) -> Dict[str, float]:
    """Device seconds by phase (``fed.*`` or ``unscoped``), averaged over
    the devices; they add up to ``bench.trace.busy_s``."""
    lo, hi = window
    total: Dict[str, float] = {}
    for ops in trace.devices.values():
        for k, v in _split(ops, lo, hi).items():
            total[k] = total.get(k, 0.0) + v
    n = len(trace.devices)
    return {k: v / n * 1e-9 for k, v in sorted(total.items())}


def _by_key(trace: tr.Trace, window: tr.Interval, keys) -> Dict[str, float]:
    """Device seconds of the leaf operations under each key that
    ``keys(op)`` gives, averaged over the devices."""
    lo, hi = window
    spans: Dict[str, List[tr.Interval]] = {}
    for ops in trace.devices.values():
        per: Dict[str, List[tr.Interval]] = {}
        for o in ops:
            if o.leaf and not o.asynchronous:
                for k in keys(o):
                    per.setdefault(k, []).append((o.start, o.end))
        for k, iv in per.items():
            spans.setdefault(k, []).append(tr.union(tr.clip(iv, lo, hi)))
    n = len(trace.devices)
    return {k: sum(map(tr.length, v)) / n * 1e-9
            for k, v in sorted(spans.items())}


def model_scope_s(trace: tr.Trace, window: tr.Interval) -> Dict[str, float]:
    """Device seconds under each ``model.*`` scope, averaged over the
    devices."""
    return _by_key(trace, window, lambda o: o.model_scopes)


def kernel_s(trace: tr.Trace, window: tr.Interval) -> Dict[str, float]:
    """Device seconds of each Pallas kernel by name, averaged over the
    devices."""
    def name(o):
        kind, _, ident = o.label.partition(":")
        return [ident] if kind in ("rolling_matmul", "pallas") else []
    return _by_key(trace, window, name)


def span_s(trace: tr.Trace, window: tr.Interval, name: str) -> float:
    """Host seconds inside the window in spans called ``name``."""
    lo, hi = window
    return tr.length(tr.union(tr.clip(
        [(s.start, s.end) for s in trace.spans if s.name == name], lo, hi))
    ) * 1e-9


def reduce(path: str) -> dict:
    """``bench.trace.reduce``'s fields, from the same events, and the
    device seconds by phase, by ``model.*`` scope and by Pallas kernel, and
    the host seconds in ``repro.round``; idle gaps take the innermost span
    of either kind."""
    trace = read(path)
    window = trace.window()
    coll, exposed = tr.collective_s(trace, window)
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": tr.busy_s(trace, window),
        "rolling_matmul_s": tr.matched_s(trace, window, tr.ROLLING_MATMUL),
        "collective_s": coll,
        "collective_exposed_s": exposed,
        "device_ops": tr.top_ops(trace, window),
        "idle_gaps": tr.idle_gaps(trace, window),
        "kernel_events": sum(1 for ops in trace.devices.values()
                             for o in tr._ops_in(ops, *window)
                             if tr.ROLLING_MATMUL.search(o.name)),
        "phase_s": phase_s(trace, window),
        "model_scope_s": model_scope_s(trace, window),
        "kernel_s": kernel_s(trace, window),
        "round_host_s": span_s(trace, window, ROUND_SPAN),
        "round_spans": sum(1 for s in trace.spans if s.name == ROUND_SPAN
                           and window[0] <= s.start < window[1]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m bench.scopes",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds in the window: adds milliseconds a round")
    args = ap.parse_args(argv)
    r = reduce(args.xplane)
    if args.rounds:
        per = {f"{k}_ms": 1e3 * v / args.rounds
               for group in ("phase_s", "model_scope_s", "kernel_s")
               for k, v in r[group].items()}
        per["round_host_ms"] = 1e3 * r["round_host_s"] / args.rounds
        per["busy_ms"] = 1e3 * r["busy_s"] / args.rounds
        r["per_round"] = per
    print(json.dumps(r, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
