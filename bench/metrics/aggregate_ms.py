"""aggregate_ms: device milliseconds per round under the program's
``fed.aggregate`` scope (client deltas to new parameters: window extract,
the cross-chip exchange, mean or scatter-add, ``w + lr * d``), from the
profiler trace, averaged over the chips.  Layer: fed round phases
(``core/fedavg.py``)."""


def read(ctx):
    t = ctx.trace
    if not t or not t["phase_s"].get("fed.aggregate") or not ctx.rounds:
        return None
    return 1e3 * t["phase_s"]["fed.aggregate"] / ctx.rounds
