"""collective_ms: device milliseconds per round in collective operations
(all-reduce, all-gather, ...), from the profiler trace, averaged over the
chips.  Layer: mesh aggregation."""


def read(ctx):
    t = ctx.trace
    if not t or not t["collective_s"] or not ctx.rounds:
        return None
    return 1e3 * t["collective_s"] / ctx.rounds
