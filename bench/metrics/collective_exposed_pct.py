"""collective_exposed_pct: share of the collective time during which no
other operation ran on that device, from the profiler trace.  Layer: mesh
aggregation."""


def read(ctx):
    t = ctx.trace
    if not t or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["collective_s"]
