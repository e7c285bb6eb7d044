"""round_host_ms: host milliseconds per round inside the program's
``repro.round`` span (``api.Trainer.step``: the RNG split, the batch to
the device, the jitted call's dispatch), from the host plane of the
profiler trace.  Layer: the host loop that drives ``api.Trainer``."""


def read(ctx):
    t = ctx.trace
    if not t or not t["round_host_s"] or not ctx.rounds:
        return None
    return 1e3 * t["round_host_s"] / ctx.rounds
