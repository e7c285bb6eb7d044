"""rolling_matmul_ms: device milliseconds per round in the Pallas kernels of
``kernels/rolling_matmul*.py`` (forward, multi, dx and their batched
forms), from the profiler trace, averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["rolling_matmul_s"] or not ctx.rounds:
        return None
    return 1e3 * t["rolling_matmul_s"] / ctx.rounds
