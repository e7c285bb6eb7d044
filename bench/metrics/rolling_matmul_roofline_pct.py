"""rolling_matmul_roofline_pct: least time over kernel time for the
rolling-matmul calls of the window.  The least time
(``bench.flops.rolling_matmul_least_s``) is, per call, the larger of its
FLOPs at the bf16 peak and its bytes at the HBM bandwidth; the calls are the
q/k/v projections and the gate/up pair, forward (twice, under remat) and
dx.  Kernel time from the profiler trace, per chip."""
from bench.flops import rolling_matmul_least_s


def read(ctx):
    t = ctx.trace
    if not t or not t["rolling_matmul_s"] or not ctx.rounds:
        return None
    per_chip = ctx.cell.mix["clients"] // ctx.cell.chips
    mix = {**ctx.cell.mix, "clients": per_chip}
    least = rolling_matmul_least_s(ctx.cell.config, mix,
                                   ctx.peaks["bf16_flops_per_s"],
                                   ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.rounds / t["rolling_matmul_s"]
