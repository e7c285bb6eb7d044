"""host_feed_ms: host milliseconds per round spent making and handing over
the round's batch (the benchmark's ``bench.feed`` span, by the host clock).
Layer: the host loop that drives ``api.Trainer``."""


def read(ctx):
    if not ctx.rounds:
        return None
    return 1e3 * ctx.feed_s / ctx.rounds
