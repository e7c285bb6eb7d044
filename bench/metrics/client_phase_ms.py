"""client_phase_ms: device milliseconds per round under the program's
``fed.client_phase`` scope (every client's K local steps, up to its
delta), from the profiler trace, averaged over the chips.  Layer: fed
round phases (``core/fedavg.py``)."""


def read(ctx):
    t = ctx.trace
    if not t or not t["phase_s"].get("fed.client_phase") or not ctx.rounds:
        return None
    return 1e3 * t["phase_s"]["fed.client_phase"] / ctx.rounds
