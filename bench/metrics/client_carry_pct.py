"""client_carry_pct: bytes of the parameter tree one client carries
through its K local steps, as a share of the whole tree's, summed over
the client phases the program traced (``repro.tracing.CLIENT_CARRY``,
recorded when the round is traced at set-up).  100 when a client carries
the whole tree (no window); a program that keeps no such record reads
nothing.  Layer: fed round phases (``core/fedavg.py``)."""


def read(ctx):
    from repro import tracing
    carry = getattr(tracing, "CLIENT_CARRY", None)
    if not carry or not carry.get("whole"):
        return None
    return 100.0 * carry["carried"] / carry["whole"]
