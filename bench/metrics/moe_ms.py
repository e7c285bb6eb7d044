"""moe_ms: device milliseconds per round of the program's expert layer:
the leaf operations under its ``model.moe`` scope (routing, dispatch, the
routed and the shared experts), from the profiler trace, averaged over
the chips.  Layer: the model's expert layer (``models/moe.py``).  A
program without the scope reads nothing."""

SCOPE = "model.moe"


def read(ctx):
    t = ctx.trace
    if not t or not t["model_scope_s"].get(SCOPE) or not ctx.rounds:
        return None
    return 1e3 * t["model_scope_s"][SCOPE] / ctx.rounds
