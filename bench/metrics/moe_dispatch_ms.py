"""moe_dispatch_ms: device milliseconds per round of the leaf operations
under the program's ``model.moe.dispatch`` scope (routing and top-k, the
balance loss, the dense path's weighted combine), from the profiler trace,
averaged over the chips.  Layer: the model's expert layer
(``models/moe.py``).  A program without the scope reads nothing."""

SCOPE = "model.moe.dispatch"


def read(ctx):
    t = ctx.trace
    if not t or not t["model_scope_s"].get(SCOPE) or not ctx.rounds:
        return None
    return 1e3 * t["model_scope_s"][SCOPE] / ctx.rounds
