"""expert_matmul_ms: device milliseconds per round of the routed experts'
matmuls, forward and backward: the leaf operations under the program's
``model.moe.experts`` scope (the expert products and the activation
between them), from the profiler trace, averaged over the chips.  Layer:
the model's expert layer (``models/moe.py``).  A program without the
scope reads nothing."""

SCOPE = "model.moe.experts"


def read(ctx):
    t = ctx.trace
    if not t or not t["model_scope_s"].get(SCOPE) or not ctx.rounds:
        return None
    return 1e3 * t["model_scope_s"][SCOPE] / ctx.rounds
