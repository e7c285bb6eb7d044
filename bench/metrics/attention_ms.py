"""attention_ms: device milliseconds per round of the leaf operations
under the program's ``model.attention`` scope (scores, softmax and the
weighted values; not the q/k/v/o projections), from the profiler trace,
averaged over the chips.  Layer: the model's attention core
(``models/attention.py``)."""


def read(ctx):
    t = ctx.trace
    if not t or not t["model_scope_s"].get("model.attention") \
            or not ctx.rounds:
        return None
    return 1e3 * t["model_scope_s"]["model.attention"] / ctx.rounds
