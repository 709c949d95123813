"""Host time of an admission: the ``loom.engine.prefill`` (batch-1
cache, prefill, first-token read) and ``loom.engine.scatter`` (copy into
the pool) spans of the traced window, summed, per prefill span (one per
admitted request)."""
import program_spans


def read(ctx):
    pre = program_spans.in_window(ctx, "loom.engine.prefill")
    if not pre:
        return None
    scatter = program_spans.in_window(ctx, "loom.engine.scatter")
    return sum(s.dur for s in pre + scatter) / len(pre) / 1e6
