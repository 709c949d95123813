"""Device time of the prefill programs per 1000 prompt tokens admitted,
in the traced window."""
import readers


def read(ctx):
    tr, rec = ctx["trace"], readers.lm(ctx)
    if tr is None or rec is None:
        return None
    runs = tr.programs("prefill")
    tokens = sum(s.prefill_tokens for s in readers.window_steps(rec))
    if not runs or not tokens:
        return None
    return sum(r.dur for r in runs) / 1e6 / (tokens / 1000)
