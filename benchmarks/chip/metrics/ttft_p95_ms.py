"""95th percentile over every request due in the window of (end of the
step that emitted its first token) - (its scheduled arrival). A request
that never got a first token counts as infinite."""
import readers


def read(ctx):
    rec = readers.lm(ctx)
    if rec is None:
        return None
    return readers.pct([(r.times[0] - r.due) * 1e3 if r.times
                        else float("inf")
                        for r in readers.window_requests(rec)], 95)
