"""Median, over the requests due in the window that were admitted, of
(start of the engine step that took the request out of the queue) -
(its scheduled arrival)."""
import readers


def read(ctx):
    rec = readers.lm(ctx)
    if rec is None:
        return None
    return readers.pct([(r.admitted - r.due) * 1e3
                        for r in readers.window_requests(rec)
                        if r.admitted is not None], 50)
