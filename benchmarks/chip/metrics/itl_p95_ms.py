"""95th percentile of every gap between consecutive tokens of every
request, both tokens inside the window."""
import readers


def read(ctx):
    rec = readers.lm(ctx)
    if rec is None:
        return None
    w0, w1 = rec["t_w0"], rec["t_w1"]
    gaps = [(b - a) * 1e3 for r in rec["reqs"]
            for a, b in zip(r.times, r.times[1:]) if w0 <= a and b < w1]
    return readers.pct(gaps, 95)
