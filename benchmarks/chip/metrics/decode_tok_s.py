"""Output tokens emitted in the window over the window's seconds (every
request, first tokens included)."""
import readers


def read(ctx):
    rec = readers.lm(ctx)
    if rec is None:
        return None
    n = sum(1 for r in rec["reqs"] for t in r.times
            if rec["t_w0"] <= t < rec["t_w1"])
    return n / rec["window_s"]
