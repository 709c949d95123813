"""Device time of the decode program per run, in the traced window."""

def read(ctx):
    tr = ctx["trace"]
    runs = tr.programs("decode") if tr is not None else []
    if not runs:
        return None
    return sum(r.dur for r in runs) / len(runs) / 1e6
