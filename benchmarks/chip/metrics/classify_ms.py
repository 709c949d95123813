"""Device time per classify call: the programs run inside each
``bench.classify`` span of the traced window."""

def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.host_spans("bench.classify")
    if not spans:
        return None
    busy = sum(tr.device_busy_in(s.start, s.end) for s in spans)
    return busy / len(spans) * 1e3
