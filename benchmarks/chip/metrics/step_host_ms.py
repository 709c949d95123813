"""Mean host time of an engine step: each ``bench.engine_step`` span in
the window less the device's busy time inside it."""

def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.host_spans("bench.engine_step")
    if not spans:
        return None
    host = sum(s.dur / 1e9 - tr.device_busy_in(s.start, s.end)
               for s in spans)
    return host / len(spans) * 1e3
