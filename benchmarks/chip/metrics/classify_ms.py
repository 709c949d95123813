"""Device time per classify call: the device's busy time in the traced
window over the calls dispatched in it (one ``bench.classify`` span
each), every one of which ends inside the window."""

def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    spans = tr.host_spans("bench.classify")
    if not spans:
        return None
    return tr.busy_s / len(spans) * 1e3
