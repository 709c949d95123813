"""Whole classification against the chip's int8 peak: dense-equivalent
operations of the images classified in the traced window (from
work.py), over (traced window x int8 peak)."""
import readers
import work


def read(ctx):
    tr, rec = ctx["trace"], readers.cnn(ctx)
    if tr is None or rec is None:
        return None
    n = len(tr.host_spans("bench.classify"))
    ops = work.classify(rec["config"], rec["batch"])[0] * n
    return readers.pct_of(ops, tr.window_s * ctx["peaks"]["int8_ops"])
