"""The whole decode step against the chip's int8 peak: dense-equivalent
operations of the decode steps of the traced window (every linear over
the active rows and the attention over valid positions, from work.py
and the configuration's reference module), over (device time of the
decode programs x int8 peak). None where the reference states no decode
work."""
import readers
import work


def read(ctx):
    tr, rec = ctx["trace"], readers.lm(ctx)
    if tr is None or rec is None:
        return None
    device_s = sum(r.dur for r in tr.programs("decode")) / 1e9
    if work.lm_decode(rec["config"]) is None:
        return None
    ops = sum(work.decode_step(rec["config"], s.contexts)[0]
              for s in readers.window_steps(rec) if s.contexts)
    if not device_s or not ops:
        return None
    return readers.pct_of(ops, device_s * ctx["peaks"]["int8_ops"])
