"""Share of the roofline of the bit-serial matmul kernels in decode: the
least time of every decode linear (each at its own bound, active rows
only, from work.py) over the window's decode steps, divided by the
device time of the ``bitserial_matmul`` kernels inside the decode
programs of the traced window. None where the configuration's reference
states no decode work."""
import readers


def read(ctx):
    tr, rec = ctx["trace"], readers.lm(ctx)
    if tr is None or rec is None:
        return None
    runs = tr.programs("decode")
    kernel_s = sum(op.dur for op in tr.ops_in(runs, r"^bitserial_matmul"))
    least = readers.decode_least_s(rec, ctx["peaks"])
    if not kernel_s or least is None:
        return None
    return readers.pct_of(least, kernel_s / 1e9)
