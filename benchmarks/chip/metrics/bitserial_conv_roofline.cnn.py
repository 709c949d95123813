"""Share of the roofline of the fused bit-serial conv kernels: the least
time of every conv of a classify call (each at its own bound, from
work.py) times the calls of the traced window (one ``bench.classify``
span each), divided by the device time of the ``bitserial_conv`` kernels
in that window."""
import readers
import work


def read(ctx):
    tr, rec = ctx["trace"], readers.cnn(ctx)
    if tr is None or rec is None:
        return None
    spans = tr.host_spans("bench.classify")
    kernel_s = sum(op.dur for op in tr.ops_in(
        tr.host_spans("bench.window"), r"^bitserial_conv"))
    if not kernel_s or not spans:
        return None
    least = sum(work.least_time(*work.conv(rec["batch"], *layer[1:]),
                                ctx["peaks"])
                for layer in work.cnn_layers(rec["config"])
                if layer[0] == "conv")
    return readers.pct_of(least * len(spans), kernel_s / 1e9)
