"""Images classified in the window over the window's seconds. The
window closes once every call dispatched in it has ended."""
import readers


def read(ctx):
    rec = readers.cnn(ctx)
    if rec is None:
        return None
    return rec["calls"] * rec["batch"] / rec["window_s"]
