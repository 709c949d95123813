"""Images classified in the window (each call ends in
block_until_ready) over the window's seconds."""
import readers


def read(ctx):
    rec = readers.cnn(ctx)
    if rec is None:
        return None
    return len(rec["calls"]) * rec["batch"] / rec["window_s"]
