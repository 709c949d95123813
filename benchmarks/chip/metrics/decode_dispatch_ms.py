"""Mean host time of a batched decode's dispatch: each
``loom.engine.decode`` span of the traced window (the host inputs and
the decode program's launch, before the host waits on it)."""
import program_spans


def read(ctx):
    return program_spans.mean_ms(
        program_spans.in_window(ctx, "loom.engine.decode"))
