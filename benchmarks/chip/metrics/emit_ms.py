"""Mean host time of a step's emit phase: each ``loom.engine.emit``
span of the traced window (the slot loop that streams the step's
tokens, retirements, and the serving gauges)."""
import program_spans


def read(ctx):
    return program_spans.mean_ms(
        program_spans.in_window(ctx, "loom.engine.emit"))
