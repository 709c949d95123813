"""Set-up: process start to the start of the window (weights, packing,
compilation or cache loads, warm-up, and an LM cell's pre-roll)."""


def read(ctx):
    return ctx["rec"]["setup_s"]
