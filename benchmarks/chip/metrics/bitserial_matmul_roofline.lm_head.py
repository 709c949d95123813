"""Share of the roofline of the lm_head's bit-serial matmul kernel in
decode; see ``linear_classes.py``."""
import linear_classes


def read(ctx):
    return linear_classes.decode_roofline(ctx, "lm_head")
