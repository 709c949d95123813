"""Share of the roofline of the attention projections' bit-serial
matmul kernels (q, k, v, o) in decode; see ``linear_classes.py``."""
import linear_classes


def read(ctx):
    return linear_classes.decode_roofline(ctx, "attn")
