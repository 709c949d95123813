"""Share of the roofline of the MLP's bit-serial matmul kernels (gate,
up, down) in decode; see ``linear_classes.py``."""
import linear_classes


def read(ctx):
    return linear_classes.decode_roofline(ctx, "mlp")
