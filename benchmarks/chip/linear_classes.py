"""Decode linears by class, from the names the program gives their
kernels.

The program names each bit-serial matmul kernel after its layer,
``bitserial_matmul_<layer>`` (``bitserial_matmul_attn_q.12`` in the
trace), with the layer names of the reference module's
``decode_linears``. A class is the layers whose names share a prefix.
Its share of the roofline is the least time of its linears (``work.py``,
each at its own bound, active rows only) over the window's decode steps,
divided by the device time of its kernels inside the decode programs of
the traced window; the classes together are
``bitserial_matmul_roofline.decode`` split up. A trace whose kernels
carry no layer names gives None, and so does a configuration whose
reference states no decode work.
"""
from __future__ import annotations

import readers

CLASSES = {"attn": "attn_", "mlp": "ffn_", "lm_head": "lm_head"}


def least_s(rec, pk, prefix: str) -> float | None:
    """``readers.decode_least_s`` over the linears named ``prefix*``."""
    return readers.decode_least_s(rec, pk, prefix)


def kernel_s(tr, prefix: str) -> float:
    """Device seconds of the ``bitserial_matmul_<prefix>*`` kernels in
    the decode programs."""
    ops = tr.ops_in(tr.programs("decode"), rf"^bitserial_matmul_{prefix}")
    return sum(op.dur for op in ops) / 1e9


def decode_roofline(ctx, cls: str) -> float | None:
    tr, rec = ctx["trace"], readers.lm(ctx)
    if tr is None or rec is None:
        return None
    prefix = CLASSES[cls]
    spent = kernel_s(tr, prefix)
    if not spent:
        return None
    least = least_s(rec, ctx["peaks"], prefix)
    return None if least is None else readers.pct_of(least, spent)
