"""Pallas TPU kernels + XLA refs. Every kernel takes ``interpret``
without a default: True validates on CPU, False compiles with Mosaic.

    bitserial_matmul   the SIP array: packed-plane serial matmul (+dynamic)
    bitserial_conv     FUSED bit-serial convolution on an Ho-banded grid:
                       implicit im2col via window-offset slices of the
                       band in VMEM (no HBM patch tensor), all Pw packed
                       planes staged per grid step and the serial plane
                       loop unrolled in the kernel body — the paper's CVL
                       execution path end-to-end; band size from the
                       plan's VMEM-budget heuristic
    dynamic_quant      per-group quantize + leading-one precision detect
    flash_attention    chunked online-softmax attention (32k prefill)
    ops                jit'd dispatch wrappers (Pallas on TPU, XLA oracle
                       off-TPU; conv's XLA path is k*k shift-and-matmul
                       passes — also patch-buffer-free)
    ref                pure-jnp oracles, the specification for every kernel

Conv weights share the linear layout: a [k*k*Cin, Cout] matrix in
(di, dj, c) row order, bit-packed by core.bitpack to
[Pw, ceil(k*k*Cin/8), Cout] (K rows zero-padded to a byte multiple).
"""
