"""Pallas TPU kernels (the analog of the reference's hand-fused CUDA kernels
in /root/reference/paddle/fluid/operators/fused/): flash attention, fused
layer_norm, fused softmax, paged attention, fused batch norm for given
statistics (normalize+activation+residual forward, one-pass
dx/dgamma/dbeta backward, local moments), the sum of a token's picks over
the rows an expert layer holds, the expert layer's grouped matrix products
at widths XLA's own grouped kernel would tile at 128.

Each kernel module exposes ``supported(...)`` gates so callers fall back to
plain XLA compositions on CPU/interpret mode or unaligned shapes.
"""
