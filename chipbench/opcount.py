"""Operations and bytes that a CONV or FC layer needs, from its shapes.

What the algorithm needs, not what an implementation moves: neither an
im2col expansion nor padding to a tile counts, so a roofline share built on
these numbers reads the same work whatever computes it.

  ops    2 * b * K * (C/g) * R * S * P * Q      (a multiply and an add)
  bytes  the weights once, b times the input and output activations, and
         the per-channel words: int32 bias and int32 scale word for int8,
         float32 bias for bf16
"""

from __future__ import annotations

import numpy as np

import reference

ELEM_BYTES = {"int8": 1, "bf16": 2}
CHANNEL_WORD_BYTES = {"int8": 8, "bf16": 4}


def layer_costs(arch: dict, input_shape, dtype: str, batch: int) -> list:
    """``[(name, ops, bytes)]`` of every CONV/FC layer at ``batch`` images."""
    layers = reference.build(arch)
    eb = ELEM_BYTES[dtype]
    out = []
    for name, k, cin_g, r, s, cin, cout, _ in reference.gemm_layers(
            layers, reference.shapes(layers, input_shape)):
        positions = cout[1] * cout[2]
        ops = 2 * batch * k * cin_g * r * s * positions
        nbytes = (k * cin_g * r * s * eb
                  + batch * (int(np.prod(cin)) + int(np.prod(cout))) * eb
                  + k * CHANNEL_WORD_BYTES[dtype])
        out.append((name, ops, nbytes))
    return out


def ops_per_image(arch: dict, input_shape) -> int:
    return sum(ops for _, ops, _ in layer_costs(arch, input_shape, "int8", 1))


def ideal_seconds(arch: dict, input_shape, dtype: str, batch: int,
                  peak: dict) -> tuple:
    """``(seconds, compute-bound layers, memory-bound layers)`` of the
    roofline floor of one launch: per layer the larger of its ops over the
    dtype's peak and its bytes over HBM bandwidth, summed."""
    total, n_compute, n_memory = 0.0, 0, 0
    for _, ops, nbytes in layer_costs(arch, input_shape, dtype, batch):
        t_c = ops / peak["ops_per_s"][dtype]
        t_m = nbytes / peak["hbm_bytes_per_s"]
        total += max(t_c, t_m)
        n_compute += t_c >= t_m
        n_memory += t_c < t_m
    return total, n_compute, n_memory
