"""Pallas TPU kernel: fused BF16 conv-as-GEMM with the nv_full SDP epilogue.

Layout matches ``kernels/int8_conv``: weights (K, C*R*S) times im2col'ed
activations (C*R*S, P*Q) giving (K, P*Q) — output *channels on the M axis*,
so the epilogue (f32 bias add, optional ReLU) broadcasts per row.

Grid (M/bm, N/bn, K/bk), K innermost; the float32 accumulator tile lives in a
VMEM scratch that persists across the K loop (the CACC), and the epilogue runs
in the same kernel on the last K step — the f32 accumulator never round-trips
through HBM, and only the final bf16 tile is written out.  bf16 x bf16
products are exact in f32 (8+8 significand bits < 24), so the only
implementation freedom is f32 summation order — which is what the tolerance
model in ``core/tolerances.py`` budgets for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bf16_conv_kernel(w_ref, x_ref, bias_ref, o_ref, acc_ref, *,
                      relu: bool, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # bf16 x bf16 -> f32: exact products, f32 accumulation on the MXU
    acc_ref[...] += jax.lax.dot_general(
        w_ref[...], x_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        acc = acc_ref[...] + bias_ref[...]
        if relu:
            acc = jnp.maximum(acc, 0.0)
        o_ref[...] = acc.astype(jnp.bfloat16)


def bf16_conv_gemm(w: jax.Array, cols: jax.Array, bias: jax.Array, *,
                   relu: bool = False, block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: bool = False,
                   name: str | None = None) -> jax.Array:
    """``bf16((w @ cols) + bias[:,None])`` with f32 accumulate — channels on rows.

    w: (M, K) bfloat16 — weights, M = output channels
    cols: (K, N) bfloat16 — im2col'ed activations, N = output positions P*Q
    bias: (M,) float32, entering the kernel as an (M, 1) column (see
    ``int8_conv_gemm``)
    Shapes must be multiples of the block sizes (ops.py pads).  ``name``
    names the kernel, and so its custom call in the compiled program and
    its events in a profile.
    """
    m, k = w.shape
    k2, n = cols.shape
    assert k == k2 and bias.shape == (m,)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_bf16_conv_kernel, relu=relu, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.bfloat16),
        # f32 accumulator tile, persistent across the K loop (CACC analogue)
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
        name=name,
    )(w, cols, bias.reshape(m, 1))
