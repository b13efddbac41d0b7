"""Pallas TPU kernel: fused INT8 conv-as-GEMM with NVDLA CONV->SDP epilogue.

The conv layout keeps NVDLA's feature-data orientation: weights (K, C*R*S)
times im2col'ed activations (C*R*S, P*Q) giving (K, P*Q) — output *channels on
the M axis*, so the SDP epilogue (int32 bias add, per-channel fixed-point
requant ``((acc >> pre) * m) >> post`` with round-half-away, optional ReLU,
int8 clip) broadcasts per *row*.  This is the transpose of
``kernels/int8_gemm`` (per-column epilogue) and saves the two P*Q-sized
transposes an adapter would need on the executor hot path.

Grid (M/bm, N/bn, K/bk), K innermost; the int32 accumulator tile lives in a
VMEM scratch that persists across the K loop (the CACC), and the epilogue runs
in the same kernel on the last K step — the accumulator never round-trips
through HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the SDP epilogue is plain jnp and shared with the executors' op closures —
# ONE copy of the requant semantics (see core/intmath.py)
from repro.core.intmath import row_epilogue as _row_epilogue


def _int8_conv_kernel(w_ref, x_ref, bias_ref, scale_ref, o_ref, acc_ref, *,
                      relu: bool, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        w_ref[...], x_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = _row_epilogue(acc_ref[...], bias_ref[...], scale_ref[...],
                                   relu)


def int8_conv_gemm(w: jax.Array, cols: jax.Array, bias: jax.Array,
                   scale_words: jax.Array, *, relu: bool = False,
                   block_m: int = 128, block_n: int = 128, block_k: int = 128,
                   interpret: bool = False,
                   name: str | None = None) -> jax.Array:
    """``clip8(requant((w @ cols) + bias[:,None]))`` — channels on rows.

    w: (M, K) int8 — weights, M = output channels
    cols: (K, N) int8 — im2col'ed activations, N = output positions P*Q
    bias: (M,) int32; scale_words: (M,) int32 packed (m,pre,post)
    Shapes must be multiples of the block sizes (ops.py pads).  The per-row
    vectors enter the kernel as (M, 1) columns: Mosaic tiles a 1-D operand
    differently from XLA's layout for it and refuses the kernel.  ``name``
    names the kernel, and so its custom call in the compiled program and
    its events in a profile.
    """
    m, k = w.shape
    k2, n = cols.shape
    assert k == k2 and bias.shape == (m,) and scale_words.shape == (m,)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_int8_conv_kernel, relu=relu, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        # int32 accumulator tile, persistent across the K loop (CACC analogue)
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
        name=name,
    )(w, cols, bias.reshape(m, 1), scale_words.reshape(m, 1))
