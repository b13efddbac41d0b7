"""Public fused-conv entry points: pad to MXU blocks, dispatch kernel/oracle.

These are the functions ``core/executor.py`` routes CONV/FC descriptors to
when ``perfmodel.select_kernel`` resolves ``pallas_fused``.  They are jit- and
vmap-compatible (the batched executor path vmaps them per lane), and
``interpret=True`` runs the very same kernel through the Pallas interpreter on
CPU — that is the path the parity tests exercise.

``conv2d_int8_batch`` / ``fc_int8_batch`` are the **natively batched**
variants: the whole coalesced bucket runs as ONE fused kernel launch with the
batch dimension folded onto the Pallas grid's N axis (each lane's im2col
columns stacked side by side), so the weight/bias/scale blocks stream from
HBM once per launch and are reused across every lane, instead of once per
vmapped single-image program.  Folding is bit-exact: GEMM columns are
independent, so stacking lanes along N changes neither any product nor any
column's accumulation order, and the fused CONV->SDP epilogue broadcasts per
*row* (output channel) — identical maths for every lane.

``name`` names the kernel launch after the layer it serves (the executor
passes ``d07_conv``), so the compiled program and a profile name it; a
grouped conv's launch for group ``g`` appends ``_g<g>``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.int8_conv.kernel import int8_conv_gemm
from repro.kernels.int8_conv.ref import (conv2d_int8_ref, fc_int8_ref,
                                         im2col)


def _group_name(name, g):
    """A grouped conv's kernel launch for group ``g``: ``<name>_g<g>``."""
    return None if name is None else f"{name}_g{g}"


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fused_gemm(wq, cols, bias, words, relu, block_m, block_n, block_k,
                interpret, name=None):
    """Pad operands to block multiples, run the fused kernel, unpad."""
    m, n = wq.shape[0], cols.shape[1]
    wp = _pad_to(_pad_to(wq, block_m, 0), block_k, 1)
    cp = _pad_to(_pad_to(cols, block_k, 0), block_n, 1)
    bp = _pad_to(bias, block_m, 0)
    sp = _pad_to(words, block_m, 0)
    out = int8_conv_gemm(wp, cp, bp, sp, relu=relu, block_m=block_m,
                         block_n=block_n, block_k=block_k, interpret=interpret,
                         name=name)
    return out[:m, :n]


def _fused_gemm_batch(wq, cols_b, bias, words, relu, block_m, block_n,
                      block_k, interpret, name=None):
    """One fused launch over a (B, K, N) column stack -> (B, M, N).

    Lanes fold onto the GEMM N axis (column index = lane * N + position), so
    the Pallas grid's j dimension walks every lane while the weight block
    index depends only on (i, k) — weights stream once per launch.  N-axis
    padding lands after the last lane's columns and is sliced off before the
    unfold.
    """
    b, k, n = cols_b.shape
    m = wq.shape[0]
    folded = jnp.moveaxis(cols_b, 0, 1).reshape(k, b * n)
    out = _fused_gemm(wq, folded, bias, words, relu, block_m, block_n,
                      block_k, interpret, name)
    return jnp.moveaxis(out.reshape(m, b, n), 0, 1)


def conv2d_int8(x: jax.Array, wq: jax.Array, bias: jax.Array,
                words: jax.Array, k: int, stride: int, pad: int,
                groups: int = 1, relu: bool = False, *,
                use_kernel: bool = True, block_m: int = 128,
                block_n: int = 128, block_k: int = 128,
                interpret: bool = False,
                name: str | None = None) -> jax.Array:
    """Fused CONV+SDP: (C,H,W) int8 -> (K,P,Q) int8, bit-exact vs refops.

    x (C,H,W) int8; wq (K, C/g*k*k) int8; bias/words (K,) int32.
    """
    if not use_kernel:
        return conv2d_int8_ref(x, wq, bias, words, k, stride, pad, groups, relu)
    kk = wq.shape[0]
    c, h, w_in = x.shape
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = im2col(x, k, stride, pad)
        out = _fused_gemm(wq, cols, bias, words, relu, block_m, block_n,
                          block_k, interpret, name)
        return out.reshape(kk, p, q)
    cg, kg = c // groups, kk // groups
    outs = []
    for g in range(groups):
        cols = im2col(x[g * cg:(g + 1) * cg], k, stride, pad)
        outs.append(_fused_gemm(wq[g * kg:(g + 1) * kg], cols,
                                bias[g * kg:(g + 1) * kg],
                                words[g * kg:(g + 1) * kg], relu,
                                block_m, block_n, block_k, interpret,
                                _group_name(name, g)))
    return jnp.concatenate(outs, 0).reshape(kk, p, q)


def fc_int8(x: jax.Array, wq: jax.Array, bias: jax.Array, words: jax.Array,
            relu: bool = False, *, use_kernel: bool = True,
            block_m: int = 128, block_n: int = 128, block_k: int = 128,
            interpret: bool = False, name: str | None = None) -> jax.Array:
    """Fused FC+SDP: flat int8 input, wq (K_out, Cin) -> (K_out,1,1) int8."""
    if not use_kernel:
        return fc_int8_ref(x, wq, bias, words, relu)
    cols = x.reshape(-1, 1)
    out = _fused_gemm(wq, cols, bias, words, relu, block_m, block_n, block_k,
                      interpret, name)
    return out.reshape(-1, 1, 1)


def conv2d_int8_batch(xs: jax.Array, wq: jax.Array, bias: jax.Array,
                      words: jax.Array, k: int, stride: int, pad: int,
                      groups: int = 1, relu: bool = False, *,
                      use_kernel: bool = True, block_m: int = 128,
                      block_n: int = 128, block_k: int = 128,
                      interpret: bool = False,
                      name: str | None = None) -> jax.Array:
    """Natively batched fused CONV+SDP: (B,C,H,W) int8 -> (B,K,P,Q) int8.

    ONE kernel launch serves the whole bucket — the batch rides the Pallas
    grid's N axis, so weights/bias/scale stream from HBM once and the fused
    epilogue + persistent VMEM accumulator are unchanged.  Bit-exact vs
    ``jax.vmap(conv2d_int8)`` over the lanes (column independence).
    """
    if not use_kernel:
        return jax.vmap(lambda x: conv2d_int8_ref(x, wq, bias, words, k,
                                                  stride, pad, groups,
                                                  relu))(xs)
    b, c, h, w_in = xs.shape
    kk = wq.shape[0]
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = jax.vmap(lambda x: im2col(x, k, stride, pad))(xs)
        out = _fused_gemm_batch(wq, cols, bias, words, relu, block_m,
                                block_n, block_k, interpret, name)
        return out.reshape(b, kk, p, q)
    cg, kg = c // groups, kk // groups
    outs = []
    for g in range(groups):
        cols = jax.vmap(
            lambda x: im2col(x[g * cg:(g + 1) * cg], k, stride, pad))(xs)
        outs.append(_fused_gemm_batch(wq[g * kg:(g + 1) * kg], cols,
                                      bias[g * kg:(g + 1) * kg],
                                      words[g * kg:(g + 1) * kg], relu,
                                      block_m, block_n, block_k, interpret,
                                      _group_name(name, g)))
    return jnp.concatenate(outs, 1).reshape(b, kk, p, q)


def fc_int8_batch(xs: jax.Array, wq: jax.Array, bias: jax.Array,
                  words: jax.Array, relu: bool = False, *,
                  use_kernel: bool = True, block_m: int = 128,
                  block_n: int = 128, block_k: int = 128,
                  interpret: bool = False,
                  name: str | None = None) -> jax.Array:
    """Natively batched fused FC+SDP: (B, Cin) int8 -> (B, K_out, 1, 1) int8.

    The bucket IS the GEMM N axis — the single-image path is a GEMV that
    re-streams the whole weight matrix per lane; here (K_out, Cin) streams
    once against a (Cin, B) activation block.
    """
    if not use_kernel:
        return jax.vmap(lambda x: fc_int8_ref(x, wq, bias, words, relu))(xs)
    b = xs.shape[0]
    cols = xs.reshape(b, -1).T
    out = _fused_gemm(wq, cols, bias, words, relu, block_m, block_n, block_k,
                      interpret, name)
    return out.T.reshape(b, -1, 1, 1)
