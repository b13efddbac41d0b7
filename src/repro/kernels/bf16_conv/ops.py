"""Public fused bf16-conv entry points: pad to MXU blocks, dispatch kernel.

These are the functions ``core/executor.py`` routes CONV/FC descriptors to
when ``perfmodel.select_kernel`` resolves ``pallas_bf16_fused`` on an nv_full
artifact.  They are jit- and vmap-compatible (the batched executor path vmaps
them per lane), and ``interpret=True`` runs the very same kernel through the
Pallas interpreter on CPU — the path the tolerance-parity tests exercise.

Zero padding is epilogue-safe here for the same reason it is in the int8
family: padded K contributes exact 0.0 products to the f32 accumulator, and
padded M/N rows/columns are sliced off before the caller sees them.

``conv2d_bf16_batch`` / ``fc_bf16_batch`` are the natively batched variants:
the coalesced bucket runs as ONE fused launch with the lanes folded onto the
Pallas grid's N axis, so bf16 weights and f32 bias stream from HBM once per
launch.  Folding preserves each column's f32 accumulation order, so the
batched kernel is *bit-identical* to vmapping the single-image kernel over
lanes (the tolerance bound is only needed vs the differently-ordered refops).

``name`` names the kernel launch after the layer it serves (the executor
passes ``d07_conv``), so the compiled program and a profile name it; a
grouped conv's launch for group ``g`` appends ``_g<g>``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.intmath import im2col
from repro.kernels.bf16_conv.kernel import bf16_conv_gemm
from repro.kernels.bf16_conv.ref import conv2d_bf16_ref, fc_bf16_ref


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


def _fused_gemm(wq, cols, bias, relu, block_m, block_n, block_k, interpret,
                name=None):
    """Pad operands to block multiples, run the fused kernel, unpad."""
    m, n = wq.shape[0], cols.shape[1]
    wp = _pad_to(_pad_to(wq, block_m, 0), block_k, 1)
    cp = _pad_to(_pad_to(cols, block_k, 0), block_n, 1)
    bp = _pad_to(bias, block_m, 0)
    out = bf16_conv_gemm(wp, cp, bp, relu=relu, block_m=block_m,
                         block_n=block_n, block_k=block_k, interpret=interpret,
                         name=name)
    return out[:m, :n]


def _fused_gemm_batch(wq, cols_b, bias, relu, block_m, block_n, block_k,
                      interpret, name=None):
    """One fused launch over a (B, K, N) column stack -> (B, M, N); lanes
    fold onto the GEMM N axis so the weight blocks stream once per launch."""
    b, k, n = cols_b.shape
    m = wq.shape[0]
    folded = jnp.moveaxis(cols_b, 0, 1).reshape(k, b * n)
    out = _fused_gemm(wq, folded, bias, relu, block_m, block_n, block_k,
                      interpret, name)
    return jnp.moveaxis(out.reshape(m, b, n), 0, 1)


def conv2d_bf16(x: jax.Array, wq: jax.Array, bias: jax.Array, k: int,
                stride: int, pad: int, groups: int = 1, relu: bool = False, *,
                use_kernel: bool = True, block_m: int = 128,
                block_n: int = 128, block_k: int = 128,
                interpret: bool = False,
                name: str | None = None) -> jax.Array:
    """Fused CONV+SDP: (C,H,W) bf16 -> (K,P,Q) bf16, f32 accumulate.

    x (C,H,W) bfloat16; wq (K, C/g*k*k) bfloat16; bias (K,) float32.
    """
    if not use_kernel:
        return conv2d_bf16_ref(x, wq, bias, k, stride, pad, groups, relu)
    kk = wq.shape[0]
    c, h, w_in = x.shape
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = im2col(x, k, stride, pad)
        out = _fused_gemm(wq, cols, bias, relu, block_m, block_n, block_k,
                          interpret, name)
        return out.reshape(kk, p, q)
    cg, kg = c // groups, kk // groups
    outs = []
    for g in range(groups):
        cols = im2col(x[g * cg:(g + 1) * cg], k, stride, pad)
        outs.append(_fused_gemm(wq[g * kg:(g + 1) * kg], cols,
                                bias[g * kg:(g + 1) * kg], relu,
                                block_m, block_n, block_k, interpret,
                                _group_name(name, g)))
    return jnp.concatenate(outs, 0).reshape(kk, p, q)


def fc_bf16(x: jax.Array, wq: jax.Array, bias: jax.Array,
            relu: bool = False, *, use_kernel: bool = True,
            block_m: int = 128, block_n: int = 128, block_k: int = 128,
            interpret: bool = False, name: str | None = None) -> jax.Array:
    """Fused FC+SDP: flat bf16 input, wq (K_out, Cin) -> (K_out,1,1) bf16."""
    if not use_kernel:
        return fc_bf16_ref(x, wq, bias, relu)
    cols = x.reshape(-1, 1)
    out = _fused_gemm(wq, cols, bias, relu, block_m, block_n, block_k,
                      interpret, name)
    return out.reshape(-1, 1, 1)


def conv2d_bf16_batch(xs: jax.Array, wq: jax.Array, bias: jax.Array, k: int,
                      stride: int, pad: int, groups: int = 1,
                      relu: bool = False, *, use_kernel: bool = True,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, interpret: bool = False,
                      name: str | None = None) -> jax.Array:
    """Natively batched fused CONV+SDP: (B,C,H,W) bf16 -> (B,K,P,Q) bf16.

    ONE kernel launch serves the whole bucket — the batch rides the Pallas
    grid's N axis, bf16 weights and f32 bias stream from HBM once, and the
    fused epilogue + persistent f32 VMEM accumulator are unchanged.
    Bit-identical to ``jax.vmap(conv2d_bf16)`` over the lanes.
    """
    if not use_kernel:
        return jax.vmap(lambda x: conv2d_bf16_ref(x, wq, bias, k, stride,
                                                  pad, groups, relu))(xs)
    b, c, h, w_in = xs.shape
    kk = wq.shape[0]
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = jax.vmap(lambda x: im2col(x, k, stride, pad))(xs)
        out = _fused_gemm_batch(wq, cols, bias, relu, block_m, block_n,
                                block_k, interpret, name)
        return out.reshape(b, kk, p, q)
    cg, kg = c // groups, kk // groups
    outs = []
    for g in range(groups):
        cols = jax.vmap(
            lambda x: im2col(x[g * cg:(g + 1) * cg], k, stride, pad))(xs)
        outs.append(_fused_gemm_batch(wq[g * kg:(g + 1) * kg], cols,
                                      bias[g * kg:(g + 1) * kg], relu,
                                      block_m, block_n, block_k, interpret,
                                      _group_name(name, g)))
    return jnp.concatenate(outs, 1).reshape(b, kk, p, q)


def fc_bf16_batch(xs: jax.Array, wq: jax.Array, bias: jax.Array,
                  relu: bool = False, *, use_kernel: bool = True,
                  block_m: int = 128, block_n: int = 128, block_k: int = 128,
                  interpret: bool = False,
                  name: str | None = None) -> jax.Array:
    """Natively batched fused FC+SDP: (B, Cin) bf16 -> (B, K_out, 1, 1) bf16.

    The bucket IS the GEMM N axis: (K_out, Cin) weights stream once against
    a (Cin, B) activation block instead of once per GEMV lane.
    """
    if not use_kernel:
        return jax.vmap(lambda x: fc_bf16_ref(x, wq, bias, relu))(xs)
    b = xs.shape[0]
    cols = xs.reshape(b, -1).T
    out = _fused_gemm(wq, cols, bias, relu, block_m, block_n, block_k,
                      interpret, name)
    return out.T.reshape(b, -1, 1, 1)
