"""Executors: bare-metal (the paper's contribution) vs linux-stack (the baseline).

``BareMetalExecutor`` consumes ONLY the two bare-metal artifacts — the configuration
file (trace) and the extracted weight image — exactly like the paper's µRISC-V
binary.  It decodes the register stream back into engine descriptors and binds the
*entire* network into one jitted XLA program: one binary, zero per-layer
dispatch.  The DRAM arena's static addressing decides, when the program is
built, which producer each read sees; on the device, weights are resident
arrays and activations flow as values.  This is the TPU-native analogue of
replaying stores from bare-metal assembly.

``LinuxStackExecutor`` models the driver-stack deployments the paper compares
against ([5]-[12]): one executable per layer, a driver-managed tensor table
(dict keyed by DRAM address), per-op submission from the host — i.e. real,
measured software overhead on the same op semantics (no simulated sleeps).

Both executors implement BOTH engine datapaths, dispatched on
``EngineConfig.dtype``:

  * ``int8`` (nv_small) — integer ops, bit-identical to the VP functional
    model; tests assert byte equality.
  * ``bf16`` (nv_full)  — bfloat16 weights/activations at 2 bytes/element in
    the same flat arena, float32 accumulation, f32 bias, no requantisation.
    bf16 products are exact in f32, so the only implementation freedom is f32
    summation order — parity against the VP is therefore *tolerance-bounded*
    (``core/tolerances.py``), never bit-asserted.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import zlib
from typing import Dict, List, Optional, Protocol, Sequence, Union, runtime_checkable

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import engine, intmath, perfmodel, quant
from repro.core.tracegen import Trace
from repro.kernels import bf16_conv, int8_conv
from repro.obs.trace import launch_phases


# ---------------------------------------------------------------------------
# jnp twins of the integer engine semantics (bit-exact vs core/refops.py) —
# one shared copy in core/intmath.py, also used by the Pallas kernel family
# ---------------------------------------------------------------------------
_apply_scale = intmath.apply_scale
_clip8 = intmath.clip8


def _dot_i8_f32(a, b, dnums):
    """One exact f32 GEMM tile -> int32 (caller guarantees K <= EXACT_K)."""
    # Precision.HIGHEST forces true f32 accumulation — the default matmul
    # precision is tf32/bf16 on GPU/TPU, which would break the exactness
    # proof (products need 15 significand bits).
    acc = jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                              dnums, preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)
    return acc.astype(jnp.int32)


def _dot_i8(a, b, dnums, contract_k: int,
            kernel: str = perfmodel.KERNEL_GEMM_TILED):
    """int8 x int8 -> int32 dot_general on the wide f32 units, exact for ANY K.

    XLA CPU lowers integer GEMMs to scalar loops; the f32 units are far wider.
    Every int8*int8 product has magnitude <= 128*128 = 16384 (both operands
    can be -128), so while the worst-case partial sum K * 16384 stays within
    2^24 every partial sum is an exactly representable f32 integer regardless
    of summation order — the float GEMM returns bit-identical int32
    accumulators.  For K > EXACT_K (= 1024) the contraction is split into
    K-tiles that each satisfy the bound; each tile's f32 accumulator converts
    to int32 exactly and the tiles are summed in int32, which cannot overflow
    (the true accumulator already fits int32 by the engine's design).  The
    scalar integer ``dot_general`` path no longer exists.
    """
    if contract_k <= perfmodel.EXACT_K:
        return _dot_i8_f32(a, b, dnums)
    if kernel == perfmodel.KERNEL_GEMM_EXACT:
        raise ValueError(f"gemm_f32_exact forced for K={contract_k} > "
                         f"{perfmodel.EXACT_K}: not bit-exact")
    (ca,), (cb,) = dnums[0]
    acc = None
    for lo in range(0, contract_k, perfmodel.EXACT_K):
        hi = min(lo + perfmodel.EXACT_K, contract_k)
        part = _dot_i8_f32(jax.lax.slice_in_dim(a, lo, hi, axis=ca),
                           jax.lax.slice_in_dim(b, lo, hi, axis=cb), dnums)
        acc = part if acc is None else acc + part
    return acc


def _pallas_interpret() -> bool:
    """The one place Pallas interpret mode is decided: the fused kernels run
    compiled on a TPU and through the Pallas interpreter anywhere else (the
    CPU tests).  The kernels themselves default to compiled."""
    return jax.default_backend() != "tpu"


_im2col = intmath.im2col


def _conv_int8(x, wq, bias, words, k, stride, pad, groups, relu,
               kernel: str = perfmodel.KERNEL_GEMM_TILED,
               name: Optional[str] = None):
    if kernel == perfmodel.KERNEL_PALLAS:
        # whole CONV->SDP pipeline fused in the Pallas kernel (epilogue
        # included) — the int32 accumulator never leaves VMEM
        return int8_conv.conv2d_int8(x, wq, bias, words, k, stride, pad,
                                     groups, relu,
                                     interpret=_pallas_interpret(), name=name)
    kk = wq.shape[0]
    c, h, w_in = x.shape
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = _im2col(x, k, stride, pad)
        acc = _dot_i8(wq, cols, (((1,), (0,)), ((), ())), c * k * k, kernel)
    else:
        cg, kg = c // groups, kk // groups
        xg = x.reshape(groups, cg, h, w_in)
        colsg = jax.vmap(lambda xx: _im2col(xx, k, stride, pad))(xg)
        wg = wq.reshape(groups, kg, cg * k * k)
        acc = _dot_i8(wg, colsg, (((2,), (1,)), ((0,), (0,))), cg * k * k,
                      kernel)
        acc = acc.reshape(kk, p * q)
    return intmath.row_epilogue(acc, bias, words, relu).reshape(kk, p, q)


def _fc_int8(x, wq, bias, words, relu,
             kernel: str = perfmodel.KERNEL_GEMM_TILED,
             name: Optional[str] = None):
    if kernel == perfmodel.KERNEL_PALLAS:
        return int8_conv.fc_int8(x.reshape(-1), wq, bias, words, relu,
                                 interpret=_pallas_interpret(),
                                 name=name)
    acc = _dot_i8(wq, x.reshape(-1, 1), (((1,), (0,)), ((), ())),
                  int(wq.shape[1]), kernel)
    return intmath.row_epilogue(acc, bias, words, relu).reshape(-1, 1, 1)


def _conv_int8_batch(xs, wq, bias, words, k, stride, pad, groups, relu,
                     kernel: str = perfmodel.KERNEL_GEMM_TILED,
                     name: Optional[str] = None):
    """Natively batched CONV twin: (B,C,H,W) -> (B,K,P,Q) as ONE GEMM/launch.

    The lanes fold onto the GEMM's N axis (column index = lane * PQ + pos),
    so the weight matrix streams once per bucket instead of once per vmapped
    lane.  GEMM columns are independent — neither any product nor any
    column's accumulation order changes — so this is bit-exact vs vmapping
    ``_conv_int8`` over the lanes, for the Pallas kernel and the exact f32
    GEMM alike.
    """
    if kernel == perfmodel.KERNEL_PALLAS:
        return int8_conv.conv2d_int8_batch(xs, wq, bias, words, k, stride,
                                           pad, groups, relu,
                                           interpret=_pallas_interpret(),
                                           name=name)
    b, c, h, w_in = xs.shape
    kk = wq.shape[0]
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = jax.vmap(lambda x: _im2col(x, k, stride, pad))(xs)
        folded = jnp.moveaxis(cols, 0, 1).reshape(c * k * k, b * p * q)
        acc = _dot_i8(wq, folded, (((1,), (0,)), ((), ())), c * k * k, kernel)
    else:
        cg, kg = c // groups, kk // groups
        xg = xs.reshape(b, groups, cg, h, w_in)
        colsg = jax.vmap(jax.vmap(lambda xx: _im2col(xx, k, stride, pad)))(xg)
        folded = colsg.transpose(1, 2, 0, 3).reshape(groups, cg * k * k,
                                                     b * p * q)
        wg = wq.reshape(groups, kg, cg * k * k)
        acc = _dot_i8(wg, folded, (((2,), (1,)), ((0,), (0,))), cg * k * k,
                      kernel)
        acc = acc.reshape(kk, b * p * q)
    y = intmath.row_epilogue(acc, bias, words, relu)
    return jnp.moveaxis(y.reshape(kk, b, p * q), 0, 1).reshape(b, kk, p, q)


def _fc_int8_batch(xs, wq, bias, words, relu,
                   kernel: str = perfmodel.KERNEL_GEMM_TILED,
                   name: Optional[str] = None):
    """Natively batched FC twin: the bucket IS the GEMM N axis — (K, Cin)
    streams once against a (Cin, B) activation block instead of B GEMVs."""
    b = xs.shape[0]
    if kernel == perfmodel.KERNEL_PALLAS:
        return int8_conv.fc_int8_batch(xs.reshape(b, -1), wq, bias, words,
                                       relu, interpret=_pallas_interpret(),
                                       name=name)
    acc = _dot_i8(wq, xs.reshape(b, -1).T, (((1,), (0,)), ((), ())),
                  int(wq.shape[1]), kernel)
    y = intmath.row_epilogue(acc, bias, words, relu)
    return y.T.reshape(b, -1, 1, 1)


def _pool_int8(x, kern, stride, pad, mode, scale_word):
    c, h, w = x.shape
    r, s = kern
    if mode == 1:      # max
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)), constant_values=-128)
        p = (h + 2 * pad - r) // stride + 1
        q = (w + 2 * pad - s) // stride + 1
        out = jnp.full((c, p, q), -128, jnp.int8)
        for i in range(r):
            for j in range(s):
                out = jnp.maximum(out, xp[:, i:i + stride * p:stride, j:j + stride * q:stride])
        return out
    xp = jnp.pad(x.astype(jnp.int32), ((0, 0), (pad, pad), (pad, pad)))
    p = (h + 2 * pad - r) // stride + 1
    q = (w + 2 * pad - s) // stride + 1
    acc = jnp.zeros((c, p, q), jnp.int32)
    for i in range(r):
        for j in range(s):
            acc = acc + xp[:, i:i + stride * p:stride, j:j + stride * q:stride]
    m, pre, post = quant.unpack_scale(scale_word)
    return _clip8(_apply_scale(acc, m, pre, post))


def _add_int8(a, b, word_a, word_b, relu):
    ma, pa, sa = quant.unpack_scale(word_a)
    mb, pb, sb = quant.unpack_scale(word_b)
    acc = (_apply_scale(a.astype(jnp.int32), ma, pa, sa)
           + _apply_scale(b.astype(jnp.int32), mb, pb, sb))
    if relu:
        acc = jnp.maximum(acc, 0)
    return _clip8(acc)


# ---------------------------------------------------------------------------
# bf16 (nv_full) twins — bf16 operands, f32 accumulate, no requantisation.
# Same jnp twins pattern as the int8 family above; the independent oracle is
# numpy core/refops.conv_bf16 (the VP), compared under core/tolerances.py.
# ---------------------------------------------------------------------------
def _conv_bf16(x, wq, bias, k, stride, pad, groups, relu,
               kernel: str = perfmodel.KERNEL_GEMM_BF16,
               name: Optional[str] = None):
    if kernel == perfmodel.KERNEL_PALLAS_BF16:
        # whole CONV->SDP pipeline fused in the Pallas kernel — the f32
        # accumulator never leaves VMEM
        return bf16_conv.conv2d_bf16(x, wq, bias, k, stride, pad, groups,
                                     relu, interpret=_pallas_interpret(),
                                     name=name)
    kk = wq.shape[0]
    c, h, w_in = x.shape
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = _im2col(x, k, stride, pad)
        acc = jax.lax.dot_general(wq, cols, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    else:
        cg, kg = c // groups, kk // groups
        xg = x.reshape(groups, cg, h, w_in)
        colsg = jax.vmap(lambda xx: _im2col(xx, k, stride, pad))(xg)
        wg = wq.reshape(groups, kg, cg * k * k)
        acc = jax.lax.dot_general(wg, colsg, (((2,), (1,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        acc = acc.reshape(kk, p * q)
    acc = acc + bias[:, None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.astype(jnp.bfloat16).reshape(kk, p, q)


def _fc_bf16(x, wq, bias, relu, kernel: str = perfmodel.KERNEL_GEMM_BF16,
             name: Optional[str] = None):
    if kernel == perfmodel.KERNEL_PALLAS_BF16:
        return bf16_conv.fc_bf16(x.reshape(-1), wq, bias, relu,
                                 interpret=_pallas_interpret(),
                                 name=name)
    acc = jax.lax.dot_general(wq, x.reshape(-1, 1), (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc + bias[:, None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.astype(jnp.bfloat16).reshape(-1, 1, 1)


def _conv_bf16_batch(xs, wq, bias, k, stride, pad, groups, relu,
                     kernel: str = perfmodel.KERNEL_GEMM_BF16,
                     name: Optional[str] = None):
    """Natively batched bf16 CONV twin: lanes fold onto the GEMM N axis.

    Folding preserves each column's f32 accumulation order, so this is
    bit-identical to vmapping ``_conv_bf16`` over the lanes.
    """
    if kernel == perfmodel.KERNEL_PALLAS_BF16:
        return bf16_conv.conv2d_bf16_batch(xs, wq, bias, k, stride, pad,
                                           groups, relu,
                                           interpret=_pallas_interpret(),
                                           name=name)
    b, c, h, w_in = xs.shape
    kk = wq.shape[0]
    p = (h + 2 * pad - k) // stride + 1
    q = (w_in + 2 * pad - k) // stride + 1
    if groups == 1:
        cols = jax.vmap(lambda x: _im2col(x, k, stride, pad))(xs)
        folded = jnp.moveaxis(cols, 0, 1).reshape(c * k * k, b * p * q)
        acc = jax.lax.dot_general(wq, folded, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    else:
        cg, kg = c // groups, kk // groups
        xg = xs.reshape(b, groups, cg, h, w_in)
        colsg = jax.vmap(jax.vmap(lambda xx: _im2col(xx, k, stride, pad)))(xg)
        folded = colsg.transpose(1, 2, 0, 3).reshape(groups, cg * k * k,
                                                     b * p * q)
        wg = wq.reshape(groups, kg, cg * k * k)
        acc = jax.lax.dot_general(wg, folded, (((2,), (1,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        acc = acc.reshape(kk, b * p * q)
    acc = acc + bias[:, None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    y = acc.astype(jnp.bfloat16)
    return jnp.moveaxis(y.reshape(kk, b, p * q), 0, 1).reshape(b, kk, p, q)


def _fc_bf16_batch(xs, wq, bias, relu,
                   kernel: str = perfmodel.KERNEL_GEMM_BF16,
                   name: Optional[str] = None):
    """Natively batched bf16 FC twin — one (K, Cin) x (Cin, B) GEMM."""
    b = xs.shape[0]
    if kernel == perfmodel.KERNEL_PALLAS_BF16:
        return bf16_conv.fc_bf16_batch(xs.reshape(b, -1), wq, bias, relu,
                                       interpret=_pallas_interpret(),
                                       name=name)
    acc = jax.lax.dot_general(wq, xs.reshape(b, -1).T,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    acc = acc + bias[:, None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.astype(jnp.bfloat16).T.reshape(b, -1, 1, 1)


def _pool_bf16(x, kern, stride, pad, mode):
    """PDP in float: max with -inf fill, avg as f32 sum / window (the gap
    descriptor is avg with kernel == (H, W), which reduces to the mean)."""
    x32 = x.astype(jnp.float32)
    c, h, w = x.shape
    r, s = kern
    p = (h + 2 * pad - r) // stride + 1
    q = (w + 2 * pad - s) // stride + 1
    if mode == 1:      # max
        xp = jnp.pad(x32, ((0, 0), (pad, pad), (pad, pad)),
                     constant_values=-jnp.inf)
        out = jnp.full((c, p, q), -jnp.inf, jnp.float32)
        for i in range(r):
            for j in range(s):
                out = jnp.maximum(out, xp[:, i:i + stride * p:stride,
                                          j:j + stride * q:stride])
        return out.astype(jnp.bfloat16)
    xp = jnp.pad(x32, ((0, 0), (pad, pad), (pad, pad)))
    acc = jnp.zeros((c, p, q), jnp.float32)
    for i in range(r):
        for j in range(s):
            acc = acc + xp[:, i:i + stride * p:stride, j:j + stride * q:stride]
    return (acc / (r * s)).astype(jnp.bfloat16)


def _add_bf16(a, b, relu):
    acc = a.astype(jnp.float32) + b.astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Descriptor -> op over values
# ---------------------------------------------------------------------------
def _surface_bytes(dims, elem_bytes: int) -> int:
    n, c, h, w = dims
    return c * h * w * elem_bytes


def _gemm_params(d: engine.Descriptor, arena: np.ndarray, base: int,
                 dtype: str) -> tuple:
    """``(wq, bias[, words])`` of one CONV/FC descriptor as numpy views into
    the preloaded arena bytes (uint8), shaped for the GEMM: wq (K, Cin/g*R*S)
    int8 or bf16, bias (K,) int32 or f32, scale words (K,) int32 (int8)."""
    _, c, h, w = d.src_dims
    k = d.dst_dims[1]
    r, s = d.kernel
    cin_g = c // d.groups if d.unit == "CONV" else c * h * w
    wt_n = k * cin_g * (r * s if d.unit == "CONV" else 1)
    wo, bo, so = d.wt_addr - base, d.bias_addr - base, d.scale_addr - base
    if dtype == "bf16":
        return (arena[wo:wo + 2 * wt_n].view(ml_dtypes.bfloat16).reshape(k, -1),
                arena[bo:bo + 4 * k].view(np.float32))
    return (arena[wo:wo + wt_n].view(np.int8).reshape(k, -1),
            arena[bo:bo + 4 * k].view(np.int32),
            arena[so:so + 4 * k].view(np.int32))


def _op_fn(index: int, d: engine.Descriptor, kernel: str, dtype: str,
           batched: bool = False):
    """``f(inputs, params) -> y`` for descriptor ``index`` of the trace.

    ``inputs`` holds the source surface (and the EW unit's second operand),
    each shaped (C, H, W); ``params`` is the descriptor's ``_gemm_params``
    (empty for PDP/EW).  ``batched=True`` builds the natively batched CONV/FC
    launch over (B, C, H, W) inputs instead: the lanes fold onto the GEMM N
    axis, so the weights stream once per bucket.  Folding changes neither any
    product nor any column's accumulation order, so it is bit-identical to
    vmapping the single-image op over the lanes.  A fused kernel is named
    after the descriptor, ``d<index>_<unit>`` (``d00_conv``), so the compiled
    program and a profile name each layer.
    """
    r, _ = d.kernel
    bf16 = dtype == "bf16"
    name = f"d{index:02d}_{d.unit.lower()}"
    if d.unit == "CONV":
        conv = ((_conv_bf16_batch if batched else _conv_bf16) if bf16
                else (_conv_int8_batch if batched else _conv_int8))
        return lambda ins, p: conv(ins[0], *p, r, d.stride, d.pad, d.groups,
                                   d.relu, kernel, name)
    if d.unit == "FC":
        fc = ((_fc_bf16_batch if batched else _fc_bf16) if bf16
              else (_fc_int8_batch if batched else _fc_int8))
        return lambda ins, p: fc(ins[0], *p, d.relu, kernel, name)
    assert not batched, d.unit
    if d.unit == "PDP":
        if bf16:
            return lambda ins, p: _pool_bf16(ins[0], d.kernel, d.stride,
                                             d.pad, d.pool_mode)
        word = engine._pack_scale(d.out_scale)
        return lambda ins, p: _pool_int8(ins[0], d.kernel, d.stride, d.pad,
                                         d.pool_mode, word)
    if d.unit == "EW":
        if bf16:
            return lambda ins, p: _add_bf16(ins[0], ins[1], d.relu)
        wa, wb = engine._pack_scale(d.out_scale), engine._pack_scale(d.aux_scale)
        return lambda ins, p: _add_int8(ins[0], ins[1], wa, wb, d.relu)
    raise ValueError(d.unit)


def _read_pieces(writes, lo: int, hi: int, eb: int) -> list:
    """Resolve the arena byte region ``[lo, hi)`` against the values written
    so far.

    ``writes`` lists ``(value index, lo, hi)`` byte regions in program order
    (index -1 is the input surface); per byte the latest write wins, exactly
    as in the DRAM arena.  Returns ``(value index, first, last)`` element
    ranges in address order.  An exact match with one producer is a single
    whole-value piece (forwarding); a concat consumer reads several
    producers laid out side by side.
    """
    pieces = []
    pos = lo
    while pos < hi:
        cover = next((n for n in range(len(writes) - 1, -1, -1)
                      if writes[n][1] <= pos < writes[n][2]), None)
        if cover is None:
            raise ValueError(f"arena byte {pos:#x} is read before the input "
                             f"or any op writes it")
        j, wlo, whi = writes[cover]
        end = min([w[1] for w in writes[cover + 1:] if pos < w[1] < hi]
                  + [hi, whi])
        pieces.append((j, (pos - wlo) // eb, (end - wlo) // eb))
        pos = end
    return pieces


def _gather(vals: dict, pieces: list, shape: tuple, lead: tuple = ()):
    """Assemble one read from its pieces: a flat value per piece (with the
    ``lead`` lane axis of batch programs), concatenated and reshaped to
    ``lead + shape``."""
    parts = []
    for src, a, b in pieces:
        v = vals[src]
        parts.append(v if (a, b) == (0, v.shape[-1]) else v[..., a:b])
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    return flat.reshape(lead + shape)


# ---------------------------------------------------------------------------
# Executors
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ExecResult:
    output_int8: np.ndarray
    output: np.ndarray
    degraded: bool = False      # served by a fallback backend (circuit open)


@dataclasses.dataclass(frozen=True)
class ExecutorCapabilities:
    """What a backend can do — consulted by the scheduler instead of
    special-casing backend names or classes.

    ``native_batching``  — ``run_batch`` executes the whole batch as one
                           program (vs the sequential fallback loop).
    ``resident_arena``   — keeps device state across calls (``reset_arena``).
    ``shardable``        — the batch program honours ``batch_sharding`` (a
                           ``NamedSharding`` over a 1-axis data mesh) to
                           split lanes across devices.
    ``max_batch``        — hard batch-size ceiling, or ``None`` (unbounded).
    ``kernels``          — the GEMM kernels this backend's plan resolved to
                           (names from ``core.perfmodel``), so callers can
                           see which code path serves each network.
    ``profileable``      — ``run_profiled``/``run_batch_profiled`` exist:
                           the backend can time each descriptor's kernel
                           individually (``obs.report.profile_layers``, the
                           cost model's calibration, relies on it).
    ``split_launch``     — ``run``/``run_batch`` come in two halves:
                           ``submit(x)``/``submit_batch(X, lanes)`` enqueue
                           the program and return a ``Launched`` handle
                           without waiting for the device, and
                           ``finish(handle)`` waits and fetches.  The
                           scheduler enqueues the next launch between the
                           two halves of the one in flight.
    """
    native_batching: bool = False
    resident_arena: bool = False
    shardable: bool = False
    max_batch: Optional[int] = None
    dtype: str = "int8"
    kernels: tuple = ()
    profileable: bool = False
    split_launch: bool = False


@dataclasses.dataclass(frozen=True)
class Launched:
    """An enqueued launch: the program's output surface on the device (not
    waited for) and the live ``lanes`` of a batch (None: a single image)."""
    y: object
    lanes: Optional[int] = None


@runtime_checkable
class ExecutorBackend(Protocol):
    """Uniform executor contract every registered backend must satisfy.

    ``run(x)`` serves one input.  ``run_batch(X, lanes=None)`` serves a
    (possibly padded) batch ``X`` of shape ``(N, ...)`` and returns results
    for the first ``lanes`` lanes (all ``N`` when ``lanes`` is ``None``) —
    padding and lane masking are owned by the *scheduler*, never by the
    backend.  ``capabilities()`` declares what the backend supports so
    callers never have to special-case backend names.
    """

    def run(self, x: np.ndarray) -> ExecResult: ...

    def run_batch(self, X: np.ndarray,
                  lanes: Optional[int] = None) -> ExecResult: ...

    def capabilities(self) -> ExecutorCapabilities: ...


class _ExecutorBase:
    """Common decode/bind logic from the two bare-metal artifacts."""

    def __init__(self, trace: Trace, weight_image: Dict[int, bytes],
                 cfg: engine.EngineConfig = engine.NV_SMALL,
                 input_scale: float = 1.0, output_scale: float = 1.0,
                 output_elems: Optional[int] = None,
                 kernel_plan: Union[str, Sequence, Dict[int, str], None] = None):
        if cfg.dtype not in ("int8", "bf16"):
            known = ", ".join(f"{n} (dtype={c.dtype})"
                              for n, c in engine.CONFIGS.items())
            raise NotImplementedError(
                f"executor backends implement the int8 (nv_small) and bf16 "
                f"(nv_full) datapaths; engine config {cfg.name!r} declares "
                f"dtype={cfg.dtype!r}.  Known engine configs: {known}")
        self.cfg = cfg
        self.trace = trace
        self.input_scale = input_scale
        self.output_scale = output_scale
        self.descs = engine.decode_descriptors(trace.commands)
        if not self.descs:
            raise ValueError("trace contains no engine ops")
        # Kernel plan: one perfmodel.KernelChoice per descriptor, cost-model
        # selected for the platform jax executes on; ``kernel_plan=`` forces
        # choices for debugging/A-B (a kernel name for all CONV/FC, a
        # per-descriptor sequence, or an {index: name} dict).  The spec is
        # kept so per-bucket plans (``batched_kernel_plan``) re-run the
        # batch-aware cost model under the same overrides.
        self._kernel_plan_spec = kernel_plan
        self.kernel_plan = self._resolve_kernel_plan(kernel_plan)
        self._plan_cache: Dict[int, List[perfmodel.KernelChoice]] = \
            {1: self.kernel_plan}
        # Program builds performed so far (single + one per batch shape for
        # natively batching backends) — the compile-stall observability knob.
        self.compile_count = 0
        # Arena geometry, derived from the trace alone.  All addresses are
        # byte addresses; surfaces occupy elem_bytes per element (1 for int8,
        # 2 for bf16 — see core/memory.plan_arena).
        eb = cfg.elem_bytes
        hi = engine.DRAM_BASE
        for d in self.descs:
            hi = max(hi, d.dst_addr + _surface_bytes(d.dst_dims, eb),
                     d.src_addr + _surface_bytes(d.src_dims, eb))
        for a, b in weight_image.items():
            hi = max(hi, a + len(b))
        self.base = engine.DRAM_BASE
        self.size = hi - self.base
        # Preloaded image: weights + (sample) input, as extracted from the VP log.
        arena0 = np.zeros(self.size, np.uint8)
        for a, b in weight_image.items():
            arena0[a - self.base:a - self.base + len(b)] = np.frombuffer(b, np.uint8)
        self.arena0 = arena0
        # Integrity anchor: the preload regions (weight/bias/scale tables plus
        # the sample input) are the only arena bytes with an authoritative
        # source, so their CRC at preload time defines "arena intact".
        # ``arena_ok()`` re-checksums them; ``reset_arena()`` restores the
        # pristine bytes in place and drops the device copies made from them.
        self._preload = sorted(
            ((a - self.base, np.frombuffer(b, np.uint8))
             for a, b in weight_image.items()), key=lambda t: t[0])
        self._weight_crc0 = self.weight_checksum()
        # I/O surfaces: input = first op's source; output = last op's dest.
        self.input_off = self.descs[0].src_addr - self.base
        self.input_dims = self.descs[0].src_dims
        self.output_off = self.descs[-1].dst_addr - self.base
        self.output_dims = self.descs[-1].dst_dims
        self.output_elems = output_elems or \
            _surface_bytes(self.output_dims, 1)       # ELEMENT count
        self.output_bytes = self.output_elems * eb    # arena-slice length

    def batched_kernel_plan(self, batch: int) -> List[perfmodel.KernelChoice]:
        """The per-bucket plan: the batch-aware cost model re-selects each
        CONV/FC kernel for this bucket size (cached per bucket).  A choice's
        ``batched`` flag says whether the natively batched variant (one fused
        launch per bucket) beats vmapping the single-image program."""
        batch = max(int(batch), 1)
        plan = self._plan_cache.get(batch)
        if plan is None:
            plan = self._resolve_kernel_plan(self._kernel_plan_spec,
                                             batch=batch)
            self._plan_cache[batch] = plan
        return plan

    def _resolve_kernel_plan(self, spec,
                             batch: int = 1) -> List[perfmodel.KernelChoice]:
        if isinstance(spec, (list, tuple)) and len(spec) != len(self.descs):
            raise ValueError(
                f"kernel_plan sequence has {len(spec)} entries but the trace "
                f"decodes to {len(self.descs)} descriptors (PDP/EW count "
                f"too — use None for non-GEMM positions, or an "
                f"{{index: kernel}} dict)")
        if isinstance(spec, dict):
            try:                    # JSON round-trips stringify object keys
                spec = {int(i): v for i, v in spec.items()}
            except (TypeError, ValueError):
                raise ValueError(
                    f"kernel_plan dict keys must be descriptor indices "
                    f"(ints), got {sorted(map(repr, spec))}") from None
            bad = [i for i in spec
                   if not (0 <= i < len(self.descs)
                           and self.descs[i].unit in ("CONV", "FC"))]
            if bad:
                convfc = [i for i, d in enumerate(self.descs)
                          if d.unit in ("CONV", "FC")]
                raise ValueError(
                    f"kernel_plan dict keys {bad} do not name CONV/FC "
                    f"descriptors (valid indices: {convfc}) — the override "
                    f"would silently no-op")
        backend = perfmodel.default_backend()
        choices = []
        for i, d in enumerate(self.descs):
            if isinstance(spec, dict):
                ov = spec.get(i)
            elif isinstance(spec, (list, tuple)):
                ov = spec[i]
            else:
                ov = spec                      # None or a kernel name for all
            if d.unit not in ("CONV", "FC"):
                ov = None
            choices.append(perfmodel.select_kernel(d, backend, override=ov,
                                                   dtype=self.cfg.dtype,
                                                   batch=batch))
        return choices

    def kernel_plan_summary(self) -> List[Dict]:
        """The resolved plan as JSON-ready dicts (mirrors the manifest)."""
        return [dict(c.to_dict(), index=i, unit=d.unit)
                for i, (d, c) in enumerate(zip(self.descs, self.kernel_plan))]

    def _quant_in(self, x: np.ndarray) -> np.ndarray:
        """Input image -> the engine's surface dtype (int8 or bf16)."""
        x = np.asarray(x)
        if self.cfg.dtype == "int8":
            if x.dtype == np.int8:
                return x
            return quant.quantize_act(x, self.input_scale)
        return np.ascontiguousarray(x).astype(ml_dtypes.bfloat16)

    def _dequant_out(self, y_i8: np.ndarray) -> np.ndarray:
        return y_i8.astype(np.float32) * self.output_scale

    def _finish_out(self, y_bytes: np.ndarray) -> ExecResult:
        """Raw output-surface bytes (last axis = ``output_bytes``) ->
        ``ExecResult``.  ``output_int8`` carries the raw engine bytes — int8
        logits for nv_small, the bf16 byte stream for nv_full (the same
        convention as ``VpResult``); ``output`` is always float32."""
        if self.cfg.dtype == "int8":
            y_i8 = y_bytes.view(np.int8)
            return ExecResult(output_int8=y_i8, output=self._dequant_out(y_i8))
        out = y_bytes.view(ml_dtypes.bfloat16).astype(np.float32) \
            * self.output_scale
        return ExecResult(output_int8=y_bytes.view(np.uint8), output=out)

    def _plan_kernels(self) -> tuple:
        return tuple(sorted({c.kernel for c in self.kernel_plan
                             if c.kernel != perfmodel.KERNEL_VPU}))

    # -- arena integrity -----------------------------------------------------
    def weight_checksum(self) -> int:
        """CRC32 over the preload regions of ``arena0`` as they are NOW."""
        crc = 0
        for off, b in self._preload:
            crc = zlib.crc32(self.arena0[off:off + b.size], crc)
        return crc

    def arena_ok(self) -> bool:
        """True when the preload regions still carry their load-time bytes.
        The scheduler's supervisor checks this after a failed launch — a
        crashed backend call may have scribbled on the weight arena."""
        return self.weight_checksum() == self._weight_crc0

    def reset_arena(self) -> None:
        """Restore the pristine preload bytes in place and drop any
        device-resident copies, so the next run re-materialises from a known
        -good arena."""
        for off, b in self._preload:
            self.arena0[off:off + b.size] = b
        self._drop_device_state()

    def _drop_device_state(self) -> None:
        """Invalidate device-resident arena copies (no-op for host-only
        backends); overridden by backends with ``resident_arena``."""

    # Backends that can time each descriptor's kernel individually set this
    # and implement ``run_profiled`` (the calibration workflow of
    # ``obs.report`` runs it; serving never does).
    _profileable = False

    def capabilities(self) -> ExecutorCapabilities:
        """Default: sequential batching, no device residency, not shardable."""
        return ExecutorCapabilities(dtype=self.cfg.dtype,
                                    kernels=self._plan_kernels(),
                                    profileable=self._profileable)

    def run_batch(self, X: np.ndarray,
                  lanes: Optional[int] = None) -> ExecResult:
        """Batched inference, default: sequential runs, stacked.

        Only the first ``lanes`` rows are executed (the rest are padding the
        scheduler added to hit a bucket size); ``lanes=None`` runs them all.
        """
        X = np.asarray(X)
        n = X.shape[0] if lanes is None else lanes
        outs = [self.run(x) for x in X[:n]]
        return ExecResult(output_int8=np.stack([o.output_int8 for o in outs]),
                          output=np.stack([o.output for o in outs]))

    def run_profiled(self, x: np.ndarray) -> tuple:
        """``(ExecResult, samples)`` with one per-layer timing sample per
        descriptor: ``{"index", "unit", "kernel", "bucket", "native", "us",
        "t0", "t1"}`` (``t0``/``t1`` are ``time.perf_counter`` bounds, so the
        caller can place the kernels on its timeline).  Only meaningful when
        ``capabilities().profileable`` — the default raises."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support per-layer profiling "
            f"(capabilities().profileable is False)")

    def run_batch_profiled(self, X: np.ndarray,
                           lanes: Optional[int] = None) -> tuple:
        """Batched profiled inference, default: sequential profiled runs per
        lane (each sample keeps ``bucket=1`` — the lanes really did execute
        as independent single-image programs)."""
        X = np.asarray(X)
        n = X.shape[0] if lanes is None else lanes
        outs, samples = [], []
        for x in X[:n]:
            r, s = self.run_profiled(x)
            outs.append(r)
            samples.extend(s)
        res = ExecResult(output_int8=np.stack([o.output_int8 for o in outs]),
                         output=np.stack([o.output for o in outs]))
        return res, samples


class BareMetalExecutor(_ExecutorBase):
    """One fused XLA executable per batch shape — the bare-metal binary.

    The CONV/FC weight, bias and scale tables are cut out of the preloaded
    image once, at first use, and stay resident on the device as one array
    each.  Activations flow through the program as values: every read of an
    arena region resolves at build time to the values last written there
    (``_read_pieces``), so the program never slices tensors out of a flat
    byte arena — the TPU compiler's time and memory grow with the size of a
    1-D array that tensors are reshaped out of.
    """

    def __init__(self, *args, donate: bool = True, native_batch: bool = True,
                 **kw):
        # ``donate`` is accepted for backward compatibility and ignored: no
        # argument of the program is a buffer worth donating.
        # ``native_batch`` picks the bucket execution style: True follows the
        # per-bucket cost-model plan, False pins every bucket to the vmapped
        # single-image program (the oracle), "force" runs every CONV/FC as
        # the natively batched fused launch regardless of the plan — the A/B
        # lever the batched_fused bench and the parity tests use.
        del donate
        if native_batch not in (True, False, "force"):
            raise ValueError(f"native_batch must be True, False or 'force', "
                             f"got {native_batch!r}")
        self.native_batch = native_batch
        super().__init__(*args, **kw)
        eb = self.cfg.elem_bytes
        dtype = self.cfg.dtype
        # Dataflow plan, from the trace alone: for each op the pieces of its
        # input reads (source, then the EW operand), and for the program the
        # pieces of the output surface.
        writes = [(-1, self.input_off,
                   self.input_off + _surface_bytes(self.input_dims, eb))]
        self._reads = []
        for i, d in enumerate(self.descs):
            regions = [d.src_addr] + ([d.aux_addr] if d.unit == "EW" else [])
            lo_n = _surface_bytes(d.src_dims, eb)
            self._reads.append([
                (_read_pieces(writes, a - self.base, a - self.base + lo_n,
                              eb), d.src_dims[1:])
                for a in regions])
            dst = d.dst_addr - self.base
            writes.append((i, dst, dst + _surface_bytes(d.dst_dims, eb)))
        self._out_pieces = _read_pieces(
            writes, self.output_off, self.output_off + self.output_bytes, eb)
        # values no later read needs are dropped as the replay goes (the
        # profiled replay runs op by op and would otherwise hold them all)
        last = {}
        for i, reads in enumerate(self._reads):
            for pieces, _ in reads:
                last.update((src, i) for src, _, _ in pieces)
        last.update((src, len(self.descs)) for src, _, _ in self._out_pieces)
        self._dead_after = [[j for j, li in last.items() if li == i]
                            for i in range(len(self.descs))]
        self._single_ops = [_op_fn(i, d, c.kernel, dtype) for i, (d, c)
                            in enumerate(zip(self.descs, self.kernel_plan))]
        # per-op jitted closures for the profiled paths, built on first use
        self._profile_fns = None
        self._profile_batch_fns: Dict[int, list] = {}
        self._fn = jax.jit(_named(functools.partial(
            self._replay, self._single_ops), "serve_single"))
        # Batch programs are built lazily per batch shape from the
        # per-bucket kernel plan: CONV/FC ops whose bucket plan says
        # ``batched`` run as ONE natively batched fused launch; everything
        # else (and the whole program when ``native_batch=False``) vmaps the
        # single-image op per lane.
        self._batch_fns: Dict[tuple, object] = {}
        self._ran_single = False
        self._params_dev = None     # device-resident GEMM params, lazy
        # Optional NamedSharding over a 1-axis data mesh: when set (by the
        # scheduler's dispatcher), batch lanes are placed across devices and
        # GSPMD partitions the batch program; the weights replicate.
        self.batch_sharding = None

    def _replay(self, ops, params, x, samples=None, meta=None):
        """Run ``ops`` over the dataflow plan: ``x`` is the flat input surface
        (with a leading lane axis in batch programs); returns the flat output
        surface.  With ``samples`` (the profiled paths) each op is timed
        behind ``block_until_ready``, ``meta(i)`` naming its sample."""
        lead = x.shape[:-1]
        vals = {-1: x}
        for i, op in enumerate(ops):
            t0 = time.perf_counter()
            ins = [_gather(vals, pieces, shape, lead)
                   for pieces, shape in self._reads[i]]
            y = op(ins, params[i])
            vals[i] = y.reshape(lead + (-1,))
            if samples is not None:
                jax.block_until_ready(vals[i])
                t1 = time.perf_counter()
                samples.append(dict(meta(i), index=i, unit=self.descs[i].unit,
                                    us=(t1 - t0) * 1e6, t0=t0, t1=t1))
            for j in self._dead_after[i]:
                del vals[j]
        return _gather(vals, self._out_pieces, (self.output_elems,), lead)

    def _host_params(self) -> list:
        return [_gemm_params(d, self.arena0, self.base, self.cfg.dtype)
                if d.unit in ("CONV", "FC") else () for d in self.descs]

    def _ensure_params(self):
        if self._params_dev is None:
            # copies: a device buffer must never alias ``arena0``, which the
            # integrity path rewrites in place
            self._params_dev = jax.tree.map(
                lambda a: jax.device_put(np.array(a)), self._host_params())
        return self._params_dev

    def _drop_device_state(self) -> None:
        """Drop the device-resident params (next run re-materialises them
        from arena0)."""
        self._params_dev = None

    def _batch_ops(self, n: int):
        """Per-bucket op list as ``(op, choice, native)`` triples: the
        natively batched fused launch where this bucket's plan says so, the
        vmapped single-image op (the oracle and the non-native fallback)
        everywhere else."""
        native = bool(self.native_batch) and n > 1
        forced = self.native_batch == "force"
        plan = self.batched_kernel_plan(n) if native else self.kernel_plan
        bops = []
        for i, (d, ch, single) in enumerate(zip(self.descs, plan,
                                                self._single_ops)):
            if native and (ch.batched or forced) and d.unit in ("CONV", "FC"):
                bops.append((_op_fn(i, d, ch.kernel, self.cfg.dtype,
                                    batched=True), ch, True))
            else:
                bops.append((jax.vmap(single, in_axes=(0, None)), ch, False))
        return bops

    def _make_batch_fn(self, n: int, sharding=None):
        """The bucket-``n`` program; with a lane ``sharding`` every device
        runs it over its own lanes (``shard_map``) — GSPMD cannot partition
        a Pallas TPU kernel — and the weights replicate."""
        replay = functools.partial(self._replay,
                                   [b for b, _, _ in self._batch_ops(n)])
        if sharding is not None:
            # check_vma=False: pallas_call declares its outputs without the
            # lane axis they vary over
            replay = jax.shard_map(replay, mesh=sharding.mesh,
                                   in_specs=(P(), sharding.spec),
                                   out_specs=sharding.spec, check_vma=False)
        return jax.jit(_named(replay, f"serve_b{n}"))

    def _abstract_args(self, batch: Optional[int] = None, sharding=None):
        """``(params, x)`` shapes of the single-image program (``batch`` None)
        or of a batch program, optionally placed with ``sharding``."""
        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                              self._host_params())
        n_in = _surface_bytes(self.input_dims, 1)
        dtype = jnp.bfloat16 if self.cfg.dtype == "bf16" else jnp.int8
        lead = () if batch is None else (batch,)
        return params, spec(lead + (n_in,), dtype)

    def compile(self):
        """AOT-compile the fused single-image program (the 'binary')."""
        return self._fn.lower(*self._abstract_args()).compile()

    def finish(self, launched: Launched) -> ExecResult:
        """The second half of a launch: wait for its output surface, fetch
        it and unpack its first ``lanes`` rows (the ``device_wait`` and
        ``d2h`` phases; the collector's ``close`` ends ``d2h`` when the
        call returns)."""
        phases = launch_phases()
        jax.block_until_ready(launched.y)
        phases.mark("device_wait", host=False)
        out = self._finish_out(
            np.asarray(launched.y)[:launched.lanes].view(np.uint8))
        phases.mark("d2h")
        return out

    def run(self, x: np.ndarray) -> ExecResult:
        """One image through the single-image program: ``submit`` then
        ``finish``.  The call's phases, in order and tiling it, marked into
        ``obs.trace.launch_phases()`` (opened by the caller): ``quantise``,
        ``h2d``, ``enqueue`` (the program call returning), ``device_wait``,
        ``d2h``."""
        return self.finish(self.submit(x))

    def submit(self, x: np.ndarray) -> Launched:
        """The first half of ``run``: quantise, transfer and enqueue one
        image, without waiting for the device."""
        if not self._ran_single:
            # the single-image program has one fixed shape, so jit compiles
            # it exactly once — on this call
            self._ran_single = True
            self.compile_count += 1
        phases = launch_phases()
        xq = self._quant_in(x).reshape(-1)
        phases.mark("quantise")
        xs = jnp.asarray(xq)
        phases.mark("h2d")
        y = self._fn(self._ensure_params(), xs)
        phases.mark("enqueue")
        return Launched(y)

    def capabilities(self) -> ExecutorCapabilities:
        return ExecutorCapabilities(native_batching=True, resident_arena=True,
                                    shardable=True, dtype=self.cfg.dtype,
                                    kernels=self._plan_kernels(),
                                    profileable=True, split_launch=True)

    def run_profiled(self, x: np.ndarray) -> tuple:
        """Single-image inference with per-descriptor kernel timing.

        Replays the SAME op closures the fused program composes, jitted
        individually so every descriptor has a host-visible boundary
        (``block_until_ready``) to time against.  Integer ops are exact under
        any fusion, so the output is bit-identical to ``run`` for int8 — the
        only cost is losing XLA's cross-op fusion, which is why this path
        serves calibration (``obs.report.profile_layers``) and never a
        request; the served program's kernels are timed by name in a
        ``jax.profiler`` trace instead.
        """
        if self._profile_fns is None:
            self._profile_fns = [jax.jit(op) for op in self._single_ops]
            # one program build per op; counted at build time (each fn
            # compiles on its first call below)
            self.compile_count += len(self._profile_fns)
        xq = jnp.asarray(self._quant_in(x).reshape(-1))
        samples = []
        y = self._replay(
            self._profile_fns, self._ensure_params(), xq, samples,
            lambda i: {"kernel": self.kernel_plan[i].kernel, "bucket": 1,
                       "native": False})
        return self.finish(Launched(y)), samples

    def run_batch_profiled(self, X: np.ndarray,
                           lanes: Optional[int] = None) -> tuple:
        """Batched profiled inference: steps the SAME per-bucket op list the
        fused batch program composes (native fused launches included), each
        op jitted and timed individually.  Bit-exact vs ``run_batch`` for
        int8; samples carry the bucket size and each op's execution style."""
        X = np.asarray(X)
        n = X.shape[0]
        entry = self._profile_batch_fns.get(n)
        if entry is None:
            entry = [(jax.jit(b), ch, nat) for b, ch, nat in
                     self._batch_ops(n)]
            self._profile_batch_fns[n] = entry
            self.compile_count += len(entry)
        xs = jnp.asarray(self._quant_in(X).reshape(n, -1))
        samples = []
        y = self._replay(
            [fn for fn, _, _ in entry], self._ensure_params(), xs, samples,
            lambda i: {"kernel": entry[i][1].kernel, "bucket": n,
                       "native": entry[i][2]})
        return self.finish(Launched(y, lanes)), samples

    def run_batch(self, X: np.ndarray,
                  lanes: Optional[int] = None) -> ExecResult:
        """Run a batch as ONE XLA program (bit-exact vs N ``run`` calls).

        CONV/FC ops whose per-bucket plan resolved ``batched`` execute as a
        single natively batched fused launch (weights stream once per
        bucket); the rest vmap the single-image op per lane.  ``lanes`` trims
        the returned results to the first ``lanes`` rows (the rest being
        scheduler padding); the program itself always executes the full
        padded shape so each bucket size compiles exactly once.  The call's
        phases are those of ``run``: ``submit_batch`` then ``finish``.
        """
        return self.finish(self.submit_batch(X, lanes))

    def submit_batch(self, X: np.ndarray,
                     lanes: Optional[int] = None) -> Launched:
        """The first half of ``run_batch``: quantise, transfer and enqueue
        the batch program, its output placed by ``batch_sharding`` when the
        bucket divides its mesh, without waiting for the device."""
        phases = launch_phases()
        X = np.asarray(X)
        n = X.shape[0]
        shard = self.batch_sharding
        if shard is not None and n % shard.mesh.size:
            shard = None
        fn = self._batch_fns.get((n, shard))
        if fn is None:
            fn = self._make_batch_fn(n, shard)
            self._batch_fns[(n, shard)] = fn
            self.compile_count += 1
        xq = self._quant_in(X).reshape(n, -1)
        phases.mark("quantise")
        xs = jnp.asarray(xq)
        if shard is not None:
            xs = jax.device_put(xs, shard)
        phases.mark("h2d")
        y = fn(self._ensure_params(), xs)
        phases.mark("enqueue")
        return Launched(y, lanes)


def _named(fn, name: str):
    """``fn`` under ``name``: jit names its program ``jit_<name>``, and a
    profile's events after it."""
    fn.__name__ = name
    return fn


def _flat_op(op, ins, params):
    return op(ins, params).reshape(-1)


class LinuxStackExecutor(_ExecutorBase):
    """Driver-stack baseline: per-op executables + tensor-table bookkeeping.

    The per-descriptor binding — jitted op callable, weight/bias/scale
    tables, activation-surface offsets — is resolved at construction (the
    driver's "model load"), so a ``run`` measures per-op dispatch overhead,
    not Python re-parsing of the trace.  Every array handed to an op is one
    the op's caller owns: a copy, never a view into a buffer written later.
    """

    _profileable = True      # per-op dispatch: each op is a natural timing
                             # boundary (the host materialises every result)

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        # Pre-build one jitted callable per op (the 'driver' compiles per-layer
        # kernels); dispatch happens op-at-a-time from Python (the 'syscall').
        # Each returns its surface flat: XLA:CPU (jax 0.9.0) corrupts the
        # heap running a conv GEMM program whose result is reshaped to
        # (K, P, Q) at its end (the 5x5/2 stem of the stride_pad test net).
        self._ops = [(d, jax.jit(functools.partial(
            _flat_op, _op_fn(i, d, ch.kernel, self.cfg.dtype))))
                     for i, (d, ch) in enumerate(zip(self.descs,
                                                     self.kernel_plan))]
        self._params = None
        self._bound_params()

    def _drop_device_state(self) -> None:
        """Drop the bound weight tables (the next run rebinds from arena0)."""
        self._params = None

    def _bound_params(self) -> list:
        if self._params is None:
            self._params = [
                tuple(jax.device_put(np.array(a)) for a in
                      _gemm_params(d, self.arena0, self.base, self.cfg.dtype))
                if d.unit in ("CONV", "FC") else () for d, _ in self._ops]
        return self._params

    def run(self, x: np.ndarray) -> ExecResult:
        return self._run_impl(x)

    def run_profiled(self, x: np.ndarray) -> tuple:
        """Per-op dispatch with per-descriptor timing — the op loop already
        materialises every result on the host, so each iteration IS the
        device-execute bound; the samples simply record it."""
        samples: list = []
        return self._run_impl(x, samples), samples

    def _run_impl(self, x: np.ndarray,
                  samples: Optional[list] = None) -> ExecResult:
        xq = self._quant_in(x)
        dram = self.arena0.copy()       # driver re-stages buffers per submission
        eb = self.cfg.elem_bytes
        sdtype = ml_dtypes.bfloat16 if self.cfg.dtype == "bf16" else np.int8
        params = self._bound_params()

        def surf(addr, dims):
            off, n = addr - self.base, _surface_bytes(dims, eb)
            return dram[off:off + n].view(sdtype).reshape(dims[1:]).copy()

        x_bytes = np.ascontiguousarray(xq.reshape(-1)).view(np.uint8)
        dram[self.input_off:self.input_off + x_bytes.size] = x_bytes
        for i, (d, fn) in enumerate(self._ops):
            t0 = time.perf_counter()
            ins = [surf(d.src_addr, d.src_dims)]
            if d.unit == "EW":
                ins.append(surf(d.aux_addr, d.src_dims))
            y = np.asarray(fn(ins, params[i]))
            off = d.dst_addr - self.base
            dram[off:off + y.size * eb] = y.view(np.uint8)  # driver flushes
            if samples is not None:
                t1 = time.perf_counter()
                samples.append({"index": i, "unit": d.unit,
                                "kernel": self.kernel_plan[i].kernel,
                                "bucket": 1, "native": False,
                                "us": (t1 - t0) * 1e6, "t0": t0, "t1": t1})
        out = dram[self.output_off:self.output_off + self.output_bytes]
        return self._finish_out(out.copy().view(np.int8))
