"""jnp twins of the engine's integer arithmetic — the ONE shared copy.

Every jax-side arm of the NVDLA SDP semantics (the executors' op closures,
the Pallas ``int8_conv`` kernel and its oracle) imports these, so a fix to
the round-half-away shift, the scale-word unpack, or the requant pipeline
cannot silently diverge between arms.  The numpy oracle lives separately in
``core/quant.py`` / ``core/refops.py`` — it must stay independent, since the
whole point of the refops parity tests is two implementations.

This is a leaf module: it imports nothing from ``repro`` (both ``core`` and
``kernels`` depend on it).
"""

from __future__ import annotations

import jax.numpy as jnp


def rha_shift(x, k):
    """Round-half-away-from-zero arithmetic right shift on int32."""
    k = jnp.asarray(k, jnp.int32)
    half = jnp.where(k > 0, jnp.left_shift(jnp.int32(1), jnp.maximum(k - 1, 0)), 0)
    return jnp.sign(x) * jnp.right_shift(jnp.abs(x) + half, k)


def apply_scale(x, m, pre, post):
    """Fixed-point rescale: ``((x >> pre) * m) >> post`` with rha rounding."""
    return rha_shift(rha_shift(x, pre) * m, post)


def unpack_words(words_i32):
    """uint32 scale words (bitcast to int32) -> (m, pre, post) int32 arrays."""
    w = words_i32
    m = jnp.right_shift(w, 16) & 0xFFFF            # arithmetic shift ok: masked
    m = jnp.where(m >= 0x8000, m - 0x10000, m)
    pre = jnp.right_shift(w, 8) & 0xFF
    post = w & 0xFF
    return m, pre, post


def clip8(x):
    return jnp.clip(x, -128, 127).astype(jnp.int8)


def row_epilogue(acc, bias, words, relu):
    """SDP epilogue, per-channel on the M (row) axis: +bias, requant, relu,
    int8 clip.  ``acc`` (M, N) int32; ``bias``/``words`` (M,) or (M, 1)
    int32 (the Pallas kernel passes column blocks)."""
    acc = acc + bias.reshape(-1, 1)
    out = apply_scale(acc, *unpack_words(words.reshape(-1, 1)))
    if relu:
        out = jnp.maximum(out, 0)
    return clip8(out)


def im2col(x, k: int, stride: int, pad: int):
    """(C,H,W) int8 -> (C*k*k, P*Q) int8, static shapes."""
    c, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    p = (h + 2 * pad - k) // stride + 1
    q = (w + 2 * pad - k) // stride + 1
    cols = []
    for r in range(k):
        for s in range(k):
            cols.append(xp[:, r:r + stride * p:stride, s:s + stride * q:stride])
    return jnp.stack(cols, 1).reshape(c * k * k, p * q)
