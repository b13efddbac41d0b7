"""INT8 error-feedback gradient compression (inter-pod link optimisation).

The multi-pod mesh's weakest links carry exactly one collective per step: the
gradient all-reduce over the 'pod' axis.  Compressing that traffic 4x (f32 ->
int8 + per-tensor scale) is the standard trick for slow cross-pod fabrics;
error feedback (Seide et al., 1-bit SGD lineage) keeps the quantisation noise
from biasing convergence: the residual of each step is carried into the next.

Two layers:
  * pure quantise/dequantise + error-feedback state (testable without devices),
  * ``compressed_psum`` — a shard_map collective that all-reduces int8 payloads
    with an f32 scale (used by launch/train.py when ``--compress-grads``).

This reuses the paper's nv_small INT8 insight at the *fabric* level: the same
symmetric-scale quantisation the engine applies to activations is applied to
gradient traffic.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def quantize(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8: returns (q, scale)."""
    amax = jnp.max(jnp.abs(g)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress(grads: Any, residual: Any) -> Tuple[Any, Any, Any]:
    """Error-feedback compression of a gradient pytree.

    Returns (quantised payloads, scales, new residual):
        corrected = g + residual
        q = Q(corrected); new_residual = corrected - deQ(q)
    """
    corrected = jax.tree.map(lambda g, r: g.astype(jnp.float32) + r, grads, residual)
    qs = jax.tree.map(quantize, corrected)
    payload = jax.tree.map(lambda t: t[0], qs,
                           is_leaf=lambda x: isinstance(x, tuple))
    scales = jax.tree.map(lambda t: t[1], qs,
                          is_leaf=lambda x: isinstance(x, tuple))
    deq = jax.tree.map(dequantize, payload, scales)
    new_residual = jax.tree.map(lambda c, d: c - d, corrected, deq)
    return payload, scales, new_residual


def init_residual(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum(grads: Any, residual: Any, mesh: jax.sharding.Mesh,
                    axis: str = "pod") -> Tuple[Any, Any]:
    """All-reduce ``grads`` over ``axis`` with int8 payloads + error feedback.

    int8 payloads are summed in int32 (max pod count 128 before overflow
    concern: 127 * 128 < 2^15), then rescaled by the max participating scale.
    """
    n = mesh.shape[axis]

    def inner(g_and_r):
        grads_, residual_ = g_and_r
        payload, scales, new_res = ef_compress(grads_, residual_)
        # share a common scale = max over participants so the int32 sum is exact
        common = jax.tree.map(lambda s: jax.lax.pmax(s, axis), scales)
        requant = jax.tree.map(
            lambda q, s_old, s_new: jnp.clip(
                jnp.round(q.astype(jnp.float32) * (s_old / s_new)),
                -127, 127).astype(jnp.int32),
            payload, scales, common)
        summed = jax.tree.map(lambda q: jax.lax.psum(q, axis), requant)
        mean = jax.tree.map(lambda q, s: q.astype(jnp.float32) * s / n,
                            summed, common)
        return mean, new_res

    from jax.sharding import PartitionSpec as P
    spec = jax.tree.map(lambda _: P(), grads)
    res_spec = jax.tree.map(lambda _: P(), residual)
    fn = jax.shard_map(inner, mesh=mesh, check_vma=False,
                       in_specs=((spec, res_spec),),
                       out_specs=(spec, res_spec))
    return fn((grads, residual))
