"""Attention primitives used by the LM zoo.

``flash_mha`` is a pure-jnp chunked online-softmax attention (FlashAttention
schedule expressed with lax.scan) — it lowers through pjit/GSPMD for the
multi-pod dry-run and bounds live memory to O(q_chunk x kv_chunk) per head.
The Pallas kernels in kernels/ implement the same math as the TPU-target
hot-path; tests pin them against each other.

``decode_attn`` is the single-new-token path against a static-shape KV cache
(cache length = the cell's seq_len), masked by the current position.  When the
cache's sequence axis is sharded (long-context SP cells), the max/sum
reductions lower to cross-device partial-softmax combines under GSPMD —
the same (m, l, acc) merge the distributed flash-decode kernel tier uses.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              q_chunk: int = 512, kv_chunk: int = 1024,
              sm_scale: Optional[float] = None) -> jax.Array:
    """q (B,H,Sq,D); k/v (B,Hkv,Skv,D); GQA via head grouping. -> (B,H,Sq,D)."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    dv = v.shape[-1]                       # may differ from d (e.g. MLA)
    g = h // hkv
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    q = q.reshape(b, hkv, g, sq, d)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nk = sq // q_chunk, skv // kv_chunk
    qs = q.reshape(b, hkv, g, nq, q_chunk, d).transpose(3, 0, 1, 2, 4, 5)
    ks = k.reshape(b, hkv, nk, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, hkv, nk, kv_chunk, dv).transpose(2, 0, 1, 3, 4)

    def q_step(_, qi_idx):
        qi, iq = qi_idx                                    # (b,hkv,g,qc,d)
        qi32 = qi.astype(jnp.float32) * sm_scale

        def kv_step(carry, kv_idx):
            m, l, acc = carry
            ki, vi, ik = kv_idx
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi32, ki.astype(jnp.float32))
            if causal:
                qpos = iq * q_chunk + jnp.arange(q_chunk)[:, None]
                kpos = ik * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((qpos >= kpos)[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhgqk,bhkd->bhgqd", p,
                                               vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (ks, vs, jnp.arange(nk)))
        return None, (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    _, out = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))
    # out: (nq, b, hkv, g, qc, dv) -> (b, h, sq, dv)
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(b, h, sq, dv)


def _context_mesh():
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:                                  # pragma: no cover
        return None


def use_sp_decode(b: int, hkv: int, smax: int) -> Optional[object]:
    """Return the mesh when the sequence-parallel decode path applies (mirrors
    the cache-layout predicate in distributed/sharding.py)."""
    mesh = _context_mesh()
    if mesh is None or "model" not in mesh.axis_names or b <= 1:
        return None
    ms = mesh.shape["model"]
    if hkv % ms != 0 and smax % ms == 0 and smax // ms >= 512:
        return mesh
    return None


def decode_attn_sp(q, k_cache, v_cache, pos, mesh, *, sm_scale=None,
                   k_new=None, v_new=None):
    """Two-tier distributed flash-decode over a SEQUENCE-sharded cache, with
    the cache update fused INSIDE the shard (each rank owns its range).

    Each 'model' rank (a) writes the new K/V token iff ``pos`` falls in its
    slice (masked local write — no cross-shard dynamic-update-slice, which
    GSPMD would otherwise lower as a whole-cache select), then (b) runs flash
    attention over its slice; partial (m, l, acc) merge with pmax/psum — the
    same combine as the Pallas split-K kernel's intra-chip tier.

    q (B,H,1,D); caches (B,Hkv,S,D); k_new/v_new optional (B,Hkv,1,D).
    Returns out, or (out, k_cache', v_cache') when k_new is given.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    b, h, _, d = q.shape
    hkv = k_cache.shape[1]
    g = h // hkv
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dpsize = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    bax = (dp if len(dp) > 1 else dp[0]) if (dp and b % dpsize == 0 and b > 1) \
        else None
    qspec = P(bax, None, None, None)
    cspec = P(bax, None, "model", None)
    with_update = k_new is not None

    def local(qv, kcv, vcv, knv, vnv, posv):
        s_loc = kcv.shape[2]
        start = jax.lax.axis_index("model") * s_loc
        if with_update:
            lpos = posv - start
            in_range = (lpos >= 0) & (lpos < s_loc)
            safe = jnp.clip(lpos, 0, s_loc - 1)
            kc_u = jax.lax.dynamic_update_slice(
                kcv, knv.astype(kcv.dtype), (0, 0, safe, 0))
            vc_u = jax.lax.dynamic_update_slice(
                vcv, vnv.astype(vcv.dtype), (0, 0, safe, 0))
            kcv = jnp.where(in_range, kc_u, kcv)
            vcv = jnp.where(in_range, vc_u, vcv)
        qg = (qv.reshape(-1, hkv, g, d) * sm_scale).astype(kcv.dtype)
        # bf16 x bf16 -> f32 accumulate: no materialised f32 cache copy
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, kcv,
                       preferred_element_type=jnp.float32)
        idx = start + jnp.arange(s_loc)[None, None, None, :]
        s = jnp.where(idx <= posv, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bhgk,bhkd->bhgd", p.astype(vcv.dtype), vcv,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_g)                         # (b,hkv,g,1), broadcasts
        l_g = jax.lax.psum(l * w, "model")
        acc_g = jax.lax.psum(acc * w, "model")
        out = (acc_g / jnp.maximum(l_g, 1e-30)).reshape(-1, h, 1, d)
        out = out.astype(qv.dtype)
        return (out, kcv, vcv) if with_update else (out,)

    zero = jnp.zeros((b, hkv, 1, d), k_cache.dtype)
    fn = jax.shard_map(local, mesh=mesh, check_vma=False,
                       in_specs=(qspec, cspec, cspec, qspec, qspec, P()),
                       out_specs=((qspec, cspec, cspec) if with_update
                                  else (qspec,)))
    res = fn(q, k_cache, v_cache,
             k_new if with_update else zero,
             v_new if with_update else zero, pos)
    return res if with_update else res[0]


def decode_attn(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                pos: jax.Array, *, sm_scale: Optional[float] = None,
                kv_chunk: int = 4096) -> jax.Array:
    """One-token attention: q (B,H,1,D); caches (B,Hkv,Smax,D); pos scalar int32.

    Entries at index > pos are masked (cache is valid on [0, pos]).

    Three tiers, chosen to match how sharding.py lays the cache out:
      * sequence-sharded cache (kv-heads don't divide the model axis):
        two-tier distributed flash-decode via shard_map (_decode_attn_sp),
      * long unsharded caches: local flash-decode scan (online-softmax carry
        keeps HLO traffic ~= cache bytes instead of full-length f32 scores),
      * short caches: single fused pass.
    """
    b, h, _, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    mesh = use_sp_decode(b, hkv, smax)
    if mesh is not None:
        return decode_attn_sp(q, k_cache, v_cache, pos, mesh, sm_scale=sm_scale)
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32) * sm_scale
    if smax <= kv_chunk or smax % kv_chunk:
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache.astype(jnp.float32))
        idx = jnp.arange(smax)[None, None, None, :]
        s = jnp.where(idx <= pos, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32)) / \
            jnp.maximum(l, 1e-30)
        return out.reshape(b, h, 1, d).astype(q.dtype)

    nc = smax // kv_chunk
    ks = k_cache.reshape(b, hkv, nc, kv_chunk, d).transpose(2, 0, 1, 3, 4)
    vs = v_cache.reshape(b, hkv, nc, kv_chunk, d).transpose(2, 0, 1, 3, 4)

    def step(carry, inp):
        m, l, acc = carry
        ki, vi, ic = inp
        s = jnp.einsum("bhgd,bhkd->bhgk", qg, ki.astype(jnp.float32))
        idx = ic * kv_chunk + jnp.arange(kv_chunk)[None, None, None, :]
        s = jnp.where(idx <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhgk,bhkd->bhgd", p,
                                       vi.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (ks, vs, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, 1, d).astype(q.dtype)


def update_cache(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Insert new (B,Hkv,T,D) at position ``pos`` along the cache's seq axis."""
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                        (0, 0, pos, 0))
