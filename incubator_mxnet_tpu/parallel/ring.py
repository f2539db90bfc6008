"""Ring attention: sequence/context parallelism over the mesh (first-class
here; the reference has NONE — SURVEY §5.7 marks this as a capability the
TPU build adds beyond parity.  Public technique: Liu et al., "Ring
Attention with Blockwise Transformers", and the jax shard_map collective
idioms from the scaling book).

Each device holds a sequence shard of Q/K/V.  K/V blocks rotate around the
ring via ``lax.ppermute`` (ICI neighbor exchange) while a flash-style
streaming softmax (running max + running sum) accumulates exact attention —
memory O(T_local), comm fully overlapped by XLA's async collectives.
"""
from __future__ import annotations

from functools import partial

from ..base import MXNetError

__all__ = ["ring_attention", "local_flash_attention",
           "ring_attention_nd"]


def local_flash_attention(q, k, v, scale=None, causal=False,
                          q_offset=0, k_offset=0, key_mask=None):
    """Single-device exact attention with numerically-stable softmax.

    q: (..., Tq, D), k/v: (..., Tk, D).  q_offset/k_offset are the global
    positions of the first query/key element — used by the ring schedule's
    causal masking.  ``key_mask``: optional (B, Tk) validity indicator
    (>0 = valid) broadcast over heads/queries.
    """
    import jax.numpy as jnp
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        tq, tk = q.shape[-2], k.shape[-2]
        qpos = q_offset + jnp.arange(tq)[:, None]
        kpos = k_offset + jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)  # fully-masked rows
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("...qk,...kd->...qd", p, v)
    return o / jnp.maximum(l, 1e-30)


def _ring_body_flash(q, k, v, kv_mask=None, *, axis_name, scale, causal):
    """Blockwise ring attention (Liu et al.'s full recipe): each ring
    step's LOCAL block runs through the Pallas flash kernel — the
    (T_local, T_local) score tile never materializes either — and
    blocks merge EXACTLY via their logsumexp:
    ``o <- w*o + w_b*o_b`` with ``w = exp(lse - logaddexp(lse, lse_b))``.
    Gradients flow through the merge because flash_attention_lse's
    custom_vjp accepts the lse cotangent (it folds into the kernels'
    dd term).  Requires (B, H, T_local, D) inputs.

    Causal cross-shard structure is data-dependent inside the loop
    (src vs my): handled with lax.switch over {full block, diagonal
    (causal) block, empty block} — all three branches trace the same
    shapes, SPMD-uniform."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ..kernels.flash_attention import flash_attention_lse

    if q.ndim != 4:
        raise MXNetError(
            "blockwise ring attention needs (B, H, T_local, D) inputs")
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    o0 = jnp.zeros(q.shape[:-1] + (v.shape[-1],), jnp.float32)
    lse0 = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)

    def blk(k_cur, v_cur, mask_cur, src):
        def run(causal_blk):
            o, l = flash_attention_lse(q, k_cur, v_cur, scale=scale,
                                       causal=causal_blk, mask=mask_cur)
            return o.astype(jnp.float32), l

        if not causal:
            return run(False)

        def full_blk():
            return run(False)

        def diag_blk():
            return run(True)

        def empty_blk():          # src > my: entirely in the future
            return jnp.zeros_like(o0), jnp.full(lse0.shape, -jnp.inf,
                                                jnp.float32)

        idx = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
        return lax.switch(idx, [full_blk, diag_blk, empty_blk])

    def body(step, carry):
        o, lse, k_cur, v_cur, mask_cur = carry
        src = (my - step) % n
        o_b, lse_b = blk(k_cur, v_cur, mask_cur, src)
        lse_new = jnp.logaddexp(lse, lse_b)
        safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
        w_o = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - safe))
        w_b = jnp.where(jnp.isneginf(lse_b), 0.0, jnp.exp(lse_b - safe))
        o = o * w_o[..., None] + o_b * w_b[..., None]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        mask_next = (None if mask_cur is None
                     else lax.ppermute(mask_cur, axis_name, perm))
        return o, lse_new, k_next, v_next, mask_next

    o, _, *_ = lax.fori_loop(0, n, body, (o0, lse0, k, v, kv_mask),
                             unroll=True)
    return o.astype(q.dtype)


def _ring_body(q, k, v, kv_mask=None, *, axis_name, scale, causal,
               use_flash=False):
    if use_flash:
        return _ring_body_flash(q, k, v, kv_mask, axis_name=axis_name,
                                scale=scale, causal=causal)
    return _ring_body_einsum(q, k, v, kv_mask, axis_name=axis_name,
                             scale=scale, causal=causal)


def _ring_body_einsum(q, k, v, kv_mask=None, *, axis_name, scale, causal):
    """Per-shard ring schedule (runs inside shard_map).

    ``kv_mask``: optional (B, T_local) key-validity indicator (>0 = valid),
    sequence-sharded like K/V; it rotates around the ring with them so
    padded keys stay masked on every device.  q/k/v are (B, H, T_local, D)
    when a mask is given, else any (..., T_local, D)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)              # ring size
    my = lax.axis_index(axis_name)
    t_local = q.shape[-2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    o = jnp.zeros(q.shape[:-1] + (v.shape[-1],), q.dtype)
    m = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)

    def body(step, carry):
        o, m, l, k_cur, v_cur, mask_cur = carry
        src = (my - step) % n                # whose K/V block we hold now
        s = jnp.einsum("...qd,...kd->...qk", q, k_cur).astype(jnp.float32) \
            * scale
        if causal:
            qpos = my * t_local + jnp.arange(t_local)[:, None]
            kpos = src * t_local + jnp.arange(t_local)[None, :]
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        if mask_cur is not None:
            s = jnp.where(mask_cur[:, None, None, :] > 0, s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.where(jnp.isneginf(m), m_safe, m) - m_safe)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None].astype(o.dtype) + \
            jnp.einsum("...qk,...kd->...qd", p.astype(v_cur.dtype), v_cur)
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        mask_next = (None if mask_cur is None
                     else lax.ppermute(mask_cur, axis_name, perm))
        return o_new, m_new, l_new, k_next, v_next, mask_next

    o, m, l, *_ = lax.fori_loop(0, n, body, (o, m, l, k, v, kv_mask))
    return (o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype))


def ring_attention(q, k, v, mesh=None, axis_name="seq", scale=None,
                   causal=False, use_flash=False):
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    q/k/v: (batch, heads, T, D) with T sharded over the mesh axis.
    Returns attention output with the same sharding.  Accepts jax arrays or
    NDArrays; batch/head dims may additionally be sharded over other axes.
    ``use_flash=True`` runs each ring step's local block through the
    Pallas flash kernel (blockwise ring attention — O(T_local) memory
    within the block as well); results are numerically the same path.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from . import mesh as mesh_mod
    from ..ndarray.ndarray import NDArray

    mesh = mesh or mesh_mod.current_mesh()
    if mesh is None:
        raise MXNetError("ring_attention needs a mesh")
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    wrap = isinstance(q, NDArray)
    if wrap:
        q, k, v = q._data, k._data, v._data
    if scale is None:
        scale = q.shape[-1] ** -0.5

    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(_ring_body, axis_name=axis_name, scale=scale,
                causal=causal, use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v)
    if wrap:
        return NDArray(out)
    return out


def ring_attention_nd(q, k, v, mesh=None, axis_name="seq", scale=None,
                      causal=False):
    """NDArray-facing alias (mx.nd layer integration)."""
    return ring_attention(q, k, v, mesh=mesh, axis_name=axis_name,
                          scale=scale, causal=causal)
