"""Flash attention as a Pallas TPU kernel.

Reference analog: the reference's fused-kernel tier (NVRTC pointwise
fusion `src/operator/fusion/fused_op.*` + cuDNN attention in its era) —
re-designed for TPU: an online-softmax (FlashAttention-2 style) kernel
that streams K/V blocks through VMEM, never materializing the (T, T)
score matrix in HBM.  The MXU does the two matmuls per block; running
max/sum rescaling happens on the VPU.

Scope/contract:
* forward AND backward are Pallas online-softmax kernels
  (FlashAttention-2): the forward also emits the per-row logsumexp, the
  backward recomputes P = exp(S - LSE) blockwise — dQ in a
  query-parallel kernel, dK/dV (+ the key-bias cotangent) in a
  key-parallel kernel — so the (T, T) score matrix exists in neither
  direction.  ``MXNET_FLASH_BWD=xla`` switches the backward to the
  XLA-recompute path, kept as the numerics oracle
  (tests/test_flash_attention.py grad-checks pallas vs xla);
* dense (non-causal or causal) attention, with an optional (B, Tk) 0/1
  key-validity mask (the shape every padded BERT batch carries as
  ``valid_length``) applied as an additive -1e30 bias streamed through
  VMEM per K block; rows must keep >= 1 valid key (valid_length >= 1),
  same contract as the XLA path.  Arbitrary (Tq, Tk) score masks are NOT
  supported — those callers use the XLA path;
* K/V for one (batch, head) stay VMEM-resident and are block-streamed
  from there, so the (T, T) score matrix never exists but T is bounded
  by the VMEM budget (~8MB for K+V).  Longer sequences fall back to XLA
  here; the genuinely long-context path is ring attention over the mesh
  (parallel/ring.py), which shards T before kernels even run;
* the Pallas path engages only for TPU-tile-aligned shapes (T a multiple
  of 128); everything else falls back to XLA;
* on CPU backends the kernel runs in interpret mode, which keeps the
  numerics testable everywhere (tests/test_flash_attention.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["flash_attention", "flash_attention_lse",
           "paged_decode_attention", "paged_verify_decode_attention",
           "paged_attention_impl", "paged_run_pages", "paged_run_lengths",
           "prefill_attention",
           "paged_prefix_attention"]

_BLOCK_Q = 128
_BLOCK_K = 128
# keys one grid step of the paged decode kernel takes (a group of pool pages)
_PAGED_GROUP_KEYS = 128
# such groups one grid step of the GROUPED paged kernel takes: each a run —
# one copy where its blocks lie in a row in the pool — so that what a step
# costs whatever it reads is paid once for 512 keys
_PAGED_GQA_STEP_GROUPS = 4


def _platform_of(x):
    """The platform a kernel over ``x`` will run on — the one place every
    dispatcher below decides Pallas-vs-lax and compiled-vs-interpret
    from.  A concrete array says where it lives (a CPU-committed array in
    a TPU-default process must interpret); a tracer or a host numpy array
    has no devices, so the answer is the current default context's device
    — jax's default backend unless the caller is inside a ``with ctx:``
    scope.  A tracer is NOT asked: jax answers with an error whose message
    walks the tracer's whole ancestry, seconds a program (PERF.md, PR 44)."""
    if not isinstance(x, jax.core.Tracer) and hasattr(x, "devices"):
        return next(iter(x.devices())).platform
    from ..context import current_context
    return current_context().jax_device().platform


def _causal_mask(s, q0, k0):
    """-inf the strictly-upper-triangular scores of one (BQ, BK) block;
    ``q0``/``k0`` are the absolute positions of the block's first
    row/column.  Shared by the forward and both backward kernels."""
    bq, bk = s.shape
    iq = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ik = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(iq >= ik, s, -jnp.inf)


def _n_diag_blocks(qi, block_q, block_k, n_kb):
    """How many leading K blocks a causal query block (index ``qi``) can
    see: blocks past the diagonal contribute nothing."""
    return jnp.minimum(
        (qi * block_q + block_q + block_k - 1) // block_k, n_kb)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_k,
                seq_len, has_bias, with_lse):
    from jax.experimental import pallas as pl

    b_ref = rest[0] if has_bias else None
    lse_ref = rest[-1] if with_lse else None
    o_ref = rest[-2] if with_lse else rest[-1]
    q = q_ref[0].astype(jnp.float32) * scale          # (BQ, D)
    block_q = q.shape[0]
    qi = pl.program_id(1)
    n_kb = seq_len // block_k

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if has_bias:
            # (1, block_k) additive key bias (0 valid / -1e30 masked),
            # broadcast over the query rows
            s = s + b_ref[0, :, pl.ds(j * block_k, block_k)]
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # fully-masked rows (causal upper blocks) keep m=-inf: exp(-inf
        # - -inf) would be nan — pin those rows' correction to 0
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    if causal:
        n_needed = _n_diag_blocks(qi, block_q, block_k, n_kb)
        m, l, acc = jax.lax.fori_loop(0, n_needed, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if with_lse:
        # per-row logsumexp of the (scaled, biased, masked) scores — the
        # one residual the FA2 backward needs to recompute P blockwise
        safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
        lse_ref[0] = (safe_m + jnp.log(jnp.maximum(l, 1e-30))).reshape(
            1, block_q)


def _xla_attention(q, k, v, scale, causal, bias=None):
    """(BH, T, D) reference path; ``bias`` is an optional (BH, 1, Tk)
    additive score bias (0 valid / -1e30 masked)."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias
    if causal:
        T = q.shape[1]
        iq = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        s = jnp.where(iq[None] >= ik[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def _flash_fwd_impl(q, k, v, bias, scale, causal, interpret, n_heads,
                    with_lse=False):
    """``bias``: None, or a (B, 1, Tk) float32 additive key bias shared by
    the batch's ``n_heads`` grid rows (indexed bh -> bh // n_heads, so the
    per-head copies never materialize in HBM).  ``with_lse`` additionally
    returns the per-row logsumexp for the backward, as (BH, 1, T) float32
    — per-row vectors travel as lane-dense rows with a unit second-minor
    dim, the one layout whose (1, 1, block) blocks the TPU lowering
    accepts (a (1, block) block over (BH, T) is refused)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    block_q = min(_BLOCK_Q, T)
    block_k = min(_BLOCK_K, T)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_len=T,
                               has_bias=bias is not None,
                               with_lse=with_lse)
    grid = (BH, T // block_q)
    spec_q = pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                          memory_space=pltpu.VMEM)
    spec_kv = pl.BlockSpec((1, T, D), lambda bh, qi: (bh, 0, 0),
                           memory_space=pltpu.VMEM)
    in_specs = [spec_q, spec_kv, spec_kv]
    operands = [q, k, v]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, T), lambda bh, qi: (bh // n_heads, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(bias)
    out_shape = jax.ShapeDtypeStruct((BH, T, D), q.dtype)
    out_specs = spec_q
    if with_lse:
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)]
        out_specs = [spec_q,
                     pl.BlockSpec((1, 1, block_q),
                                  lambda bh, qi: (bh, 0, qi),
                                  memory_space=pltpu.VMEM)]
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )(*operands)


def _bwd_dq_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref, *rest,
                   scale, causal, block_k, seq_len, has_bias):
    """Query-parallel dQ: stream K/V blocks, recompute P from the saved
    logsumexp, accumulate dQ = sum_j (P * (dP - D)) @ K * scale."""
    from jax.experimental import pallas as pl

    b_ref = rest[0] if has_bias else None
    dq_ref = rest[-1]
    q = q_ref[0].astype(jnp.float32)                  # (BQ, D)
    do = do_ref[0].astype(jnp.float32)
    block_q = q.shape[0]
    lse = lse_ref[0].reshape(block_q, 1)              # rows -> columns
    dd = dd_ref[0].reshape(block_q, 1)
    qi = pl.program_id(1)
    n_kb = seq_len // block_k

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * scale
        if has_bias:
            s = s + b_ref[0, :, pl.ds(j * block_k, block_k)]
        if causal:
            s = _causal_mask(s, qi * block_q, j * block_k)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd)                            # (BQ, BK)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    dq0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    if causal:
        n_needed = _n_diag_blocks(qi, block_q, block_k, n_kb)
        dq = jax.lax.fori_loop(0, n_needed, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, n_kb, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, dd_ref, k_ref, v_ref, *rest,
                    scale, causal, block_q, seq_len, has_bias):
    """Key-parallel dK/dV (+ key-bias cotangent rows): stream Q/dO
    blocks over one K/V block, recomputing P from the logsumexp.
    dV = P^T dO;  dK = (P * (dP - D))^T Q * scale;
    dbias_rows = sum_rows(P * (dP - D))."""
    from jax.experimental import pallas as pl

    b_ref = rest[0] if has_bias else None
    dk_ref, dv_ref, dbs_ref = rest[-3], rest[-2], rest[-1]
    k = k_ref[0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    block_k = k.shape[0]
    kj = pl.program_id(1)
    n_qb = seq_len // block_q

    def body(i, carry):
        dk, dv, dbs = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, :, pl.ds(i * block_q, block_q)].reshape(
            block_q, 1)
        dd = dd_ref[0, :, pl.ds(i * block_q, block_q)].reshape(block_q, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * scale
        if has_bias:
            s = s + b_ref[0]                          # (1, BK) broadcast
        if causal:
            s = _causal_mask(s, i * block_q, kj * block_k)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (BK, D)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dd)                            # (BQ, BK)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dbs = dbs + jnp.sum(ds, axis=0, keepdims=True)    # (1, BK)
        return dk, dv, dbs

    z = jnp.zeros((block_k, k.shape[1]), jnp.float32)
    carry0 = (z, z, jnp.zeros((1, block_k), jnp.float32))
    if causal:
        # q blocks strictly above the diagonal see this k block masked out
        start = (kj * block_k) // block_q
        dk, dv, dbs = jax.lax.fori_loop(start, n_qb, body, carry0)
    else:
        dk, dv, dbs = jax.lax.fori_loop(0, n_qb, body, carry0)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dbs_ref[0] = dbs


def _flash_bwd_impl(q, k, v, bias, out, lse, g, scale, causal, interpret,
                    n_heads, g_lse=None):
    """FA2 backward as two Pallas kernels; returns (dq, dk, dv, dbias).

    ``g_lse``: optional cotangent of the logsumexp output (the
    with-lse variant used by blockwise ring attention).  It folds into
    the existing kernels with NO kernel change: ds = p*(dp - dd) and
    d(lse_i)/d(s_ij) = p_ij, so the lse term is exactly dd -> dd - g_lse
    (dv = p^T dO is lse-independent and untouched)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    block_q = min(_BLOCK_Q, T)
    block_k = min(_BLOCK_K, T)
    has_bias = bias is not None
    # D_i = rowsum(dO * O): tiny elementwise reduce, XLA fuses it.
    # (BH, 1, T) like lse — see _flash_fwd_impl for the layout's why
    dd = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                 -1)[:, None, :]
    if g_lse is not None:
        dd = dd - g_lse.astype(jnp.float32)

    spec_row_q = pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0),
                              memory_space=pltpu.VMEM)
    spec_full = pl.BlockSpec((1, T, D), lambda bh, i: (bh, 0, 0),
                             memory_space=pltpu.VMEM)
    spec_vec_q = pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i),
                              memory_space=pltpu.VMEM)
    spec_vec_full = pl.BlockSpec((1, 1, T), lambda bh, i: (bh, 0, 0),
                                 memory_space=pltpu.VMEM)

    # dQ: grid over query blocks
    in_specs = [spec_row_q, spec_row_q, spec_vec_q, spec_vec_q,
                spec_full, spec_full]
    operands = [q, g, lse, dd, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, T), lambda bh, i: (bh // n_heads, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(bias)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_len=T, has_bias=has_bias),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        grid=(BH, T // block_q),
        in_specs=in_specs,
        out_specs=spec_row_q,
        interpret=interpret,
    )(*operands)

    # dK/dV (+ bias-cotangent rows): grid over key blocks
    spec_row_k = pl.BlockSpec((1, block_k, D), lambda bh, j: (bh, j, 0),
                              memory_space=pltpu.VMEM)
    spec_vec_k = pl.BlockSpec((1, 1, block_k), lambda bh, j: (bh, 0, j),
                              memory_space=pltpu.VMEM)
    in_specs = [spec_full, spec_full, spec_vec_full, spec_vec_full,
                spec_row_k, spec_row_k]
    operands = [q, g, lse, dd, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, j: (bh // n_heads, 0, j),
            memory_space=pltpu.VMEM))
        operands.append(bias)
    dk, dv, dbs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_len=T, has_bias=has_bias),
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, T, D), v.dtype),
                   jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)],
        grid=(BH, T // block_k),
        in_specs=in_specs,
        out_specs=[spec_row_k, spec_row_k, spec_vec_k],
        interpret=interpret,
    )(*operands)

    dbias = None
    if has_bias:
        # (BH, 1, Tk) rows -> the (B, 1, Tk) bias: sum the head axis out
        dbias = dbs.reshape(-1, n_heads, 1, T).sum(1)
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, scale, causal, interpret, n_heads):
    """One custom_vjp covers both paths: ``bias`` is None (dense) or the
    (B, 1, Tk) additive key bias (None is an empty pytree to JAX, so the
    masked/unmasked cases share this plumbing)."""
    return _flash_fwd_impl(q, k, v, bias, scale, causal, interpret,
                           n_heads)


def _flash_fwd(q, k, v, bias, scale, causal, interpret, n_heads):
    out, lse = _flash_fwd_impl(q, k, v, bias, scale, causal, interpret,
                               n_heads, with_lse=True)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(scale, causal, interpret, n_heads, res, g):
    q, k, v, bias, out, lse = res
    from ..base import getenv
    # read at TRACE time: an already-jitted step keeps whichever backward
    # it was traced with (docs/env_var.md) — set before the first trace
    if (getenv("MXNET_FLASH_BWD") or "pallas").lower() != "xla":
        dq, dk, dv, dbias = _flash_bwd_impl(
            q, k, v, bias, out, lse, g, scale, causal, interpret, n_heads)
        return dq, dk, dv, dbias
    # MXNET_FLASH_BWD=xla — the recompute oracle: same math, standard
    # memory, autodiffed under XLA
    BH = q.shape[0]
    if bias is None:
        _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(
            q_, k_, v_, scale, causal), q, k, v)
        return vjp(g) + (None,)
    # broadcast the (B, 1, Tk) bias to the (BH, 1, Tk) the reference path
    # wants, summing the head axis back out of its cotangent
    def ref(q_, k_, v_, b_):
        bb = jnp.broadcast_to(
            b_[:, None], (b_.shape[0], n_heads) + b_.shape[1:]).reshape(
                (BH,) + b_.shape[1:])
        return _xla_attention(q_, k_, v_, scale, causal, bias=bb)
    _, vjp = jax.vjp(ref, q, k, v, bias)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_lse(q, k, v, bias, scale, causal, interpret, n_heads):
    """Variant exposing (out, lse) as OUTPUTS — the building block of
    blockwise ring attention, whose cross-shard merge needs each
    block's logsumexp (and gradients through it)."""
    return _flash_fwd_impl(q, k, v, bias, scale, causal, interpret,
                           n_heads, with_lse=True)


def _flash_lse_fwd(q, k, v, bias, scale, causal, interpret, n_heads):
    out, lse = _flash_fwd_impl(q, k, v, bias, scale, causal, interpret,
                               n_heads, with_lse=True)
    return (out, lse), (q, k, v, bias, out, lse)


def _flash_lse_bwd(scale, causal, interpret, n_heads, res, g):
    q, k, v, bias, out, lse = res
    g_out, g_lse = g
    from ..base import getenv
    if (getenv("MXNET_FLASH_BWD") or "pallas").lower() != "xla":
        return _flash_bwd_impl(q, k, v, bias, out, lse, g_out, scale,
                               causal, interpret, n_heads, g_lse=g_lse)
    g_lse = g_lse[:, 0, :]      # the oracle's lse is (BH, T)
    # MXNET_FLASH_BWD=xla — the recompute oracle (same switch as the
    # no-lse path; AD produces the g_lse term naturally here)
    BH = q.shape[0]

    def ref(q_, k_, v_, b_):
        bb = None
        if b_ is not None:
            bb = jnp.broadcast_to(
                b_[:, None], (b_.shape[0], n_heads) + b_.shape[1:]
            ).reshape((BH,) + b_.shape[1:])
        return _xla_attention_lse(q_, k_, v_, scale, causal, bias=bb)

    if bias is None:
        _, vjp = jax.vjp(lambda q_, k_, v_: ref(q_, k_, v_, None),
                         q, k, v)
        return vjp((g_out, g_lse)) + (None,)
    _, vjp = jax.vjp(ref, q, k, v, bias)
    return vjp((g_out, g_lse))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _xla_attention_lse(q, k, v, scale, causal, bias=None):
    """(BH, T, D) reference path returning (out, lse) — differentiable
    by plain AD; the odd-shape fallback of flash_attention_lse."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias
    if causal:
        T = q.shape[1]
        iq = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        ik = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        s = jnp.where(iq[None] >= ik[None], s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(
        q.dtype)
    return out, lse


def flash_attention_lse(q, k, v, scale=None, causal=False, mask=None):
    """Like :func:`flash_attention` but ALSO returns the per-row
    logsumexp: (out (B, H, T, D), lse (B, H, T)).  Gradients flow
    through both outputs (the lse cotangent folds into the kernels'
    dd term).  Used by blockwise ring attention to merge per-shard
    blocks exactly; same tile-alignment gate and XLA fallback as
    flash_attention (one dispatcher)."""
    return _dispatch(q, k, v, scale, causal, mask, with_lse=True)


def _dispatch(q, k, v, scale, causal, mask, with_lse):
    """ONE dispatcher for both public entry points: mask→bias encoding,
    the tile-alignment + VMEM gate, and platform/interpret detection
    live here once (they had already drifted when duplicated)."""
    B, H, T, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    bias = None
    if mask is not None:
        bias = jnp.where(mask > 0, 0.0, -1e30).astype(
            jnp.float32).reshape(B, 1, T)
    qf, kf, vf = (x.reshape(B * H, T, D) for x in (q, k, v))
    kv_bytes = 2 * T * D * q.dtype.itemsize
    if T % _BLOCK_Q or kv_bytes > 8 * 2 ** 20:
        # not tile-aligned, or K+V would blow the VMEM budget: XLA path
        bb = None if bias is None else jnp.broadcast_to(
            bias[:, None], (B, H, 1, T)).reshape(B * H, 1, T)
        if with_lse:
            out, lse = _xla_attention_lse(qf, kf, vf, scale, causal,
                                          bias=bb)
            return out.reshape(B, H, T, D), lse.reshape(B, H, T)
        return _xla_attention(qf, kf, vf, scale, causal,
                              bias=bb).reshape(B, H, T, D)
    interpret = _platform_of(q) == "cpu"
    if with_lse:
        out, lse = _flash_lse(qf, kf, vf, bias, scale, causal,
                              interpret, H)
        return out.reshape(B, H, T, D), lse.reshape(B, H, T)
    out = _flash(qf, kf, vf, bias, scale, causal, interpret, H)
    return out.reshape(B, H, T, D)


def flash_attention(q, k, v, scale=None, causal=False, mask=None):
    """Online-softmax attention over (B, H, T, D) jax arrays.

    ``mask``: optional (B, Tk) key-validity array (nonzero = attend), the
    ``valid_length``-derived mask every padded batch carries; rows must
    keep >= 1 valid key.  Falls back to the XLA implementation when shapes
    don't fit the kernel contract (T not divisible by the block size)."""
    return _dispatch(q, k, v, scale, causal, mask, with_lse=False)


def _by_query_chunks(rows, q, axis, chunk=512):
    """``rows(q's queries i0 .. i0 + chunk, i0)`` over ``q``'s query
    ``axis`` 512 at a time, so that a long prompt's (T, T) scores never
    exist at once; all at once where they are few or do not divide."""
    T = q.shape[axis]
    if T <= chunk or T % chunk:
        return rows(q, 0)
    n = T // chunk
    qs = jnp.moveaxis(q.reshape(q.shape[:axis] + (n, chunk)
                                + q.shape[axis + 1:]), axis, 0)
    out = jax.lax.map(lambda a: rows(a[0], a[1]),
                      (qs, jnp.arange(n, dtype=jnp.int32) * chunk))
    return jnp.moveaxis(out, 0, axis).reshape(q.shape)


def prefill_attention(q, k, v, window=None, scale=None):
    """Causal attention of a whole prompt in the layout a served layer
    holds it: ``q`` (B, T, Hq, D), ``k``/``v`` (B, T, Hkv, D) with
    ``Hq`` a multiple of ``Hkv`` (each group of ``Hq // Hkv`` query heads
    reads one KV head), keys ``j`` with ``i - window < j <= i`` (all
    ``j <= i`` without a window).  Returns (B, T, Hq, D) in ``q``'s type.

    The lax path: scores and softmax in float32, the products with the
    operands' own type accumulated in float32, queries taken 512 at a
    time (:func:`_by_query_chunks`).  The
    Pallas flash kernels above take neither groups nor a window."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    kidx = jnp.arange(T, dtype=jnp.int32)[None, :]

    def rows(q_rows, i0):
        """q_rows (B, Tq, Hq, D), whose first row is query ``i0``."""
        Tq = q_rows.shape[1]
        qg = q_rows.reshape(B, Tq, Hkv, G, D)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                       preferred_element_type=jnp.float32) * scale
        qidx = (i0 + jnp.arange(Tq, dtype=jnp.int32))[:, None]
        live = kidx <= qidx
        if window is not None:
            live = live & (kidx > qidx - int(window))
        s = jnp.where(live[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, Tq, Hq, D).astype(q.dtype)

    return _by_query_chunks(rows, q, 1)


# ---------------------------------------------------------------------------
# decode-shaped attention: one query position per slot over a
# contiguous (S, H, T, D) strip of keys — the body of the lax paged path
# (which gathers each slot's blocks into such a strip) and the reference
# the paged kernels are tested against.
# ---------------------------------------------------------------------------

def _xla_decode_attention(q, k, v, positions, scale):
    """(S, H, D) single-position attention over (S, H, T, D) caches.
    Per-slot ``positions`` mask out cache entries beyond each slot's
    write head (entries > position are stale/garbage by contract)."""
    T = k.shape[2]
    s = jnp.einsum("shd,shtd->sht", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    live = jnp.arange(T, dtype=jnp.int32)[None, None, :] \
        <= positions[:, None, None]
    s = jnp.where(live, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("sht,shtd->shd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode attention (the GenerationEngine's per-step attention): the
# KV cache lives in fixed-size blocks (serving/kvcache.py BlockPool) and
# each slot reads through an int32 block table.
# ---------------------------------------------------------------------------

def _pool_dims(pages, position_major):
    """``(num_blocks, H, block_size, stored features)`` of a pool stored
    as stated, ``[N, H, bs, D]``, or position-major, ``[N, bs, H, Dp]``
    (``serving.kvcache.KVLayout.pool_shape``)."""
    n, a, b, d = pages.shape
    return (n, b, a, d) if position_major else (n, a, b, d)


def _dense_view(pages, tables, position_major=False, head_dim=None):
    """Each slot's blocks gathered into a dense strip: ``pages``
    (num_blocks, H, block_size, D) through ``tables`` (S, max_blocks) ->
    (S, H, max_blocks * block_size, D); of a position-major pool
    (num_blocks, block_size, H, Dp), its first ``head_dim`` features."""
    S, nb = tables.shape
    if position_major:
        _, bs, H, Dp = pages.shape
        return jax.lax.slice_in_dim(
            jnp.moveaxis(pages[tables].reshape(S, nb * bs, H, Dp), 2, 1),
            0, head_dim, axis=3)
    _, H, bs, D = pages.shape
    return jnp.moveaxis(pages[tables], 2, 1).reshape(S, H, nb * bs, D)


def _xla_paged_decode_attention(q, k_pages, v_pages, tables, positions,
                                scale, window=None, position_major=False):
    """Gather each slot's blocks into a dense (S, H, T, D) view and reuse
    :func:`_xla_decode_attention` verbatim.  Masked (stale / null-block)
    positions contribute exact-zero softmax weight, so the result is
    bit-identical to attention over the valid entries alone.  Grouped
    heads or a window take :func:`_xla_grouped_decode_attention` over the
    same view."""
    H = _pool_dims(k_pages, position_major)[1]
    k = _dense_view(k_pages, tables, position_major, q.shape[-1])
    v = _dense_view(v_pages, tables, position_major, q.shape[-1])
    if window is None and q.shape[1] == H:
        return _xla_decode_attention(q, k, v, positions, scale)
    return _xla_grouped_decode_attention(
        q[:, :, None, :], k, v, positions, scale, window)[:, :, 0, :]


def _paged_kernel_kind(q, k_pages, q_heads, window, position_major=False):
    """``"mha"`` (:func:`_paged_kernel`), ``"gqa"``
    (:func:`_paged_gqa_kernel`) or None (the lax gather) for a paged call
    with ``q_heads`` query heads and a ``window`` (or None) over the pool
    ``k_pages``; ``q`` names the platform."""
    from ..base import getenv_bool
    if _platform_of(q) != "tpu" \
            and not getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
        return None
    _, H, bs, D = _pool_dims(k_pages, position_major)
    f32 = k_pages.dtype == jnp.float32
    if int(q_heads) == H and window is None:
        return "mha" if f32 and bs % 8 == 0 and D % 8 == 0 else None
    if position_major:          # the grouped kernel takes the stated shape
        return None
    if int(q_heads) % H == 0 and D % 128 == 0 and (
            (f32 and bs % 8 == 0)
            or (k_pages.dtype == jnp.bfloat16 and bs % 16 == 0)):
        return "gqa"
    return None


def _paged_group_pages(block_size, n_cols):
    """Pool blocks one step of a paged kernel's work list takes:
    ``_PAGED_GROUP_KEYS`` keys' worth, the whole table where it is
    shorter."""
    return min(max(1, _PAGED_GROUP_KEYS // int(block_size)), int(n_cols))


def _paged_gqa_step(block_size, n_cols):
    """``(pages a group, groups a step)`` of the grouped kernel's work
    list over tables of ``n_cols`` columns."""
    pages = _paged_group_pages(block_size, n_cols)
    return pages, min(_PAGED_GQA_STEP_GROUPS, -(-int(n_cols) // pages))


def paged_run_pages(q, k_pages, q_heads, window, n_cols,
                    position_major=False):
    """``(pages a group, groups a step)`` for a paged call that takes the
    grouped kernel — the one that fetches a group's blocks in ONE copy
    where the table names them in a row (:func:`paged_run_lengths`) — and
    None for a call that takes another kernel or the gather (arguments as
    :func:`paged_attention_impl`'s; ``n_cols`` the table's columns).  What
    the engine's ``mxtpu_paged_groups_total`` is counted by."""
    if _paged_kernel_kind(q, k_pages, q_heads, window,
                          position_major) != "gqa":
        return None
    return _paged_gqa_step(k_pages.shape[2], n_cols)


def paged_run_lengths(table, n_pages, pool_blocks):
    """The host's half of the grouped kernel's run flags
    (:func:`_paged_work_list`), in numpy: for each group of ``n_pages``
    columns of one block ``table``, how many of its leading columns name
    consecutive blocks, ``table[c + j] == table[c] + j`` — 0 where
    ``n_pages`` blocks from ``table[c]`` would pass the pool's end.  A
    step whose write head leaves ``n`` columns of the group live is a run
    iff ``n <=`` the group's entry."""
    import numpy as np
    table = np.asarray(table, np.int64)
    n_groups = -(-len(table) // n_pages)
    ids = np.full(n_groups * n_pages, -1, np.int64)
    ids[:len(table)] = table
    ids = ids.reshape(n_groups, n_pages)
    in_a_row = ids == ids[:, :1] + np.arange(n_pages)
    lengths = np.where(in_a_row.all(axis=1), n_pages,
                       np.argmin(in_a_row, axis=1))
    return np.where(ids[:, 0] + n_pages <= pool_blocks, lengths, 0)


def paged_attention_impl(q, k_pages, q_heads=None, window=None,
                         position_major=False):
    """Which implementation the two paged entry points trace for a call
    with operand ``q`` (any of them: it names the platform), ``q_heads``
    query heads (default: as many as the pool has) and a ``window`` over
    the pool ``k_pages`` (num_blocks, H, block_size, D):
    ``"pallas"`` — a kernel that reads the pool in place, live blocks
    only — on a TPU, else ``"lax_gather"``, the dense gather (the CPU
    path, and the reference the kernels are tested against).  Two kernels
    exist: one query head a KV head, no window, over a float32 pool with
    ``block_size`` and ``D`` multiples of 8 (:func:`_paged_kernel`, the
    VPU); and ``Hq / H`` query heads on each of ``H`` KV heads — a
    grouped-query layer, or a chip's share of one — with or without a
    window, over a float32 or bfloat16 pool stored as stated whose page
    is whole tiles (``D`` a multiple of 128, ``block_size`` of 8, of 16
    for bfloat16; :func:`_paged_gqa_kernel`, the MXU; one query head a KV
    head with a window, or over a bfloat16 pool, takes it too).  Anything
    else takes the gather.  Decided from what is
    visible at trace time, never from the environment;
    ``MXNET_FA_DECODE_FORCE_PALLAS=1`` is the test hook that takes the
    kernel (interpreted on a CPU) wherever the shapes allow it.
    ``GenerationEngine.program_inventory()`` reports it.  A pool stored
    ``position_major`` (num_blocks, block_size, H, Dp: features padded to
    whole lanes, ``KVLayout.pool_shape``) is read by the same kernel, its
    pages as they lie, or by the same gather."""
    kind = _paged_kernel_kind(
        q, k_pages, _pool_dims(k_pages, position_major)[1]
        if q_heads is None else q_heads, window, position_major)
    return "pallas" if kind else "lax_gather"


def _paged_pallas(q, k_pages, v_pages, tables, positions, scale, window,
                  position_major=False):
    """``q`` (S, Hq, Q, D) through the kernel that takes the call, or None
    where none does."""
    kind = _paged_kernel_kind(q, k_pages, q.shape[1], window,
                              position_major)
    if kind is None:
        return None
    interpret = _platform_of(q) == "cpu"
    if kind == "mha":
        return _paged_verify_pallas(q, k_pages, v_pages, tables, positions,
                                    scale, interpret=interpret,
                                    position_major=position_major)
    return _paged_gqa_pallas(q, k_pages, v_pages, tables, positions, scale,
                             None if window is None else int(window),
                             interpret)


def paged_decode_attention(q, k_pages, v_pages, tables, positions,
                           scale=None, window=None, position_major=False):
    """Per-slot single-position attention over a PAGED KV cache.

    ``q`` (S, Hq, D): this step's query; ``k_pages``/``v_pages``
    (num_blocks, H, block_size, D): the block pool, already holding this
    position's K/V (``Hq`` a multiple of ``H``: each group of query heads
    reads one KV head); ``tables`` (S, max_blocks) int32: each slot's
    block table, padded with the null block 0; ``positions`` (S,) int32:
    each slot's current write head in logical token coordinates.  Attends
    over logical positions ``<= positions[s]`` — with a ``window`` (a
    static int), over ``positions[s] - window < t <= positions[s]`` only,
    and the kernel's work list starts at the window's first block — and
    returns (S, Hq, D).

    The position mask is the load-bearing contract for **scanned decode
    bursts** (``GenerationEngine.decode_burst``): ``positions`` may be a
    traced value riding a ``lax.scan`` carry — per-slot, data-dependent,
    frozen for finished slots — not just a host constant.  Every
    implementation masks strictly by comparison against ``positions``
    (never by python-level slicing on its value), so a slot frozen
    mid-burst attends over exactly its old prefix while its redirected
    null-block writes stay invisible.

    :func:`paged_attention_impl` picks the implementation at trace time:
    a Pallas kernel (single-query decode IS verify at query width 1)
    or the lax gather.  ``position_major``: the pools are stored
    (num_blocks, block_size, H, Dp), as ``KVLayout.pool_shape`` has them
    where that is how they rest row-major."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out = _paged_pallas(q[:, :, None, :], k_pages, v_pages, tables,
                        positions, scale, window, position_major)
    if out is not None:
        return out[:, :, 0, :]
    return _xla_paged_decode_attention(q, k_pages, v_pages, tables,
                                       positions, scale, window,
                                       position_major)


# ---------------------------------------------------------------------------
# verify-shaped attention: a k+1-wide query block per slot over the same
# strips and pools — the speculative-decode verify program scores every
# drafted position in ONE dispatch.  Query row j of slot s sits at logical
# position positions[s] + j, so the mask is causal-within-the-block on
# top of the per-slot length mask the single-query kernels already use.
# ---------------------------------------------------------------------------

def _xla_verify_decode_attention(q, k, v, positions, scale):
    """(S, H, Q, D) query-block attention over (S, H, T, D) caches.
    ``positions`` (S,) is the base position of query row 0; row j attends
    keys ``<= positions[s] + j`` (causal inside the block, stale entries
    beyond each row's head masked exactly like single-query decode)."""
    S, H, Q, D = q.shape
    T = k.shape[2]
    s = jnp.einsum("shqd,shtd->shqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    key_idx = jnp.arange(T, dtype=jnp.int32)
    qpos = positions[:, None].astype(jnp.int32) \
        + jnp.arange(Q, dtype=jnp.int32)[None, :]          # (S, Q)
    live = key_idx[None, None, None, :] <= qpos[:, None, :, None]
    s = jnp.where(live, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("shqt,shtd->shqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _xla_grouped_decode_attention(q, k, v, positions, scale, window):
    """:func:`_xla_verify_decode_attention` for grouped heads and a
    window: ``q`` (S, Hq, Q, D) over ``k``/``v`` (S, Hkv, T, D), each
    group of ``Hq // Hkv`` query heads on one KV head; row j attends keys
    ``t`` with ``head - window < t <= head``, ``head = positions[s] + j``
    (no lower bound without a window).  Scores and softmax in float32;
    the products take the cache in its own type (a bfloat16 pool is not
    upcast whole) and accumulate in float32."""
    S, Hq, Q, D = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    qg = q.reshape(S, Hkv, Hq // Hkv, Q, D).astype(k.dtype)
    s = jnp.einsum("skgqd,sktd->skgqt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    key_idx = jnp.arange(T, dtype=jnp.int32)
    head = positions[:, None].astype(jnp.int32) \
        + jnp.arange(Q, dtype=jnp.int32)[None, :]              # (S, Q)
    live = key_idx[None, None, :] <= head[:, :, None]          # (S, Q, T)
    if window is not None:
        live = live & (key_idx[None, None, :] > head[:, :, None]
                       - int(window))
    s = jnp.where(live[:, None, None], s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("skgqt,sktd->skgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(S, Hq, Q, D).astype(q.dtype)


def _xla_paged_verify_decode_attention(q, k_pages, v_pages, tables,
                                       positions, scale, window=None,
                                       position_major=False):
    """Gather each slot's blocks into a dense (S, H, T, D) view and reuse
    :func:`_xla_verify_decode_attention` verbatim (same bit-identity
    argument as the single-query paged gather); grouped heads or a window
    take :func:`_xla_grouped_decode_attention`."""
    H = _pool_dims(k_pages, position_major)[1]
    k = _dense_view(k_pages, tables, position_major, q.shape[-1])
    v = _dense_view(v_pages, tables, position_major, q.shape[-1])
    if window is None and q.shape[1] == H:
        return _xla_verify_decode_attention(q, k, v, positions, scale)
    return _xla_grouped_decode_attention(q, k, v, positions, scale, window)


def _paged_kernel(slot_ref, group_ref, page_ref, pos_ref, q_ref, *refs,
                  scale, n_pages, n_cols):
    """One step of :func:`_paged_verify_pallas`'s work list: every head
    of slot ``slot_ref[i]`` against its ``group_ref[i]``-th group of
    ``n_pages`` pool pages, online softmax across a slot's consecutive
    steps.  ``refs``: the group's K pages, then its V pages — each a
    whole (1, bs, H, D) pool block the index maps picked from
    ``page_ref`` — the output block and the three scratch buffers.

    A page holds (H, D) tiles, one per key: the scores' sum over D is the
    only reduction across lanes, everything over keys runs down the
    leading axis on whole registers, and no operand is ever rounded —
    the float32 pool meets float32 arithmetic, with no MXU pass."""
    from jax.experimental import pallas as pl
    del page_ref                        # the index maps' business
    k_refs, v_refs = refs[:n_pages], refs[n_pages:2 * n_pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * n_pages:]
    i = pl.program_id(0)
    g = group_ref[i]
    pos = pos_ref[slot_ref[i]]
    n_q = q_ref.shape[1]
    bs = k_refs[0].shape[1]
    T = n_pages * bs
    n_keys = n_cols * bs

    @pl.when(g == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = g * T + jax.lax.broadcasted_iota(jnp.int32, (T, 1, 1), 0)
    k = jnp.concatenate([r[0] for r in k_refs], axis=0).astype(jnp.float32)
    v = jnp.concatenate([r[0] for r in v_refs], axis=0).astype(jnp.float32)
    for j in range(n_q):            # query row j sits at position pos + j
        q = q_ref[0, j].astype(jnp.float32)                   # (H, D)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        # a column past the table is no key, whatever the head says
        s = jnp.where(idx <= jnp.minimum(pos + j, n_keys - 1), s, -1e30)
        m_prev = m_ref[j]                                     # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])                          # (T, H, 1)
        l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=0)
        acc_ref[j] = acc_ref[j] * alpha + jnp.sum(p * v, axis=0)
        m_ref[j] = m_new

    @pl.when((g + 1) * T > jnp.minimum(pos + n_q - 1, n_keys - 1))
    def _fin():                         # the slot's last live group
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_work_list(tables, positions, n_q, bs, n_pages, window=None,
                     runs=None):
    """The (slot, group) steps that hold a live key, slot-major, and the
    pool page each of a step's ``n_pages`` operands reads: every slot's
    groups up to its write head (at least its first), none past it —
    and, with a ``window``, none before the group that holds the first
    key the slot's first query row may read (``positions - window + 1``).
    Returns ``(n_steps, slot, group, page)``; the arrays are padded to
    the static bound ``S * n_groups`` by repeating the last live step.

    A column of a live group that lies past the write head names the
    page the same operand read one step earlier — Pallas fetches an
    operand only when its block index moves, so it costs nothing.

    With ``runs = (pages a group, blocks in the pool)`` — the grouped
    kernel's call, whose step is ``n_pages // pages`` groups of columns —
    a fifth array says which groups are RUNS, ``n_pages // pages`` flags
    a step: 1 where the group's live columns — the table's own entries:
    such a call's dead columns are not filled — name consecutive blocks,
    ``tables[s, c + j] == tables[s, c] + j``, and ``pages`` blocks from
    the first lie inside the pool, so that ONE copy brings every live key
    of the group (:func:`_paged_gqa_kernel`); 2, on all of a step's
    groups, where the same holds of the whole step and its ``n_pages``
    blocks: one copy brings them all; else 0.  ``BlockPool`` hands blocks
    out in a row, so an unfragmented table is all runs."""
    S, n_cols = tables.shape
    n_groups = -(-n_cols // n_pages)
    last = jnp.minimum((positions + n_q - 1) // bs, n_cols - 1)    # (S,)
    n_live = last // n_pages + 1                     # live groups a slot
    if window is not None:
        first = jnp.maximum(positions - int(window) + 1, 0) \
            // (bs * n_pages)                        # a slot's first group
        n_live = n_live - first
    ends = jnp.cumsum(n_live)
    n_steps = ends[-1]
    step = jnp.minimum(jnp.arange(S * n_groups, dtype=jnp.int32),
                       n_steps - 1)
    slot = jnp.sum(step[:, None] >= ends[None, :], axis=1,
                   dtype=jnp.int32)                  # the slot of a step
    group = step - (ends - n_live)[slot]
    if window is not None:
        group = group + first[slot]
    col = group[:, None] * n_pages \
        + jnp.arange(n_pages, dtype=jnp.int32)[None, :]   # (steps, pages)
    live = col <= last[slot][:, None]
    page = tables[slot[:, None], jnp.minimum(col, n_cols - 1)]
    if runs is not None:
        pages, pool_blocks = runs

        def in_a_row(ids, live):
            """Over the last axis: the live ids follow the first, and as
            many blocks from the first lie inside the pool."""
            n = ids.shape[-1]
            follow = ids == ids[..., :1] + jnp.arange(n, dtype=jnp.int32)
            return jnp.all(follow | ~live, axis=-1) \
                & (ids[..., 0] + n <= int(pool_blocks))

        by_group = (-1, n_pages // pages, pages)
        run = jnp.where(
            in_a_row(page, live)[:, None], 2,
            in_a_row(page.reshape(by_group), live.reshape(by_group))
            .astype(jnp.int32))
        # the kernel copies live columns by hand: nothing to fill
        return n_steps, slot, group, page.reshape(-1), run.reshape(-1)
    # forward-fill the dead columns from the operand's last live step
    # (the null block 0 before any)
    src = jax.lax.cummax(jnp.where(live, step[:, None], -1), axis=0)
    page = jnp.where(src >= 0, jnp.take_along_axis(
        page, jnp.maximum(src, 0), axis=0), 0)
    return n_steps, slot, group, page.reshape(-1)


def _paged_gqa_kernel(slot_ref, group_ref, page_ref, run_ref, pos_ref,
                      steps_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
                      sem, acc_ref, m_ref, l_ref, *, scale, n_pages,
                      run_pages, n_cols, bs, n_q, q_heads, kv_heads, window):
    """:func:`_paged_kernel` for a grouped-query layer: the ``q_heads``
    query heads of each of the ``kv_heads`` KV heads of slot
    ``slot_ref[i]`` against that head's part of its ``group_ref[i]``-th
    ``n_pages`` pages.  A page is one block of the pool, all its KV heads —
    ``kv_heads`` (bs, D) tiles in the pool's own type, one run of memory
    whatever the head count.  A KV head's query rows — ``n_q`` positions
    times ``q_heads`` heads, position-major, padded to whole tiles — meet
    its tiles on the MXU: scores ``q k^T`` and ``p v`` with operands of
    the pool's type, accumulated in float32; the softmax runs in float32.
    With a ``window`` row j reads keys ``head - window < t <= head`` only,
    and the slot's first step is the group that holds the first of them.

    The pools stay where they rest (``k_hbm``/``v_hbm``, ``[N * H * bs,
    D]``) and the kernel fetches by hand, a step ahead, into the two
    halves of ``k_buf``/``v_buf`` (``[2, n_pages * H * bs, D]``), a
    group of ``run_pages`` columns at a time: where ``run_ref`` says the
    group's blocks lie in a row, ONE copy of ``run_pages`` blocks from
    the first; where it does not, a copy a live column; nothing for a
    group past the write head; and where it says that of the whole step,
    one copy of its ``n_pages`` blocks.  A copy costs about the same
    whatever it brings, and a step about the same whatever it reads, so
    a run costs a fraction of its blocks and a step takes several
    groups.  Rows past the write head — a run's tail, a skipped column's
    leftovers — are whatever finite values the pool or the zeroed buffer
    held: masked by position, their weights exact zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    i = pl.program_id(0)
    g = group_ref[i]
    pos = pos_ref[slot_ref[i]]
    R = q_ref.shape[1] // kv_heads      # a KV head's rows, whole tiles
    P = kv_heads * bs                   # a page's rows in the pool
    T = n_pages * bs
    n_keys = n_cols * bs
    first = 0 if window is None \
        else jnp.maximum(pos - window + 1, 0) // T

    def fetch(t, op):
        """Start (``op`` "start") or await ("wait") step ``t``'s copies."""
        half = jax.lax.rem(t, 2)

        def copy(src, dst, rows):
            for c, (pool, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    pool.at[pl.ds(pl.multiple_of(src, P), rows)],
                    buf.at[half, pl.ds(dst, rows)], sem.at[c, half]), op)()

        n_runs = n_pages // run_pages

        @pl.when(run_ref[t * n_runs] == 2)
        def _step():
            copy(page_ref[t * n_pages] * P, 0, n_pages * P)

        @pl.when(run_ref[t * n_runs] != 2)
        def _groups():
            # the columns of step t's table that are live, from its first
            live = jnp.minimum((pos_ref[slot_ref[t]] + n_q - 1) // bs,
                               n_cols - 1) - group_ref[t] * n_pages + 1
            for u in range(n_runs):
                c, run = u * run_pages, run_ref[t * n_runs + u]

                @pl.when((run == 1) & (c < live))
                def _run(c=c):
                    copy(page_ref[t * n_pages + c] * P, c * P, run_pages * P)

                @pl.when(run == 0)
                def _blocks(c=c):
                    def block(j, _):
                        copy(page_ref[t * n_pages + j] * P,
                             pl.multiple_of(j * P, P), P)

                    jax.lax.fori_loop(c, jnp.minimum(c + run_pages, live),
                                      block, None)

    @pl.when(i == 0)
    def _zero():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    # the next step's copies — and, first of all, the first step's own
    jax.lax.fori_loop(jnp.where(i == 0, 0, i + 1),
                      jnp.minimum(i + 2, steps_ref[0]),
                      lambda t, _: fetch(t, "start"), None)
    fetch(i, "wait")

    @pl.when(g == first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = g * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
    head = pos
    for j in range(1, n_q):         # row r is query position r // q_heads
        head = head + (row >= j * q_heads).astype(jnp.int32)
    live = idx <= jnp.minimum(head, n_keys - 1)
    if window is not None:
        live = live & (idx > head - window)
    half = jax.lax.rem(i, 2)
    for h in range(kv_heads):
        rows = pl.ds(h * R, R)
        # a page's rows are its heads' (bs, D) tiles: head h's keys
        k, v = (buf[half].reshape(n_pages, kv_heads, bs, -1)[:, h].reshape(
            T, -1) for buf in (k_buf, v_buf))                     # (T, D)
        s = jax.lax.dot_general(
            q_ref[0, rows].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # (R, T)
        s = jnp.where(live, s, -1e30)
        m_prev = m_ref[rows]                                      # (R, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row whose window starts in a later group has no key here
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        l_ref[rows] = l_ref[rows] * alpha \
            + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # (R, D)
        m_ref[rows] = m_new

    @pl.when((g + 1) * T > jnp.minimum(pos + n_q - 1, n_keys - 1))
    def _fin():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "interpret"))
def _paged_gqa_pallas(q, k_pages, v_pages, tables, positions, scale, window,
                      interpret):
    """:func:`_paged_verify_pallas` for ``q`` (S, Hq, n_q, D) over a pool
    of ``H`` KV heads ``[N, H, bs, D]``, ``Hq // H`` query heads a KV
    head — taken as ``[N * H * bs, D]``, the same bytes, and left where
    it rests: the kernel copies a step's blocks itself, a group of
    ``_PAGED_GROUP_KEYS`` keys in ONE copy where the table names its
    blocks in a row (:func:`_paged_work_list`'s run flags),
    ``_PAGED_GQA_STEP_GROUPS`` groups a step — with the work list bounded
    from below by ``window``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, Hq, n_q, D = q.shape
    N, H, bs, _ = k_pages.shape
    G = Hq // H
    n_cols = tables.shape[1]
    run_pages, n_runs = _paged_gqa_step(bs, n_cols)
    n_pages = run_pages * n_runs
    positions = positions.astype(jnp.int32)
    n_steps, slot, group, page, run = _paged_work_list(
        tables.astype(jnp.int32), positions, n_q, bs, n_pages, window,
        runs=(run_pages, N))
    R = n_q * G
    Rp = -(-R // 16) * 16               # whole tiles of either type
    rows = jnp.swapaxes(q.reshape(S, H, G, n_q, D), 2, 3).reshape(
        S, H, R, D).astype(jnp.float32)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, Rp - R), (0, 0))).reshape(
        S, H * Rp, D)
    spec_q = pl.BlockSpec((1, H * Rp, D),
                          lambda i, slot, *_: (slot[i], 0, 0))
    spec_pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_steps,),
        in_specs=[spec_q, spec_pool, spec_pool],
        out_specs=spec_q,
        scratch_shapes=[
            pltpu.VMEM((2, n_pages * H * bs, D), k_pages.dtype),
            pltpu.VMEM((2, n_pages * H * bs, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H * Rp, D), jnp.float32),
            pltpu.VMEM((H * Rp, 1), jnp.float32),
            pltpu.VMEM((H * Rp, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_gqa_kernel, scale=scale, n_pages=n_pages,
        run_pages=run_pages, n_cols=n_cols, bs=bs, n_q=n_q, q_heads=G,
        kv_heads=H, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H * Rp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
    )(slot, group, page, run, positions, jnp.reshape(n_steps, (1,)), rows,
      k_pages.reshape(N * H * bs, D), v_pages.reshape(N * H * bs, D))
    out = out.reshape(S, H, Rp, D)[:, :, :R].reshape(S, H, n_q, G, D)
    return jnp.swapaxes(out, 2, 3).reshape(S, Hq, n_q, D).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "position_major"))
def _paged_verify_pallas(q, k_pages, v_pages, tables, positions, scale,
                         interpret, position_major=False):
    """The paged cache's ``pallas_call``: K and V are read from the pool's
    own buffers, a page (one block, all H heads) at a time, and only up
    to each slot's write head.  No dense (S, H, T, D) view exists.

    The grid is :func:`_paged_work_list`: one step per (slot, group of
    ``_PAGED_GROUP_KEYS`` keys) that holds a live key, its length a
    runtime value — a free slot, table padding and the reserved tail of
    a stream are not visited at all.  The pool is passed once per page
    of a group, each operand with its own index map into the list.

    The kernel takes the pool as ``[N, bs, H, D]``: on the TPU the
    compiler keeps a pool that the decode programs scatter (S, H, D) rows
    into with H and D minor-most, so the ``swapaxes`` below is a bitcast
    there and a page is one contiguous run of (H, D) tiles
    (``tests/test_paged_attention.py`` compiles the burst program for the
    chip and holds it to that).  A ``position_major`` pool IS
    ``[N, bs, H, Dp]``: its pages are taken as they lie, the query padded
    with zeros to ``Dp`` features (a zero feature adds nothing to a score,
    and the pool's lanes past D hold zeros) and the result cut back to D.

    Jitted on its own so that a program's 24 layers trace and lower the
    kernel once: unrolled per layer it added 4 s to a decode program's
    trace, paid at every start-up whatever the compile cache holds."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, n_q, head_dim = q.shape
    n_cols = tables.shape[1]
    _, _, bs, D = _pool_dims(k_pages, position_major)
    if position_major:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, D - head_dim),))
    else:
        k_pages, v_pages = (jnp.swapaxes(k_pages, 1, 2),
                            jnp.swapaxes(v_pages, 1, 2))
    n_pages = _paged_group_pages(bs, n_cols)
    positions = positions.astype(jnp.int32)
    n_steps, slot, group, page = _paged_work_list(
        tables.astype(jnp.int32), positions, n_q, bs, n_pages)

    spec_q = pl.BlockSpec((1, n_q, H, D),
                          lambda i, slot, *_: (slot[i], 0, 0, 0))
    spec_pages = [
        pl.BlockSpec((1, bs, H, D),
                     lambda i, slot, group, page, pos, j=j:
                     (page[i * n_pages + j], 0, 0, 0))
        for j in range(n_pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),
        in_specs=[spec_q] + spec_pages + spec_pages,
        out_specs=spec_q,
        scratch_shapes=[
            pltpu.VMEM((n_q, H, D), jnp.float32),
            pltpu.VMEM((n_q, H, 1), jnp.float32),
            pltpu.VMEM((n_q, H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, n_pages=n_pages,
                               n_cols=n_cols)
    qt = jnp.swapaxes(q, 1, 2)                                # (S, Q, H, D)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
    )(slot, group, page, positions, qt,
      *([k_pages] * n_pages), *([v_pages] * n_pages))
    out = jnp.swapaxes(out, 1, 2)
    return out[..., :head_dim] if position_major else out


def paged_verify_decode_attention(q, k_pages, v_pages, tables, positions,
                                  scale=None, window=None,
                                  position_major=False):
    """Per-slot k+1-wide attention over a PAGED KV cache.

    ``q`` (S, Hq, Q, D): query block, row j at logical position
    ``positions[s] + j``; ``k_pages``/``v_pages`` (num_blocks, H,
    block_size, D), ``Hq`` a multiple of ``H``; ``tables`` (S, max_blocks)
    int32 padded with null block 0; ``positions`` (S,) int32 base
    positions; ``window`` as in :func:`paged_decode_attention`.  Returns
    (S, Hq, Q, D).  :func:`paged_attention_impl` picks a Pallas kernel or
    the lax gather at trace time; ``position_major`` as there."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out = _paged_pallas(q, k_pages, v_pages, tables, positions, scale,
                        window, position_major)
    if out is not None:
        return out
    return _xla_paged_verify_decode_attention(
        q, k_pages, v_pages, tables, positions, scale, window,
        position_major)


def paged_prefix_attention(q, k_pages, v_pages, table, ctx, window=None,
                           scale=None, position_major=False):
    """A prompt's SUFFIX over ONE slot's paged strip (the prefix-hit
    prefill): ``q`` (1, Hq, Tb, D), row j at logical position ``ctx + j``;
    the pool already holds the suffix's own K/V; ``table`` (max_blocks,)
    int32; ``ctx`` an int32 scalar.  Row j attends keys ``<= ctx + j``
    (and ``> ctx + j - window``).  Returns (1, Hq, Tb, D).

    Always the lax gather of the slot's whole strip: the kernels' work
    lists are built for a few query rows a slot, not for hundreds.  One
    query head a KV head without a window keeps the arithmetic of the
    model's own fused attention (stable softmax, probabilities in the
    cache's type), which is what keeps a prefix hit's tokens those of a
    miss."""
    H, D = _pool_dims(k_pages, position_major)[1], q.shape[3]
    Hq, Tb = q.shape[1], q.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    ck = _dense_view(k_pages, table[None], position_major, D)  # (1, H, T, D)
    cv = _dense_view(v_pages, table[None], position_major, D)
    T = ck.shape[2]
    if Hq != H or window is not None:
        def rows(q_rows, i0):       # whose first row sits at ctx + i0
            return _xla_grouped_decode_attention(
                q_rows, ck, cv, jnp.reshape(ctx + i0, (1,)), scale, window)

        return _by_query_chunks(rows, q, 2)
    q_idx = jnp.arange(Tb, dtype=jnp.int32)
    key_idx = jnp.arange(T, dtype=jnp.int32)
    live = key_idx[None, :] <= (ctx + q_idx)[:, None]          # (Tb, T)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, ck) * scale
    s = jnp.where(live[None, None], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    lsum = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", (p / lsum).astype(cv.dtype), cv)
