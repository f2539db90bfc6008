"""The gated delta rule (Gated DeltaNet), over a prompt and over one token.

A value head keeps a matrix ``S`` (Dk x Dv, float32) that every token
updates::

    S <- e^{g_t} S;   u = beta_t (v_t - S^T k_t);   S <- S + k_t u^T;   o_t = S^T q_t

``gated_delta_scan`` is that recurrence token by token (``lax.scan``): the
definition, and what the tests hold the other two against.

``gated_delta_prefill`` computes the same over a whole prompt in chunks of
``CHUNK`` positions (the WY form).  With ``G_t`` the running sum of ``g``
inside a chunk and ``S_0`` the state it starts from, the chunk's ``u`` solve
the unit lower-triangular system ``(I + A) U = beta (V - e^G K S_0)``,
``A[t, s] = beta_t e^{G_t - G_s} (k_t . k_s)`` for ``s < t``: two triangular
products and one triangular solve, all chunks at once
(:func:`_chunk_operands`).  What is left is sequential only from chunk to
chunk, over the Dk x Dv state (:func:`_recurrence_pallas`, :func:`_recurrence_scan`): a Pallas kernel on
a TPU — the state rests in VMEM across a head's chunks and each chunk's
operands stream past it — and ``lax.scan`` elsewhere (the CPU path, and the
kernel's reference; ``MXNET_FA_DECODE_FORCE_PALLAS=1``, the test hook of the
paged attention kernels, interprets this kernel on a CPU too).

``gated_delta_step`` is one token a row of state, in two passes over the
state: what the decode programs run over the engine's state rows.

Everything here is float32 with products at ``Precision.HIGHEST``: the state
is float32 by the model's statement, and a product that rounded it to
bfloat16 every chunk would not be.  A position that is not ``live`` (padding
of a prompt's bucket, a free slot) leaves the state bit for bit: its ``g``
and ``beta`` are taken as 0 in the prefill, and the step selects the old
state.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax

# the module (the package's attribute of that name is a function)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["CHUNK", "gated_delta_scan", "gated_delta_prefill",
           "gated_delta_step", "gated_delta_impl"]

#: positions a chunk of the prefill holds
CHUNK = 64
_HI = lax.Precision.HIGHEST


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def gated_delta_impl(x) -> str:
    """``"pallas"`` or ``"lax_scan"``: what :func:`gated_delta_prefill`
    traces for the chunk-to-chunk recurrence with operand ``x`` (it names
    the platform, as for the attention kernels:
    ``flash_attention._platform_of``)."""
    from ..base import getenv_bool
    if _fa._platform_of(x) == "tpu" \
            or getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
        return "pallas"
    return "lax_scan"


def gated_delta_scan(q, k, v, g, beta, s0, live=None):
    """Token by token: ``q``, ``k`` (T, H, Dk), ``v`` (T, H, Dv), ``g``,
    ``beta`` (T, H), ``s0`` (H, Dk, Dv), ``live`` (T,) bool or None.
    Returns ``(o (T, H, Dv) float32, the last state (H, Dk, Dv))``."""
    q, k, v, g, beta, s0 = _f32(q, k, v, g, beta, s0)
    T = q.shape[0]
    live = jnp.ones(T, bool) if live is None else live

    def step(S, x):
        qt, kt, vt, gt, bt, on = x
        o, S2 = gated_delta_step(qt, kt, vt, gt, bt, S,
                                 jnp.broadcast_to(on, gt.shape))
        return S2, o

    last, o = lax.scan(step, s0, (q, k, v, g, beta, live))
    return o, last


def gated_delta_step(q, k, v, g, beta, S, live=None):
    """One token a row: ``q``, ``k`` (..., Dk), ``v`` (..., Dv), ``g``,
    ``beta`` (...), ``S`` (..., Dk, Dv), ``live`` (...) bool or None.
    Returns ``(o (..., Dv), S')``; a row that is not live keeps its state
    bit for bit (its ``o`` is not to be read).

    Two passes over the state: ``S^T k`` and ``S^T q`` from one read, then
    ``S' = e^g S + k u^T`` — ``o = e^g S^T q + (k . q) u`` needs no third."""
    q, k, v, g, beta, S = _f32(q, k, v, g, beta, S)
    decay = jnp.exp(g)[..., None]
    Sk = jnp.einsum("...kv,...k->...v", S, k, precision=_HI)
    Sq = jnp.einsum("...kv,...k->...v", S, q, precision=_HI)
    u = beta[..., None] * (v - decay * Sk)
    o = decay * Sq + jnp.sum(k * q, -1, keepdims=True) * u
    S2 = decay[..., None] * S + k[..., :, None] * u[..., None, :]
    if live is not None:
        S2 = jnp.where(live[..., None, None], S2, S)
    return o, S2


def _chunk_operands(q, k, v, g, beta):
    """What the recurrence reads a chunk, all chunks at once.  ``q``, ``k``
    (N, C, H, Dk), ``v`` (N, C, H, Dv), ``g``, ``beta`` (N, C, H) ->
    head-major ``(qg, kd, w, uv, qk, gl)``: ``qg = e^G q``, ``kd = e^{G_C -
    G} k`` (H, N, C, Dk); ``w = T (beta e^G k)`` (H, N, C, Dk) and ``uv = T
    (beta v)`` (H, N, C, Dv) with ``T = (I + A)^-1``; ``qk`` (H, N, C, C)
    the causal ``e^{G_t - G_s} (q_t . k_s)``; ``gl = e^{G_C}`` (H, N)."""
    C = q.shape[1]
    hm = lambda x: jnp.moveaxis(x, 2, 0)                 # noqa: E731
    q, k, v, g, beta = (hm(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=2)                            # (H, N, C)
    diff = G[..., :, None] - G[..., None, :]             # G_t - G_s
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    decay = jnp.where(s <= t, jnp.exp(jnp.where(s <= t, diff, 0.0)), 0.0)
    kk = jnp.einsum("hntd,hnsd->hnts", k, k, precision=_HI)
    A = jnp.where(s < t, beta[..., None] * decay * kk, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([beta[..., None] * eG * k, beta[..., None] * v],
                          -1)
    sol = lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    Dk = k.shape[-1]
    qk = decay * jnp.einsum("hntd,hnsd->hnts", q, k, precision=_HI)
    gl = jnp.exp(G[..., -1])
    kd = jnp.exp(G[..., -1:] - G)[..., None] * k
    return eG * q, kd, sol[..., :Dk], sol[..., Dk:], qk, gl


def _recurrence_scan(qg, kd, w, uv, qk, gl, s0, every):
    """The chunk-to-chunk recurrence as ``lax.scan``: ``(o (H, N, C, Dv),
    states after every ``every`` chunks (H, N // every, Dk, Dv), last)``."""
    def step(S, x):
        qg_n, kd_n, w_n, uv_n, qk_n, gl_n = x
        U = uv_n - jnp.einsum("htk,hkv->htv", w_n, S, precision=_HI)
        o = jnp.einsum("htk,hkv->htv", qg_n, S, precision=_HI) \
            + jnp.einsum("hts,hsv->htv", qk_n, U, precision=_HI)
        S2 = gl_n[:, None, None] * S \
            + jnp.einsum("hsk,hsv->hkv", kd_n, U, precision=_HI)
        return S2, (o, S2)

    nm = lambda x: jnp.moveaxis(x, 1, 0)                 # noqa: E731
    last, (o, states) = lax.scan(
        step, s0, tuple(nm(x) for x in (qg, kd, w, uv, qk, gl)))
    o, states = jnp.moveaxis(o, 0, 1), jnp.moveaxis(states, 0, 1)
    return o, (states[:, every - 1::every] if every else states[:, :0]), last


def _recurrence_kernel(qg_ref, kd_ref, w_ref, uv_ref, qk_ref, gl_ref, s0_ref,
                       o_ref, *refs):
    """One chunk of one head: the state is ``s_ref`` (VMEM, kept across the
    head's chunks).  ``refs`` are ``[snap_ref,] last_ref, s_ref``."""
    from jax.experimental import pallas as pl
    s_ref = refs[-1]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    S = s_ref[...]
    U = uv_ref[...] - dot(w_ref[...], S)                        # (C, Dv)
    o_ref[...] = dot(qg_ref[...], S) + dot(qk_ref[...], U)
    S2 = gl_ref[...] * S + lax.dot_general(
        kd_ref[...], U, (((0,), (0,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32)                     # (Dk, Dv)
    s_ref[...] = S2
    # a snapshot's block stays in VMEM while the chunks of its span pass:
    # what goes back to memory is the state after the span's last chunk
    for ref in refs[:-1]:
        ref[...] = S2


@functools.partial(jax.jit, static_argnames=("every", "interpret"))
def _recurrence_pallas(qg, kd, w, uv, qk, gl, s0, every, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    H, N, C, Dk = qg.shape
    Dv = uv.shape[-1]
    n_snap = N // every if every else 0
    n_spans = -(-N // every) if every else 0    # the last may be cut short
    gl = jnp.broadcast_to(gl[..., None, None], (H, N, 1, Dv))
    per = lambda d: pl.BlockSpec((None, None, C, d),             # noqa: E731
                                 lambda h, n: (h, n, 0, 0))
    state = pl.BlockSpec((None, Dk, Dv), lambda h, n: (h, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((H, N, C, Dv), jnp.float32)]
    out_specs = [per(Dv)]
    if n_snap:
        out_shape.append(jax.ShapeDtypeStruct((H, n_spans, Dk, Dv),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((None, None, Dk, Dv),
                                      lambda h, n: (h, n // every, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((H, Dk, Dv), jnp.float32))
    out_specs.append(state)
    out = pl.pallas_call(
        _recurrence_kernel,
        grid=(H, N),
        in_specs=[per(Dk), per(Dk), per(Dk), per(Dv), per(C),
                  pl.BlockSpec((None, None, 1, Dv),
                               lambda h, n: (h, n, 0, 0)), state],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kd, w, uv, qk, gl, s0)
    if not n_snap:
        return out[0], jnp.zeros((H, 0, Dk, Dv), jnp.float32), out[1]
    return out[0], out[1][:, :n_snap], out[2]


def gated_delta_prefill(q, k, v, g, beta, s0, live=None, snapshot_every=0):
    """A whole prompt from the state ``s0``: ``q``, ``k`` (T, H, Dk), ``v``
    (T, H, Dv), ``g``, ``beta`` (T, H), ``s0`` (H, Dk, Dv), ``live`` (T,)
    bool or None.  Returns ``(o (T, H, Dv) float32, the states after every
    ``snapshot_every`` positions (T // snapshot_every, H, Dk, Dv) — none
    for 0 — and the last state (H, Dk, Dv))``.  ``snapshot_every`` is a
    multiple of :data:`CHUNK`; ``T`` need not be (it is padded with
    positions that are not live, which leave the state as it is)."""
    q, k, v, g, beta, s0 = _f32(q, k, v, g, beta, s0)
    T, H, _ = q.shape
    every = int(snapshot_every)
    if every % CHUNK:
        raise ValueError(f"snapshot_every {every} is no multiple of the "
                         f"chunk, {CHUNK}")
    if live is not None:
        g = jnp.where(live[:, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    pad = -T % CHUNK
    if pad:        # g = 0, beta = 0: the state passes through unchanged
        q, k, v, g, beta = (jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    N = (T + pad) // CHUNK
    ops = _chunk_operands(*(x.reshape(N, CHUNK, *x.shape[1:])
                            for x in (q, k, v, g, beta)))
    if gated_delta_impl(q) == "pallas":
        o, snaps, last = _recurrence_pallas(
            *ops, s0, every=every // CHUNK,
            interpret=_fa._platform_of(q) == "cpu")
    else:
        o, snaps, last = _recurrence_scan(*ops, s0, every // CHUNK)
    o = jnp.moveaxis(o, 0, 2).reshape(N * CHUNK, H, -1)[:T]
    return o, jnp.moveaxis(snaps, 0, 1)[:T // every if every else 0], last
