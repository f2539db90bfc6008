"""The grouped expert product of a decode-shaped call as ONE Pallas kernel.

``held_experts_ffn`` (``models/moe.py``) gives every token the weighted sum
of the gated units of its ``k`` experts.  A decode step holds few tokens and
touches many experts — 32 tokens on 61 of 64, 64 tokens on 175 of 256 — so
the product is the experts' bytes, read once each, and what has to be fast
is the walk from one expert's matrices to the next.

:func:`held_experts_pallas` makes that walk the kernel's grid.  A work list
names the experts a token chose, in order (:func:`_visits`), and rides to the
kernel by scalar prefetch; the block index of ``w_gate``, ``w_up`` and
``w_down`` is read from it, so Pallas's pipeline fetches visit ``i + 1``'s
blocks while visit ``i`` is multiplied, and an expert no token chose is in
no visit and is never read.  A visit carries ALL the tokens — they rest in
VMEM, as does the float32 output — with a combine weight that is zero for a
token that did not choose the expert: rows the MXU has to spare when the
product is bound by the weights, and no sort, scatter or gather around the
kernel.  The hidden width is the grid's second axis, in blocks of
:func:`_f_block` columns, the down projection summed over them in float32.

The mathematics is ``moe._glu``'s: operands of the arrays' type, float32
accumulation, the activation in float32, the middle cast to ``x``'s type,
the router's weight applied to the expert's float32 output.  A token's
experts are summed in expert order (the loop sums them in the router's):
float32, so the two differ by rounding alone.

:func:`held_experts_impl` says which of the two a call traces, from what the
call shows; ``MXNET_FA_DECODE_FORCE_PALLAS=1``, the test hook of the paged
attention kernels, interprets this kernel on a CPU too.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module (the package's attribute of that name is a function)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["held_experts_impl", "held_experts_pallas"]

#: pairs (tokens x experts a token) from which a call is a prompt's, not a
#: decode step's: ``held_experts_ffn`` takes 128-row tiles there
DECODE_PAIRS = 1024
#: tokens a visit may carry: beyond, the rows cost more than the weights
_MAX_TOKENS = 128
_LANES = 128
#: bytes the two buffers of a visit's three weight blocks may take in VMEM
_WEIGHT_BUFFER_BYTES = 24 * 2 ** 20
_VMEM_LIMIT_BYTES = 48 * 2 ** 20


def held_experts_impl(x, w_gate, P) -> str:
    """``"pallas"`` or ``"lax_loop"``: what ``held_experts_ffn`` traces for
    tokens ``x`` (T, d), stacked gate matrices ``w_gate`` (count, d, f) and
    ``P = T * k`` (token, expert) pairs.  The kernel takes a decode-shaped
    call — fewer than ``DECODE_PAIRS`` pairs of at most 128 tokens — on a
    TPU when ``d`` and ``f`` are whole lane tiles; the loop takes the rest:
    prompts, the CPU (the kernel's reference), odd widths.  Decided from
    what is visible at trace time (``x`` names the platform, as for
    ``paged_attention_impl``); ``MXNET_FA_DECODE_FORCE_PALLAS=1`` is the
    test hook that interprets the kernel on a CPU, at any width."""
    from ..base import getenv_bool
    T, d = x.shape
    f = w_gate.shape[-1]
    if P >= DECODE_PAIRS or T > _MAX_TOKENS:
        return "lax_loop"
    if _fa._platform_of(x) == "tpu":
        return "pallas" if d % _LANES == 0 and f % _LANES == 0 \
            else "lax_loop"
    return "pallas" if getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS") \
        else "lax_loop"


def _f_block(d, f, itemsize):
    """Columns of the hidden width a grid step takes: the most, in whole
    lane tiles that divide ``f``, whose three blocks fit VMEM twice (the
    pipeline's two buffers); all of ``f`` where it is no whole tile."""
    if f % _LANES:
        return f
    fits = [fb for fb in range(_LANES, f + 1, _LANES) if f % fb == 0
            and 2 * 3 * d * fb * itemsize <= _WEIGHT_BUFFER_BYTES]
    return fits[-1] if fits else _LANES


def _visits(local, count, n_steps):
    """The work list of a call whose (token, expert) pairs are ``local``
    (T, k) int32 — an index into the ``count`` experts held, -1 for a pair
    that falls elsewhere or on no live token: ``(n (1,), expert
    (n_steps,), pairs_held)``, the experts a pair fell on in rising order,
    the list padded by repeating its last entry (expert 0 when it is
    empty)."""
    hit = local.reshape(-1, 1) == jnp.arange(count, dtype=jnp.int32)[None]
    n_e = jnp.sum(hit, axis=0, dtype=jnp.int32)      # pairs of each expert
    ends = jnp.cumsum((n_e > 0).astype(jnp.int32))
    n = ends[-1]
    step = jnp.minimum(jnp.arange(n_steps, dtype=jnp.int32), n - 1)
    expert = jnp.sum(step[:, None] >= ends[None, :], axis=1,
                     dtype=jnp.int32)
    return n.reshape(1), expert, jnp.sum(n_e)


def _experts_kernel(n_ref, expert_ref, local_ref, w_ref, x_ref, wg_ref,
                    wu_ref, wd_ref, o_ref, *acc, act, n_f):
    """Grid step ``(v, j)``: expert ``expert_ref[v]``'s block ``j`` of the
    hidden width against all the tokens.  ``o_ref`` (T, d) float32 rests
    in VMEM over the whole grid; with more than one block ``acc`` holds
    the scratch that sums an expert's down projection over them before
    the router's weight meets it."""
    from jax.experimental import pallas as pl
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when((v == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(v < n_ref[0])
    def _visit():
        gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        mid = (gate(g) * u).astype(x.dtype)
        part = jnp.dot(mid, wd_ref[0], preferred_element_type=jnp.float32)

        def combine(out):
            # the router's weight of the tokens that chose this expert
            cw = jnp.sum(jnp.where(local_ref[...] == expert_ref[v],
                                   w_ref[...], 0.0), axis=1, keepdims=True)
            o_ref[...] += cw * out

        if n_f == 1:
            combine(part)
        else:
            acc_ref, = acc

            @pl.when(j == 0)
            def _first():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _more():
                acc_ref[...] += part

            @pl.when(j == n_f - 1)
            def _last():
                combine(acc_ref[...])


def held_experts_pallas(x, local, w, w_gate, w_up, w_down, act="silu",
                        f_block=None):
    """``x`` (T, d), ``local`` (T, k) int32 (a pair's expert among the
    ``count`` held, -1 where it counts for nothing), ``w`` (T, k) float32,
    the stacked matrices ``w_gate`` / ``w_up`` (count, d, f) and ``w_down``
    (count, f, d).  Returns ``(y (T, d) float32, pairs_held,
    experts_touched)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, d = x.shape
    k = local.shape[1]
    count, _, f = w_gate.shape
    fb = int(f_block or _f_block(d, f, w_gate.dtype.itemsize))
    n_f = f // fb
    n_steps = min(count, T * k)         # the most experts a call can touch
    n, expert, pairs_held = _visits(local, count, n_steps)
    Tp = -(-T // 16) * 16               # whole tiles of either type
    if Tp != T:
        x = jnp.pad(x, ((0, Tp - T), (0, 0)))
        local = jnp.pad(local, ((0, Tp - T), (0, 0)), constant_values=-1)
        w = jnp.pad(w, ((0, Tp - T), (0, 0)))

    # a step past the last visit names the block the last one read: Pallas
    # fetches an operand only when its block index moves
    def col(v, j, n, expert):
        return expert[v], 0, jnp.where(v < n[0], j, n_f - 1)

    def row(v, j, n, expert):
        return expert[v], jnp.where(v < n[0], j, n_f - 1), 0

    whole = lambda shape: pl.BlockSpec(shape, lambda v, j, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_steps, n_f),
        in_specs=[whole((Tp, k)), whole((Tp, k)), whole((Tp, d)),
                  pl.BlockSpec((1, d, fb), col),
                  pl.BlockSpec((1, d, fb), col),
                  pl.BlockSpec((1, fb, d), row)],
        out_specs=whole((Tp, d)),
        scratch_shapes=[pltpu.VMEM((Tp, d), jnp.float32)] * (n_f > 1),
    )
    y = pl.pallas_call(
        functools.partial(_experts_kernel, act=act, n_f=n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_fa._platform_of(x) == "cpu",
        name="held_experts",
    )(n, expert, local, w.astype(jnp.float32), x, w_gate, w_up, w_down)
    return y[:T], pairs_held, n[0]
