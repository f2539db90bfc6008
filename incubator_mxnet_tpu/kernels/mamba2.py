"""The Mamba-2 recurrence (a selective state space with a scalar decay a
head), over a prompt and over one token.

A head of ``P`` features keeps a matrix ``S`` (N x P here: the state's ``N``
coordinates on the rows, float32) that every token updates::

    S <- a_t S + B_t (dt_t x_t)^T;   y_t = S^T C_t + D x_t,   a_t = e^{g_t}

with ``x_t`` (P,) the head's input, ``dt_t`` its step (after the softplus),
``g_t = -exp(A_log) dt_t`` the log of its decay, ``B_t`` and ``C_t`` (N,) the
input and output maps — ONE pair for all the heads (``mamba_n_groups`` 1) —
and ``D`` a scalar a head.  A sequence's state is held ``(N, H, P)``, or
``(N, H * P)`` in the engine's rows: the heads' features side by side on the
lanes, so that a row of 64 x 64 x 128 floats is 128 dense rows of 4,096 and
the decay, the outer product and ``S^T C`` are elementwise over them.

``ssd_scan`` is that recurrence token by token (``lax.scan``): the
definition, and what the tests hold the other two against.

``ssd_prefill`` computes the same over a whole prompt in chunks of
:data:`CHUNK` positions (the "state space dual" form).  With ``G_t`` the
running sum of ``g`` inside a chunk and ``S_0`` the state it starts from::

    y_t = e^{G_t} S_0^T C_t + sum_{s <= t} e^{G_t - G_s} (C_t . B_s) dt_s x_s + D x_t
    S_end = e^{G_C} S_0 + sum_s e^{G_C - G_s} B_s (dt_s x_s)^T

so every chunk's own sum and its contribution to the state are products over
all chunks at once, and what is sequential from chunk to chunk is a decay
and an add over the state (``lax.scan`` everywhere: unlike the delta rule's
(``kernels/gated_delta.py``) this recurrence has no product against the
state inside it, so there is nothing for a kernel to keep in VMEM).

``ssd_step`` is one token a row of state in ONE pass: each row read once and
written once.  ``ssd_step_rows`` is the same over the LIVE ones of rows ``0
.. B - 1`` of one layer of the engine's whole state leaf ``(R, n, N, H *
P)``, in place: a Pallas kernel on a TPU whose output IS its input
(``input_output_aliases``) and whose grid steps are named by a work list —
``step_work_list``: the live rows' indices, by scalar prefetch, their count
the grid's first bound — so the rows it does not visit (a slot nobody is
on, snapshot rows, other layers) are never touched; ``lax`` slices elsewhere
(``MXNET_FA_DECODE_FORCE_PALLAS=1``, the test hook of the paged attention
kernels, interprets the kernel on a CPU).

Everything here is float32 with products at ``Precision.HIGHEST``, as
``gated_delta.py`` states and for its reason: the state is float32 by the
model's statement.  A position that is not ``live`` (padding of a prompt's
bucket, a free slot) leaves the state bit for bit: its ``g`` and ``dt`` are
taken as 0 in the prefill; the step's kernel does not visit its row (the
``lax`` step selects the old state), and its output is zeros.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax

# the module (the package's attribute of that name is a function)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["CHUNK", "ssd_scan", "ssd_prefill", "ssd_step", "ssd_step_rows",
           "step_work_list", "ssd_impl"]

#: positions a chunk of the prefill holds (the source's ``mamba_chunk_size``)
CHUNK = 256
#: lanes of a state row one step of the step kernel's grid takes
_STEP_LANES = 2048
_HI = lax.Precision.HIGHEST


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def ssd_impl(x) -> str:
    """``"pallas"`` or ``"lax"``: what :func:`ssd_step_rows` traces with
    operand ``x`` (it names the platform, as for the attention kernels:
    ``flash_attention._platform_of``)."""
    from ..base import getenv_bool
    if _fa._platform_of(x) == "tpu" \
            or getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
        return "pallas"
    return "lax"


def ssd_step(x, dt, g, B, C, D, S, live=None):
    """One token a row: ``x`` (..., H, P), ``dt``, ``g`` (..., H), ``B``,
    ``C`` (..., N), ``D`` (H,), ``S`` (..., N, H, P), ``live`` (...) bool or
    None.  Returns ``(y (..., H, P), S')``; a row that is not live keeps its
    state bit for bit (its ``y`` is not to be read)."""
    x, dt, g, B, C, D, S = _f32(x, dt, g, B, C, D, S)
    xd = dt[..., None] * x                                   # (..., H, P)
    S2 = jnp.exp(g)[..., None, :, None] * S \
        + B[..., :, None, None] * xd[..., None, :, :]
    y = jnp.einsum("...nhp,...n->...hp", S2, C, precision=_HI) \
        + D[:, None] * x
    if live is not None:
        S2 = jnp.where(live[..., None, None, None], S2, S)
    return y, S2


def ssd_scan(x, dt, g, B, C, D, s0, live=None):
    """Token by token: ``x`` (T, H, P), ``dt``, ``g`` (T, H), ``B``, ``C``
    (T, N), ``D`` (H,), ``s0`` (N, H, P), ``live`` (T,) bool or None.
    Returns ``(y (T, H, P) float32, the last state (N, H, P))``."""
    x, dt, g, B, C, s0 = _f32(x, dt, g, B, C, s0)
    live = jnp.ones(x.shape[0], bool) if live is None else live

    def step(S, t):
        xt, dtt, gt, Bt, Ct, on = t
        y, S2 = ssd_step(xt, dtt, gt, Bt, Ct, D, S, on)
        return S2, y

    last, y = lax.scan(step, s0, (x, dt, g, B, C, live))
    return y, last


def ssd_prefill(x, dt, g, B, C, D, s0, live=None, snapshot_every=0):
    """A whole prompt from the state ``s0``: ``x`` (T, H, P), ``dt``, ``g``
    (T, H), ``B``, ``C`` (T, N), ``D`` (H,), ``s0`` (N, H, P), ``live`` (T,)
    bool or None.  Returns ``(y (T, H, P) float32, the states after every
    ``snapshot_every`` positions (T // snapshot_every, N, H, P) — none for 0
    — and the last state (N, H, P))``.  ``snapshot_every`` is a multiple of
    :data:`CHUNK`; ``T`` need not be (it is padded with positions that are
    not live, which leave the state as it is)."""
    x, dt, g, B, C, D, s0 = _f32(x, dt, g, B, C, D, s0)
    T, H, P = x.shape
    every = int(snapshot_every)
    if every % CHUNK:
        raise ValueError(f"snapshot_every {every} is no multiple of the "
                         f"chunk, {CHUNK}")
    if live is not None:        # a = 1, dt = 0: the state passes unchanged
        g = jnp.where(live[:, None], g, 0.0)
        dt = jnp.where(live[:, None], dt, 0.0)
    pad = -T % CHUNK
    if pad:
        x, dt, g, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                          for a in (x, dt, g, B, C))
    n = (T + pad) // CHUNK
    x, dt, g, B, C = (a.reshape(n, CHUNK, *a.shape[1:])
                      for a in (x, dt, g, B, C))
    G = jnp.cumsum(g, axis=1)                                # (n, C, H)
    xd = dt[..., None] * x                                   # (n, C, H, P)
    # what each chunk adds to the state, and its decay over the chunk
    to_end = jnp.exp(G[:, -1:] - G)                          # (n, C, H)
    Z = jnp.einsum("csn,cshp->cnhp", B, to_end[..., None] * xd,
                   precision=_HI)
    gl = jnp.exp(G[:, -1])                                   # (n, H)

    def carry(S, z):
        Zc, glc = z
        S2 = glc[None, :, None] * S + Zc
        return S2, S                    # the state each chunk STARTS from

    last, starts = lax.scan(carry, s0, (Z, gl))              # (n, N, H, P)
    # inside a chunk: the causal sum over its own positions, head-major
    t, s = jnp.arange(CHUNK)[:, None], jnp.arange(CHUNK)[None, :]
    Gh = jnp.moveaxis(G, 2, 1)                               # (n, H, C)
    diff = Gh[..., :, None] - Gh[..., None, :]               # G_t - G_s
    L = jnp.where(s <= t, jnp.exp(jnp.where(s <= t, diff, 0.0)), 0.0)
    CB = jnp.einsum("ctn,csn->cts", C, B, precision=_HI)
    y = jnp.einsum("chts,chsp->chtp", L * CB[:, None],
                   jnp.moveaxis(xd, 2, 1), precision=_HI) \
        + jnp.exp(Gh)[..., None] * jnp.einsum(
            "cnhp,ctn->chtp", starts, C, precision=_HI)
    y = jnp.moveaxis(y, 1, 2) + D[:, None] * x
    y = y.reshape(n * CHUNK, H, P)[:T]
    if not every:
        return y, jnp.zeros((0,) + s0.shape, jnp.float32), last
    k = every // CHUNK          # the state after span j starts chunk (j+1)k
    ends = jnp.concatenate([starts[1:], last[None]], 0)
    return y, ends[k - 1::k][:T // every], last


def step_work_list(live):
    """The step kernel's work list for ``live`` (B,) bool: ``(order (B,)
    int32, n_live () int32)`` — ``order[:n_live]`` the live rows' indices
    in ascending order (what follows them is never read: 0).  A rank by
    cumulative sum and a comparison of B x B (no sort, no scatter: 48
    rows)."""
    on = live.astype(jnp.int32)
    r = jnp.arange(live.shape[0], dtype=jnp.int32)
    mine = live[:, None] & ((jnp.cumsum(on) - 1)[:, None] == r[None, :])
    return jnp.sum(jnp.where(mine, r[:, None], 0), axis=0,
                   dtype=jnp.int32), jnp.sum(on)


def _step_kernel(layer_ref, order_ref, a_ref, xd_ref, b_ref, c_ref, s_ref,
                 y_ref, o_ref):
    """One live row, ``_STEP_LANES`` lanes of it (which row, the block
    specs read off the work list): the state's block is read once and
    written once — decay, outer product and ``S^T C`` on 128 x 128 tiles,
    ``B`` and ``C`` handed in spread over the lanes."""
    del layer_ref, order_ref
    Bc, Cc = b_ref[...], c_ref[...]                          # (N, 128)
    for j in range(s_ref.shape[1] // 128):
        at = slice(j * 128, (j + 1) * 128)
        S2 = a_ref[:, at] * s_ref[:, at] + Bc * xd_ref[:, at]
        y_ref[:, at] = jnp.sum(S2 * Cc, axis=0, keepdims=True)
        o_ref[:, at] = S2


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(leaf, layer, order, n_live, a, xd, B, C, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _, _, N, F = leaf.shape
    rows = a.shape[0]
    lanes = _STEP_LANES if F % _STEP_LANES == 0 else F
    spread = lambda v: jnp.broadcast_to(v[:, :, None],           # noqa: E731
                                        (rows, N, 128))
    # grid step r is the r-th LIVE row, ``order[r]``, and the grid's first
    # bound is their count: a row nobody is on costs neither a fetch nor a
    # write, and a step with nobody live is an empty grid
    row = pl.BlockSpec((None, 1, lanes),
                       lambda r, j, layer, order: (order[r], 0, j))
    col = pl.BlockSpec((None, N, 128),
                       lambda r, j, layer, order: (order[r], 0, 0))
    state = pl.BlockSpec(
        (None, None, N, lanes),
        lambda r, j, layer, order: (order[r], layer[0], 0, j))
    y, leaf = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_live, F // lanes),
            in_specs=[row, row, col, col, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, F), jnp.float32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # operand 6 (after the two prefetched scalars) is the leaf
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), order,
      a[:, None, :], xd[:, None, :], spread(B), spread(C), leaf)
    return y[:, 0], leaf


def ssd_step_rows(leaf, layer, x, dt, g, B, C, D, live=None, work=None):
    """One token for rows ``0 .. B - 1`` of layer ``layer`` (an int32
    scalar, traced or not) of the state leaf ``leaf`` (R, n, N, H * P)
    float32, in place: ``x`` (B, H, P), ``dt``, ``g`` (B, H), ``B``, ``C``
    (B, N), ``D`` (H,), ``live`` (B,) bool or None, ``work``
    :func:`step_work_list` of ``live`` where the caller has made it (one
    list serves every layer of a run).  Returns ``(y (B, H, P) float32,
    the leaf)`` — every row and layer it was not asked for as it came, a
    row that is not live among them, whose ``y`` is zeros."""
    x, dt, g, B, C, D = _f32(x, dt, g, B, C, D)
    rows, H, P = x.shape
    on = jnp.ones(rows, bool) if live is None else live
    if ssd_impl(x) == "pallas":
        order, n_live = step_work_list(on) if work is None else work
        a = jnp.repeat(jnp.exp(g), P, axis=-1)                   # (B, H * P)
        y, leaf = _step_pallas(
            leaf, layer, order, n_live, a,
            (dt[..., None] * x).reshape(rows, H * P), B, C,
            interpret=_fa._platform_of(x) == "cpu")
        y = y.reshape(rows, H, P) + D[:, None] * x
    else:
        N = leaf.shape[2]
        S = lax.dynamic_index_in_dim(leaf[:rows], layer, 1, keepdims=False)
        y, S2 = ssd_step(x, dt, g, B, C, D, S.reshape(rows, N, H, P), on)
        leaf = lax.dynamic_update_slice(
            leaf, S2.reshape(rows, 1, N, H * P).astype(leaf.dtype),
            (0, layer, 0, 0))
    # a row the kernel did not visit wrote no output at all
    return jnp.where(on[:, None, None], y, 0.0), leaf
