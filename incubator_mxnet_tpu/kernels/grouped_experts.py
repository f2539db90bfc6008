"""The grouped expert product as ONE Pallas kernel: a decode step's, and a
prompt's.

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

A prompt holds thousands of tokens and every expert gets hundreds of rows:
there the product is the rows', and :func:`held_experts_sorted` walks them in
SORTED form — the pairs grouped by expert (:func:`group_pairs`, the loop's
own counting sort), a grid over (row tile, block of the hidden width), a
work list that names a tile's expert and the rows of the tile that are its
(:func:`_tile_visits`).  Consecutive tiles of one expert keep its matrices
in VMEM and the next expert's arrive under the last tile's product; a run is
padded to whole tiles in the list, not in memory (a tile two experts share
is visited once for each, the other's rows masked out of the write), and a
tile no held pair falls into is in no visit.  The same mathematics, a
token's experts summed in the router's order as the loop sums them.

:func:`held_experts_impl` says which of the three a call traces, from what
the call shows; ``MXNET_FA_DECODE_FORCE_PALLAS=1``, the test hook of the
paged attention kernels, interprets the kernels on a CPU too.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module (the package's attribute of that name is a function)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["held_experts_impl", "held_experts_route", "held_experts_pallas",
           "held_experts_sorted", "group_pairs"]

#: pairs (tokens x experts a token) from which a call is a prompt's, not a
#: decode step's: ``held_experts_ffn`` takes 128-row tiles there
DECODE_PAIRS = 1024
#: tokens a visit may carry: beyond, the rows cost more than the weights
_MAX_TOKENS = 128
_LANES = 128
#: bytes the two buffers of a visit's three weight blocks may take in VMEM
_WEIGHT_BUFFER_BYTES = 24 * 2 ** 20
#: rows a grid step of the sorted form takes.  On the v5e 128 and 256 rows
#: read the same and 512 a sixth slower (PERF.md section 6, PR 44): a longer
#: tile amortises a step, a tile two runs share is multiplied twice
_ROW_TILE = 128
#: bytes a row tile may take in VMEM: its rows and float32 output twice (the
#: pipeline's two buffers), the products' float32 results
_ROW_TILE_BYTES = 16 * 2 ** 20
_VMEM_LIMIT_BYTES = 48 * 2 ** 20


def held_experts_impl(x, w_gate, P) -> str:
    """``"pallas"``, ``"pallas_sorted"`` or ``"lax_loop"``: what
    ``held_experts_ffn`` traces for tokens ``x`` (T, d), stacked gate
    matrices ``w_gate`` (count, d, f) and ``P = T * k`` (token, expert)
    pairs.  On a TPU, when ``d`` and ``f`` are whole lane tiles, a
    decode-shaped call — fewer than ``DECODE_PAIRS`` pairs of at most 128
    tokens — takes the kernel over the touched experts and any other call
    (a prompt, a long verify block) the kernel over the sorted rows, the
    latter for 16-bit and float32 arrays whose row tile fits VMEM; the
    loop takes the rest: the CPU (the kernels' reference), odd widths,
    other types.  Decided from what is visible at trace time (``x`` names
    the platform, as for ``paged_attention_impl``);
    ``MXNET_FA_DECODE_FORCE_PALLAS=1`` is the test hook that interprets
    either kernel on a CPU, at any width."""
    return held_experts_route(x, w_gate, P)[0]


def held_experts_route(x, w_gate, P):
    """``(impl, interpret)``: :func:`held_experts_impl`'s answer and
    whether the kernel it names is interpreted (on the CPU), from ONE
    platform query — what ``held_experts_ffn`` hands the kernel it calls."""
    from ..base import getenv_bool
    T, d = x.shape
    f = w_gate.shape[-1]
    platform = _fa._platform_of(x)
    if platform != "tpu":
        if not getenv_bool("MXNET_FA_DECODE_FORCE_PALLAS"):
            return "lax_loop", False
    elif d % _LANES or f % _LANES:
        return "lax_loop", False
    if P < DECODE_PAIRS and T <= _MAX_TOKENS:
        return "pallas", platform == "cpu"
    sized = all(jnp.issubdtype(a.dtype, jnp.floating)
                and a.dtype.itemsize in (2, 4) for a in (x, w_gate))
    if sized and _row_tile_fits(d, f, w_gate.dtype.itemsize):
        return "pallas_sorted", platform == "cpu"
    return "lax_loop", False


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
                        f_block=None, interpret=False):
    """``x`` (T, d), ``local`` (T, k) int32 (a pair's expert among the
    ``count`` held, -1 where it counts for nothing), ``w`` (T, k) float32,
    the stacked matrices ``w_gate`` / ``w_up`` (count, d, f) and ``w_down``
    (count, f, d); ``interpret`` as :func:`held_experts_route` answered.
    Returns ``(y (T, d) float32, pairs_held, experts_touched)``."""
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
        interpret=interpret,
        name="held_experts",
    )(n, expert, local, w.astype(jnp.float32), x, w_gate, w_up, w_down)
    return y[:T], pairs_held, n[0]


# ---------------------------------------------------------------------------
# the sorted form: a prompt's pairs grouped by expert, a grid over row tiles
# ---------------------------------------------------------------------------

def group_pairs(idx, held, live=None):
    """The counting sort of ``held_experts_ffn``: the ``P = T * k`` (token,
    expert) pairs of ``idx`` (T, k) grouped by expert, those of the
    ``held = (first, count)`` experts first and in expert order, pairs of
    experts held elsewhere and of tokens that are not ``live`` last; a
    group keeps the pairs' order.  Returns ``(is_held (P,), n_live, n
    (count + 1,) pairs of each expert held — the rest last —, starts
    (count + 1,) a group's first sorted row, dest (P,) a pair's sorted
    row, src (P,) a sorted row's pair)``."""
    T, k = idx.shape
    P = T * k
    first, count = held
    local = idx.reshape(P) - first
    is_held = (local >= 0) & (local < count)
    n_live = jnp.asarray(T, jnp.int32)
    if live is not None:
        is_held = is_held & jnp.repeat(live, k)
        n_live = jnp.sum(live, dtype=jnp.int32)
    key = jnp.where(is_held, local, count)                       # (P,)
    onehot = (key[:, None] == jnp.arange(count + 1, dtype=jnp.int32)[None]
              ).astype(jnp.int32)                                # (P, c+1)
    n = jnp.sum(onehot, axis=0)                # pairs of each expert held
    starts = jnp.cumsum(n) - n
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    dest = starts[key] + rank                  # a pair's row once sorted
    src = jnp.zeros(P, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32))
    return is_held, n_live, n, starts, dest, src


def _row_tile_fits(d, f, itemsize):
    """Whether a tile of :data:`_ROW_TILE` sorted rows of width ``d`` rests
    in VMEM beside the blocks :func:`_f_block` gives the matrices."""
    fb = _f_block(d, f, itemsize)
    a_row = d * (2 * itemsize + 2 * 4 + 4 + 4 * (f // fb > 1)) + 3 * fb * 4
    return _ROW_TILE * a_row <= _ROW_TILE_BYTES


def _tile_visits(n_e, starts, tm, n_steps):
    """The work list of the sorted form: expert ``e``'s run is the sorted
    rows ``starts[e] .. starts[e] + n_e[e] - 1`` and is visited once a
    row tile of ``tm`` rows it reaches into, runs in expert order.
    Returns ``(n (1,), tile, expert, lo, hi)``, the last four
    ``(n_steps,)``: visit ``i`` multiplies tile ``tile[i]`` by expert
    ``expert[i]``'s matrices and writes its rows ``lo[i] <= row <
    hi[i]``; the list is padded by repeating its last entry."""
    count = n_e.shape[0]
    t0 = starts // tm
    visits = jnp.where(n_e > 0, (starts + n_e - 1) // tm - t0 + 1, 0)
    ends = jnp.cumsum(visits)
    n = ends[-1]
    step = jnp.minimum(jnp.arange(n_steps, dtype=jnp.int32),
                       jnp.maximum(n - 1, 0))
    expert = jnp.minimum(jnp.sum(step[:, None] >= ends[None, :], axis=1,
                                 dtype=jnp.int32), count - 1)
    tile = t0[expert] + step - (ends[expert] - visits[expert])
    lo = starts[expert]
    return n.reshape(1), tile, expert, lo, lo + n_e[expert]


def _sorted_kernel(n_ref, tile_ref, expert_ref, lo_ref, hi_ref, x_ref,
                   wg_ref, wu_ref, wd_ref, o_ref, *acc, act, n_f, tm):
    """Grid step ``(i, j)``: row tile ``tile_ref[i]`` against block ``j``
    of expert ``expert_ref[i]``'s hidden width.  ``o_ref`` (tm, d) float32
    is the tile's output, resting in VMEM while consecutive visits name
    the tile: the first of them zeroes the rows that are not its own, a
    later one leaves them as they are."""
    from jax.experimental import pallas as pl
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _visit():
        gate = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        mid = (gate(g) * u).astype(x.dtype)
        part = jnp.dot(mid, wd_ref[0], preferred_element_type=jnp.float32)

        def write(out):
            row = tile_ref[i] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, 1), 0)
            mine = (row >= lo_ref[i]) & (row < hi_ref[i])
            opens = (i == 0) | (tile_ref[i] != tile_ref[jnp.maximum(i - 1,
                                                                    0)])

            @pl.when(opens)
            def _first():
                o_ref[...] = jnp.where(mine, out, 0.0)

            @pl.when(jnp.logical_not(opens))
            def _later():
                o_ref[...] = jnp.where(mine, out, o_ref[...])

        if n_f == 1:
            write(part)
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
                write(acc_ref[...])


def _sorted_pallas(xs, n_e, starts, w_gate, w_up, w_down, act, tm, fb,
                   interpret):
    """``xs`` (Pp, d), whole tiles of ``tm`` sorted rows; ``n_e`` /
    ``starts`` (count,) the held experts' runs.  Returns (Pp, d) float32:
    a run's rows multiplied by its expert, the other rows of a visited
    tile zero, a tile no run reaches into NOT WRITTEN."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    Pp, d = xs.shape
    count, _, f = w_gate.shape
    n_f = f // fb
    n_steps = Pp // tm + count - 1      # a run's first tile may be shared
    work = _tile_visits(n_e, starts, tm, n_steps)

    # a step past the last visit names the blocks the last one read
    def rows(i, j, n, tile, *_):
        return tile[i], 0

    def col(i, j, n, tile, expert, *_):
        return expert[i], 0, jnp.where(i < n[0], j, n_f - 1)

    def row(i, j, n, tile, expert, *_):
        return expert[i], jnp.where(i < n[0], j, n_f - 1), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps, n_f),
        in_specs=[pl.BlockSpec((tm, d), rows),
                  pl.BlockSpec((1, d, fb), col),
                  pl.BlockSpec((1, d, fb), col),
                  pl.BlockSpec((1, fb, d), row)],
        out_specs=pl.BlockSpec((tm, d), rows),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)] * (n_f > 1),
    )
    return pl.pallas_call(
        functools.partial(_sorted_kernel, act=act, n_f=n_f, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Pp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="held_experts_sorted",
    )(*work, xs, w_gate, w_up, w_down)


def held_experts_sorted(x, idx, w, held, w_gate, w_up, w_down, live=None,
                        act="silu", row_tile=None, f_block=None,
                        interpret=False):
    """``held_experts_ffn``'s arguments and result for a prompt: grouping,
    kernel, un-sort and the weighted sum of a token's ``k`` rows — behind
    ONE jitted function, so that the layers of a program that call it
    alike share one traced and lowered body.  ``interpret`` as
    :func:`held_experts_route` answered."""
    return _held_experts_sorted(
        x, idx, w, w_gate, w_up, w_down, live,
        held=(int(held[0]), int(held[1])), act=act, row_tile=row_tile,
        f_block=f_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "held", "act", "row_tile", "f_block", "interpret"))
def _held_experts_sorted(x, idx, w, w_gate, w_up, w_down, live, *, held, act,
                         row_tile, f_block, interpret):
    T, k = idx.shape
    d = x.shape[-1]
    P = T * k
    count, _, f = w_gate.shape
    fb = int(f_block or _f_block(d, f, w_gate.dtype.itemsize))
    tm = int(row_tile or _ROW_TILE)
    is_held, n_live, n, starts, dest, src = group_pairs(idx, held, live)
    n_e = n[:count]
    src = jnp.pad(src, (0, -P % tm))            # whole tiles of sorted rows
    ys = _sorted_pallas(x[src // k], n_e, starts[:count], w_gate, w_up,
                        w_down, act, tm, fb, interpret)
    # a row of no held pair may lie in a tile the kernel never wrote
    got = jnp.where(is_held.reshape(T, k, 1), ys[dest].reshape(T, k, d), 0.0)
    y = jnp.sum(got * w[..., None], axis=1)
    counts = (n_live * k, jnp.sum(n_e, dtype=jnp.int32),
              jnp.sum(n_e > 0, dtype=jnp.int32))
    return y, counts
