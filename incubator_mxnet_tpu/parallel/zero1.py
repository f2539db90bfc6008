"""ZeRO-1 weight-update sharding (arXiv:2004.13336).

Data-parallel training replicates optimizer state and redundantly
computes the whole-tree update on every replica.  The paper's scheme —
stage 1 of ZeRO — partitions the *update* instead: each replica owns a
contiguous 1/N shard of the flattened parameter space, updates only its
shard of the weights and optimizer state, and an all-gather rebuilds the
full weights for the next forward pass.  The gradient all-reduce
decomposes into reduce-scatter (each replica receives the summed grads
for its shard) + all-gather (of updated weights), so per-replica
optimizer-state memory drops N× for the price of one weights-worth of
gather traffic per step.

This module provides the layout bookkeeping and the functional wrapper:

* :class:`ShardSpec` — the contiguous-slice layout of a fixed list of
  leaves flattened into one (or a few, grouped by a static key) 1-D
  buffers, each padded to a multiple of ``n_shards``.
* :func:`flatten_segment` / :func:`unflatten_segment` — pure ``jnp``
  transforms usable both in-program (traced) and eagerly.
* :class:`Zero1Optimizer` — wraps a ``parallel.optim``
  FunctionalOptimizer so its state lives as dp-sharded flat buffers and
  its update runs on the local shard only, with the weight all-gather
  expressed as a sharding constraint INSIDE the program — the whole
  thing stays within the single donated dispatch of ``SPMDTrainer``'s
  step and ``CompiledLoop``'s k-step scan.

The sharding is expressed with GSPMD constraints
(``lax.with_sharding_constraint`` on the flat buffers + ``out_shardings``
pinning the state to ``P(axis)``) rather than ``shard_map``: the
elementwise update cores need no index plumbing, and XLA places the
reduce-scatter / all-gather around the constrained region.  Because the
supported cores (sgd / momentum / nag / adam / adamw / rmsprop /
adagrad) are purely elementwise, the sharded update is the replicated
one's to rounding (another compiled program: docs/performance.md); rules with per-tensor reductions (LAMB's trust
ratio) straddle shard boundaries and are excluded
(``FunctionalOptimizer.elementwise`` is False → callers fall back).
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

from ..base import MXNetError

__all__ = ["Segment", "ShardSpec", "build_shard_spec", "flatten_segment",
           "unflatten_segment", "expand_per_leaf", "Zero1Optimizer",
           "per_replica_state_bytes"]


class Segment(NamedTuple):
    """One flat buffer: a run of leaves sharing a static key (dtype, and
    for the fused tier wd/multi-precision pattern), laid out back to
    back and zero-padded so ``padded % n_shards == 0``."""
    key: Any
    idx: Tuple[int, ...]          # positions in the original leaf list
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    dtype: Any                    # numpy dtype of the flat buffer
    total: int                    # sum(sizes)
    padded: int                   # total rounded up to n_shards multiple


class ShardSpec(NamedTuple):
    """Contiguous-slice layout of a fixed leaf list across ``n_shards``
    data-parallel shards.  Records enough to round-trip
    leaves <-> flat padded segments on host or in-program, and to
    re-partition a checkpoint saved at a different shard count."""
    n_shards: int
    n_leaves: int
    segments: Tuple[Segment, ...]


def _np():
    import numpy as np
    return np


def build_shard_spec(leaves, n_shards: int, keys=None) -> ShardSpec:
    """Group ``leaves`` (arrays or ShapeDtypeStructs) by ``keys``
    (default: dtype) preserving order within each group, and record the
    flat padded layout.  ``n_shards`` must be >= 1; padding makes every
    segment length divisible by it so a 1-D ``P(axis)`` sharding is
    always legal."""
    np = _np()
    if n_shards < 1:
        raise MXNetError(f"n_shards must be >= 1, got {n_shards}")
    leaves = list(leaves)
    if keys is None:
        keys = [np.dtype(x.dtype).str for x in leaves]
    if len(keys) != len(leaves):
        raise MXNetError("build_shard_spec: len(keys) != len(leaves)")
    order: List[Any] = []
    groups: dict = {}
    for i, (leaf, key) in enumerate(zip(leaves, keys)):
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    segments = []
    for key in order:
        idx = tuple(groups[key])
        shapes = tuple(tuple(int(d) for d in leaves[i].shape) for i in idx)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1
                      for s in shapes)
        offsets, off = [], 0
        for s in sizes:
            offsets.append(off)
            off += s
        total = off
        padded = total + (-total) % n_shards if total else n_shards
        segments.append(Segment(
            key=key, idx=idx, shapes=shapes, sizes=sizes,
            offsets=tuple(offsets), dtype=np.dtype(leaves[idx[0]].dtype),
            total=total, padded=padded))
    return ShardSpec(n_shards=int(n_shards), n_leaves=len(leaves),
                     segments=tuple(segments))


def flatten_segment(seg: Segment, leaves, dtype=None):
    """Concatenate the segment's leaves (raveled, optionally cast) into
    one zero-padded 1-D buffer.  Pure jnp — traceable."""
    import jax.numpy as jnp
    dt = dtype or seg.dtype
    parts = [jnp.ravel(leaves[i]).astype(dt) for i in seg.idx]
    pad = seg.padded - seg.total
    if pad or not parts:
        parts.append(jnp.zeros((pad if parts else seg.padded,), dt))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unflatten_segment(seg: Segment, flat):
    """Inverse of :func:`flatten_segment` (padding dropped): returns
    ``[(leaf_index, array), ...]`` in segment order.  Pure jnp."""
    out = []
    for i, shape, size, off in zip(seg.idx, seg.shapes, seg.sizes,
                                   seg.offsets):
        out.append((i, flat[off:off + size].reshape(shape)))
    return out


def expand_per_leaf(seg: Segment, values, dtype=None):
    """Per-leaf scalars → flat vector constant over each leaf's slice
    (zeros in the padding).  ``values`` indexes the ORIGINAL leaf list;
    elementwise-multiplying the result is bit-identical to broadcasting
    each scalar over its own leaf.  Pure jnp — traceable."""
    import jax.numpy as jnp
    dt = dtype or seg.dtype
    parts = [jnp.broadcast_to(values[i].astype(dt), (size,))
             for i, size in zip(seg.idx, seg.sizes)]
    pad = seg.padded - seg.total
    if pad or not parts:
        parts.append(jnp.zeros((pad if parts else seg.padded,), dt))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def flatten_tree(spec: ShardSpec, leaves):
    """All segments of ``leaves`` as a tuple of flat padded buffers."""
    return tuple(flatten_segment(seg, leaves) for seg in spec.segments)


def unflatten_tree(spec: ShardSpec, flats):
    """Inverse of :func:`flatten_tree`: tuple of leaves in original
    order."""
    out: List[Any] = [None] * spec.n_leaves
    for seg, flat in zip(spec.segments, flats):
        for i, arr in unflatten_segment(seg, flat):
            out[i] = arr
    return tuple(out)


def per_replica_state_bytes(tree) -> int:
    """Bytes of optimizer state ONE replica materializes: each leaf's
    per-device shard shape (full shape when unsharded/eager) times its
    itemsize — the feed for the ``mxtpu_optimizer_state_bytes`` gauge."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree.leaves(tree):
        if leaf is None:
            continue
        shape = tuple(leaf.shape)
        sh = getattr(leaf, "sharding", None)
        if sh is not None:
            try:
                shape = sh.shard_shape(shape)
            except Exception:
                pass
        total += int(np.prod(shape, dtype=np.int64)) * \
            np.dtype(leaf.dtype).itemsize
    return total


def zero1_allgather_bytes(spec: ShardSpec) -> int:
    """Per-step, per-replica inbound all-gather volume the scheme adds:
    every replica receives the other N-1 shards of each flat weight
    buffer after the sharded update."""
    np = _np()
    n = spec.n_shards
    total = 0
    for seg in spec.segments:
        total += seg.padded * np.dtype(seg.dtype).itemsize
    return total * (n - 1) // n


class Zero1Optimizer:
    """ZeRO-1 wrapper around a ``parallel.optim`` FunctionalOptimizer.

    Duck-types the ``(init, update)`` pair SPMDTrainer / CompiledLoop
    drive, but:

    * ``init`` flattens the params into per-dtype padded segments and
      places the base optimizer's state — whose leaves are now those
      flat buffers — with ``NamedSharding(mesh, P(axis))``, so each
      replica holds 1/N of every state buffer;
    * ``update`` flattens params and grads IN-PROGRAM, pins them to
      ``P(axis)`` (the slice is free under GSPMD; with a preceding
      psum the compiler fuses it into a reduce-scatter), runs the base
      update on the flat tree, re-pins the new state to ``P(axis)`` and
      the new flat weights to replicated — the all-gather — then
      unflattens.  No host round-trip: callers' donated single dispatch
      is preserved.

    The portable_state / from_portable pair converts between the flat
    sharded layout and the plain per-leaf layout the unsharded tier
    uses, making checkpoints independent of the shard count (save at
    N=8, resume at N=4) and interchangeable with non-ZeRO trainers.
    """

    def __init__(self, base, mesh, axis: str = "data"):
        if not getattr(base, "elementwise", True):
            raise MXNetError(
                "zero1: optimizer update is not elementwise (per-tensor "
                "reductions straddle shard boundaries) — use the "
                "unsharded path")
        self.base = base
        self.mesh = mesh
        self.axis = axis
        self.spec: Optional[ShardSpec] = None
        self.n_shards = int(mesh.shape[axis])

    # -- sharding helpers ----------------------------------------------
    def _sharded(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec(self.axis))

    def _replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh, PartitionSpec())

    def _map_flats(self, state, fn):
        """Apply ``fn`` to every flat buffer in the state.  The base
        optimizers all return ``{name: params-shaped tree}`` where the
        params tree here is the tuple of flat segments."""
        import jax
        return jax.tree.map(fn, state)

    # -- FunctionalOptimizer surface -----------------------------------
    def init(self, params):
        import jax
        leaves = jax.tree.leaves(params)
        self.spec = build_shard_spec(leaves, self.n_shards)
        flats = flatten_tree(self.spec, leaves)
        state = self.base.init(flats)
        shard = self._sharded()
        return self._map_flats(state, lambda v: jax.device_put(v, shard))

    def update(self, params, grads, state, step):
        import jax
        from jax.lax import with_sharding_constraint as wsc
        if self.spec is None:
            raise MXNetError("zero1: update before init")
        spec = self.spec
        p_leaves, treedef = jax.tree.flatten(params)
        g_leaves = jax.tree.leaves(grads)
        shard, repl = self._sharded(), self._replicated()
        flat_p = tuple(wsc(f, shard) for f in flatten_tree(spec, p_leaves))
        flat_g = tuple(wsc(f, shard) for f in flatten_tree(spec, g_leaves))
        new_fp, new_state = self.base.update(flat_p, flat_g, state, step)
        new_state = self._map_flats(new_state, lambda v: wsc(v, shard))
        # the all-gather: replicating the updated flat weights is the
        # only cross-replica traffic the scheme adds.  The barrier keeps
        # the update arithmetic out of the all-gather's fusion cluster —
        # fused in, XLA re-contracts the multiply-add chains (FMA
        # placement changes) and results drift 1-2 ulp off the unsharded
        # program; the kernel boundary preserves bit parity.
        new_fp = tuple(wsc(jax.lax.optimization_barrier(f), repl)
                       for f in new_fp)
        new_leaves = unflatten_tree(spec, new_fp)
        return jax.tree.unflatten(treedef, new_leaves), new_state

    # -- state layout conversions --------------------------------------
    def state_shardings(self, state):
        sh = self._sharded()
        return self._map_flats(state, lambda v: sh)

    def portable_state(self, state, fetch=None):
        """Sharded flat state → host numpy state with the SAME structure
        the unsharded functional tier produces ({name: per-leaf tuple}),
        so checkpoints are shard-count-agnostic."""
        import numpy as np
        if fetch is None:
            fetch = lambda v: np.asarray(v)         # noqa: E731
        spec = self.spec

        def to_leaves(flats):
            flats = tuple(fetch(f) for f in flats)
            return unflatten_tree(spec, flats)
        return {k: to_leaves(v) for k, v in state.items()}

    def from_portable(self, state):
        """Per-leaf state (from :meth:`portable_state`, possibly saved
        at a DIFFERENT shard count, or from an unsharded trainer) →
        flat buffers placed with the current mesh's sharding."""
        import jax
        shard = self._sharded()

        def to_flats(leaves):
            flats = flatten_tree(self.spec, list(leaves))
            return tuple(jax.device_put(f, shard) for f in flats)
        return {k: to_flats(v) for k, v in state.items()}
