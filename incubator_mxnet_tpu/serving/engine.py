"""InferenceEngine — donated, jitted forward programs keyed by shape
bucket.

The serving problem on XLA is compile-cache discipline: every distinct
input shape is a fresh trace+compile, so serving raw request shapes
means unbounded compilation.  The engine fixes the shape space up
front — a sorted list of batch-size **buckets** (declared, or
auto-derived powers of two up to ``max_batch_size``) — and pads every
request batch up to the next bucket, so a stream of mixed-size requests
leaves the jit cache bounded by the bucket count (the acceptance
invariant: exactly one compiled program per (model, bucket)).

One engine wraps one model — a Gluon ``(Hybrid)Block``
(:meth:`from_block`), a bound ``Module`` (:meth:`from_module`), or an
exported/checkpointed symbol+params pair (:meth:`from_symbol`,
:meth:`from_export`) — as a single pure function
``(inputs, params, aux, key) -> outputs`` under ``jax.jit`` with the
input batch donated (the request buffers are dead after dispatch, so
XLA may reuse them for outputs).  Parameter values are fetched per
dispatch, so live weight updates (e.g. a trainer running in the same
process) propagate without recompiling.

The jit is wrapped in :func:`telemetry.instrument_jit` under
``serving:<name>`` — compile cache hits/misses, cost analysis, and
``jit:serving:<name>`` spans ride the existing observability plane.
"""
from __future__ import annotations

import contextlib
import threading
import warnings
import weakref
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError
from ..compile_cache import ensure_compile_cache
from ..context import current_context
from ..ndarray.ndarray import NDArray
from .. import telemetry as _telemetry
from .. import telemetry_device as _telemetry_device
from .. import health as _health
from . import metrics as _m
from .kvcache import (NO_SNAPSHOTS, BlockPool, KVLayout, blocks_for,
                      cache_forms, grouped_pool_shape,
                      pool_layout as _pool_layout)

__all__ = ["InferenceEngine", "GenerationEngine", "derive_buckets",
           "derive_prefill_buckets"]


@contextlib.contextmanager
def _donating():
    """Around a call that donates buffers: donation is advisory on the
    CPU, which says so on every call."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def derive_buckets(max_batch_size: int) -> Tuple[int, ...]:
    """Powers of two up to (and always including) ``max_batch_size``:
    ``derive_buckets(32) == (1, 2, 4, 8, 16, 32)``,
    ``derive_buckets(24) == (1, 2, 4, 8, 16, 24)``."""
    m = int(max_batch_size)
    if m < 1:
        raise MXNetError(f"max_batch_size must be >= 1, got {m}")
    out, b = [], 1
    while b < m:
        out.append(b)
        b *= 2
    out.append(m)
    return tuple(out)


def _canon_specs(input_specs):
    """[(per-example shape, dtype)] with the batch dim EXCLUDED."""
    if input_specs is None:
        return None
    out = []
    for spec in input_specs:
        if isinstance(spec, tuple) and len(spec) == 2 \
                and isinstance(spec[0], (tuple, list)):
            shape, dtype = spec
        else:
            shape, dtype = spec, _np.float32
        out.append((tuple(int(d) for d in shape), _np.dtype(dtype)))
    return out


def _register_device_observers(engine) -> None:
    """Enroll an engine in the device-observability plane
    (telemetry_device): a program-inventory callback (``GET /programs``,
    flight dumps) and per-owner memory attribution (params, and the KV
    cache for generation engines).  All weak — a telemetry registration
    must never keep a dead engine's caches alive; a collected engine
    reports empty/zero until a successor with the same name replaces
    the registration."""
    wref = weakref.ref(engine)

    def inventory():
        eng = wref()
        return eng.program_inventory() if eng is not None else {}

    def param_bytes():
        eng = wref()
        if eng is None:
            return 0
        try:
            pv, av = eng._param_fn()
            return sum(int(v.size) * v.dtype.itemsize
                       for vals in (pv, av) for v in vals)
        except Exception:
            return 0

    _telemetry_device.register_inventory(engine.name, inventory)
    _telemetry_device.register_owner("params:" + engine.name, param_bytes)
    if hasattr(engine, "cache_bytes"):
        def kv_bytes():
            eng = wref()
            return eng.cache_bytes + eng.state_bytes \
                if eng is not None else 0
        _telemetry_device.register_owner("kv:" + engine.name, kv_bytes)


class InferenceEngine:
    """A model as a bucketed set of compiled inference programs.

    ``pure_fn(in_vals, param_vals, aux_vals, key) -> tuple(outputs)``
    must be a pure jax function; ``param_fn() -> (param_vals, aux_vals)``
    supplies the CURRENT weight values per dispatch.  Most callers build
    engines via :meth:`from_block` / :meth:`from_symbol` /
    :meth:`from_module` / :meth:`from_export` instead of this
    constructor.
    """

    def __init__(self, pure_fn: Callable, input_names: Sequence[str],
                 param_fn: Callable, *, name: str = "model",
                 buckets: Optional[Sequence[int]] = None,
                 max_batch_size: Optional[int] = None,
                 input_specs=None, ctx=None):
        import jax
        ensure_compile_cache()
        self.name = str(name)
        self.input_names = [str(n) for n in input_names]
        self._param_fn = param_fn
        self._ctx = ctx if ctx is not None else current_context()
        self.input_specs = _canon_specs(input_specs)
        if buckets:
            self.buckets = tuple(sorted({int(b) for b in buckets}))
            if self.buckets[0] < 1:
                raise MXNetError(f"buckets must be >= 1: {self.buckets}")
        elif max_batch_size:
            self.buckets = derive_buckets(max_batch_size)
        else:
            self.buckets = ()       # exact-shape mode (the predict ABI)
        self.max_batch_size = self.buckets[-1] if self.buckets else None
        self._jit = jax.jit(pure_fn, donate_argnums=(0,))
        self._call = _telemetry.instrument_jit("serving:" + self.name,
                                               self._jit)
        self._shapes_seen = set()
        self._warmup_done = False
        _register_device_observers(self)

    @property
    def input_dtypes(self):
        """Declared per-input dtypes (from ``input_specs``), or None
        when the engine was built without specs — the HTTP front-end
        uses these to decode JSON tensors at the model's real dtypes
        instead of forcing float32."""
        if not self.input_specs:
            return None
        return [dtype for _, dtype in self.input_specs]

    @property
    def warm(self) -> bool:
        """True once every declared bucket has a compiled program (the
        readiness gate: a replica is not *ready* until its programs
        are).  Bucket-free (exact-shape) engines are vacuously warm."""
        if not self.buckets:
            return True
        if self._warmup_done:
            return True
        return self.compiled_programs() >= len(self.buckets)

    # -- shape bucketing ------------------------------------------------
    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest bucket that fits ``n`` rows (None when ``n`` exceeds
        the largest bucket — the caller chunks)."""
        for b in self.buckets:
            if b >= int(n):
                return b
        return None

    # -- dispatch -------------------------------------------------------
    def _prepare(self, arrays, target: Optional[int]):
        """Convert to jax values, pad the batch dim up to ``target``.
        Buffers we did not create are copied — the jit donates its input
        batch, and donation must never eat a caller-owned array."""
        import jax.numpy as jnp
        vals = []
        for a in arrays:
            if isinstance(a, NDArray):
                v, owned = a._data, False
            elif isinstance(a, jnp.ndarray) and not isinstance(a, _np.ndarray):
                v, owned = a, False
            else:
                v, owned = jnp.asarray(a), True
            if target is not None and v.shape[0] != target:
                pad = target - int(v.shape[0])
                if pad < 0:
                    raise MXNetError(
                        f"{self.name}: batch {v.shape[0]} exceeds bucket "
                        f"{target}")
                v = jnp.concatenate(
                    [v, jnp.zeros((pad,) + tuple(v.shape[1:]), v.dtype)],
                    axis=0)
            elif not owned:
                v = v.copy()
            vals.append(v)
        return tuple(vals)

    def _dispatch(self, in_vals: tuple):
        from .. import random as _random
        self._shapes_seen.add(tuple(v.shape for v in in_vals))
        param_vals, aux_vals = self._param_fn()
        key = _random.new_key(self._ctx)
        try:
            with _telemetry.trace_span("serve.infer", cat="serving",
                                       model=self.name,
                                       batch=int(in_vals[0].shape[0])):
                with _donating():
                    return self._call(in_vals, tuple(param_vals),
                                      tuple(aux_vals), key)
        except Exception as e:
            if _telemetry_device.is_oom(e):
                _telemetry_device.report_oom("serving." + self.name, e,
                                             model=self.name)
            raise

    def predict(self, arrays: Sequence) -> List:
        """Run one batch: pad up to the next bucket, dispatch ONE
        compiled program, slice outputs back to the true row count.
        Batches larger than the biggest bucket are chunked.  Outputs are
        jax arrays (``np.asarray`` them for host use)."""
        arrays = list(arrays)
        if len(arrays) != len(self.input_names):
            raise MXNetError(
                f"{self.name}: got {len(arrays)} inputs, expected "
                f"{len(self.input_names)} ({self.input_names})")
        if not self.buckets:
            return list(self._dispatch(self._prepare(arrays, None)))
        n = int(arrays[0].shape[0])
        bucket = self.bucket_for(n)
        if bucket is None:          # chunk by the largest bucket
            import jax.numpy as jnp
            step = self.buckets[-1]
            chunks = [self.predict([a[i:i + step] for a in arrays])
                      for i in range(0, n, step)]
            return [jnp.concatenate([c[k] for c in chunks], axis=0)
                    for k in range(len(chunks[0]))]
        outs = self._dispatch(self._prepare(arrays, bucket))
        if bucket == n:
            return list(outs)
        return [o[:n] for o in outs]

    def run_exact(self, arrays: Sequence) -> List:
        """Dispatch at the exact input shapes, no bucketing — the
        per-shape compiled-program cache for the C predict ABI, where
        shapes are declared up front and ``reshape`` handles share one
        engine."""
        return list(self._dispatch(self._prepare(list(arrays), None)))

    def warmup(self) -> int:
        """AOT-compile every declared bucket (requires ``input_specs``);
        returns the number of buckets warmed."""
        if not self.buckets:
            return 0
        if not self.input_specs:
            raise MXNetError(
                f"{self.name}: warmup needs input_specs (per-example "
                "shapes) to synthesize bucket batches")
        for b in self.buckets:
            self.predict([_np.zeros((b,) + shape, dtype)
                          for shape, dtype in self.input_specs])
        self._warmup_done = True
        return len(self.buckets)

    def compiled_programs(self) -> int:
        """Entries in the jit compile cache — bounded by the bucket
        count for bucketed serving."""
        try:
            return int(self._jit._cache_size())
        except Exception:
            return len(self._shapes_seen)

    def program_inventory(self) -> dict:
        """Runtime program-set inventory (``GET /programs``, flight
        dumps): expected vs compiled program counts plus this engine's
        dispatch-ledger row (dispatch count, wall-time stats,
        last-dispatch age)."""
        site = "serving:" + self.name
        ledger = _telemetry.dispatch_ledger(prefix=site)
        return {
            "model": self.name,
            "expected_programs": len(self.buckets) or None,
            "compiled_programs": self.compiled_programs(),
            "warm": self.warm,
            "programs": {k: v for k, v in ledger.items() if k == site},
        }

    def __repr__(self):
        return (f"<InferenceEngine {self.name!r}: inputs="
                f"{self.input_names}, buckets={list(self.buckets)}, "
                f"programs={self.compiled_programs()}>")

    # -- constructors ---------------------------------------------------
    @classmethod
    def from_block(cls, block, input_specs, *, name: Optional[str] = None,
                   buckets=None, max_batch_size: Optional[int] = None,
                   ctx=None):
        """Wrap a Gluon ``Block``/``HybridBlock``.  ``input_specs`` are
        per-example shapes (batch dim excluded), e.g. ``[(784,)]``;
        deferred-init parameters are settled with one zero forward."""
        from .. import ndarray as nd
        from .. import autograd as _ag
        from ..gluon.block import functional_call
        specs = _canon_specs(input_specs)
        if not specs:
            raise MXNetError("from_block: input_specs is required")
        ctx = ctx if ctx is not None else current_context()
        params = list(block.collect_params().values())
        if any(p._deferred_init is not None or p._data is None
               for p in params):
            probe = [nd.zeros((1,) + shape, ctx=ctx, dtype=dtype)
                     for shape, dtype in specs]
            with _ag.pause(train_mode=False):
                block(*probe)
            params = list(block.collect_params().values())
        trainable = [p for p in params if p.grad_req != "null"]
        aux = [p for p in params if p.grad_req == "null"]

        def param_fn():
            return (tuple(p._data._data for p in trainable),
                    tuple(p._data._data for p in aux))

        def pure(in_vals, param_vals, aux_vals, key):
            inputs_nd = [NDArray(v) for v in in_vals]
            out_vals, _ = functional_call(
                block, trainable, list(param_vals), aux, list(aux_vals),
                inputs_nd, False, key)
            return tuple(out_vals)

        names = ["data"] if len(specs) == 1 else \
            [f"data{i}" for i in range(len(specs))]
        return cls(pure, names, param_fn,
                   name=name or getattr(block, "name", "block"),
                   buckets=buckets, max_batch_size=max_batch_size or 32,
                   input_specs=specs, ctx=ctx)

    @classmethod
    def from_symbol(cls, symbol, arg_params, aux_params, input_names,
                    *, input_specs=None, output_names=(),
                    name: Optional[str] = None, buckets=None,
                    max_batch_size: Optional[int] = None, ctx=None):
        """Wrap a symbol + decoded params (a checkpoint / export pair).
        ``output_names`` selects internal outputs by name (the partial-out
        contract of the predict ABI); empty means the symbol's own
        outputs.  Without ``buckets``/``max_batch_size`` the engine runs
        in exact-shape mode (:meth:`run_exact`)."""
        from .. import ndarray as nd
        from .. import autograd as _ag
        from .. import random as _random
        from ..symbol import symbol as sym_mod
        from ..symbol.symbol import eval_graph
        if output_names:
            internals = symbol.get_internals()
            symbol = sym_mod.Group([internals[str(n)]
                                    for n in output_names])
        input_names = [str(n) for n in input_names]
        ctx = ctx if ctx is not None else current_context()
        arg_params = arg_params or {}
        aux_params = aux_params or {}
        param_names = [n for n in symbol.list_arguments()
                       if n not in input_names]
        for n in param_names:
            if n not in arg_params:
                raise ValueError(f"parameter {n!r} missing from the "
                                 ".params bytes and not a declared input")
        aux_names = symbol.list_auxiliary_states()
        for n in aux_names:
            if n not in aux_params:
                raise MXNetError(f"from_symbol: aux_states missing {n!r}")
        as_nd = lambda v: v if isinstance(v, NDArray) \
            else nd.array(v, ctx=ctx)
        params = {n: as_nd(arg_params[n]) for n in param_names}
        aux = {n: as_nd(aux_params[n]) for n in aux_names}

        def param_fn():
            return (tuple(params[n]._data for n in param_names),
                    tuple(aux[n]._data for n in aux_names))

        def pure(in_vals, param_vals, aux_vals, key):
            values = {n: NDArray(v) for n, v in zip(input_names, in_vals)}
            values.update({n: NDArray(v)
                           for n, v in zip(param_names, param_vals)})
            values.update({n: NDArray(v)
                           for n, v in zip(aux_names, aux_vals)})
            sink = {}
            with _ag.pause(train_mode=False), _random.trace_stream(key):
                outs = eval_graph(symbol, values, False, sink)
            return tuple(o._data for o in outs)

        return cls(pure, input_names, param_fn,
                   name=name or getattr(symbol, "name", "symbol"),
                   buckets=buckets, max_batch_size=max_batch_size,
                   input_specs=input_specs, ctx=ctx)

    @classmethod
    def from_module(cls, module, **kw):
        """Wrap a bound, initialized ``Module``.  Data names become the
        engine inputs; label arguments (if the symbol has any) ride as
        fixed arrays from the module's executor — suitable for
        label-free inference outputs."""
        if not module.binded or not module.params_initialized:
            raise MXNetError("from_module: bind() and init_params() first")
        input_names = list(module._data_names)
        arg = dict(module._exec.arg_dict)
        params = {n: v for n, v in arg.items() if n not in input_names}
        kw.setdefault("input_specs",
                      [(tuple(d.shape[1:]), d.dtype)
                       for d in module._data_shapes])
        kw.setdefault("max_batch_size",
                      int(module._data_shapes[0].shape[0])
                      if module._data_shapes else None)
        kw.setdefault("name", getattr(module._symbol, "name", "module"))
        return cls.from_symbol(module._symbol, params,
                               dict(module._exec.aux_dict), input_names,
                               **kw)

    @classmethod
    def from_export(cls, prefix: str, epoch: int = 0,
                    input_names=("data",), **kw):
        """Load a ``HybridBlock.export`` / ``model.save_checkpoint``
        artifact pair (``<prefix>-symbol.json`` +
        ``<prefix>-NNNN.params``)."""
        import os
        from .. import model
        sym, arg_params, aux_params = model.load_checkpoint(prefix,
                                                            int(epoch))
        kw.setdefault("name", os.path.basename(str(prefix)) or "export")
        return cls.from_symbol(sym, arg_params, aux_params, input_names,
                               **kw)


# ===========================================================================
# GenerationEngine — continuous-batching autoregressive decode
# ===========================================================================

def derive_prefill_buckets(max_len: int, smallest: int = 8):
    """Prompt-length buckets: powers of two from ``smallest`` up to (and
    always including) ``max_len`` — ``derive_prefill_buckets(128) ==
    (8, 16, 32, 64, 128)``.  One compiled prefill program per bucket."""
    m = int(max_len)
    if m < 1:
        raise MXNetError(f"max_len must be >= 1, got {m}")
    out, b = [], min(int(smallest), m)
    while b < m:
        out.append(b)
        b *= 2
    out.append(m)
    return tuple(out)


# Columns of a slot's row in the slot state (GenerationEngine, "the slot
# state"); the slot's block table follows from _TABLE on.  Temperature
# and top-p ride as their float32 bit patterns, the root key as its two
# uint32 words, so that a row is ONE int32 vector.
_LAST, _POS, _BUDGET, _EOS, _DONE, _TOPK, _TEMP, _TOPP, _KEY0, _KEY1 = \
    range(10)
_TABLE = 10
#: what the dispatch columns (_LAST.._DONE) of a free slot hold: the
#: values the batcher passes for a slot without a request
_FREE = (0, 0, 1, -1, 1)
#: slot rows one dispatch of the row edit writes (fewer are padded by
#: repeating the first)
_EDIT_ROWS = 4


class GenerationEngine:
    """Autoregressive generation as a closed set of compiled programs
    over a PREALLOCATED paged KV cache: per layer one K and one V pool of
    ``num_blocks`` blocks of ``block_size`` positions, managed by a
    :class:`~.kvcache.BlockPool` — and, for a layer that keeps a state of
    constant size a sequence instead of keys (``KVLayout.states``), one
    array a leaf of that state beside the pools, a row a slot plus the
    snapshot rows prefix hits start from (docs/serving.md "The layer
    interface", "State snapshots").

    The naive serving path re-runs prefill over the whole growing
    context every token — O(n^2) work and one fresh dispatch per request
    per token.  This engine splits the work once:

    * ``prefill(tokens, slot)`` — full-prefix forward at the request's
      prompt-length bucket, writing the slot's K/V into its blocks and
      returning the first generated token.  One compiled program per
      prefill bucket (:func:`derive_prefill_buckets`), and one more per
      bucket for prefix-cache hits, which prefill only the unshared
      suffix.
    * ``decode(last_tokens, positions)`` — ONE fixed-shape dispatch
      advancing every slot one token: embeds each slot's last token at
      its own position, appends K/V at that position, and attends over
      its live prefix through the block table (the layer's cache form,
      :mod:`~.kvcache`).  Exactly one compiled program, regardless of how
      many requests are in flight or how long they run; ``decode_burst`` scans
      ``scan_steps`` of them into one dispatch, and ``verify`` scores a
      draft's proposals ``spec_k + 1`` positions wide.

    Each slot addresses its K/V through an int32 *block table* operand —
    an (S, max_blocks) array that enters the SAME compiled programs as
    data, never as a shape.  A request reserves only ``ceil((prompt +
    budget) / block_size)`` blocks, so a byte budget admits many more
    concurrent streams than ``max_len`` rows would, and full prompt
    blocks are shared across requests via the pool's prefix cache.

    **The slot state.**  What a program reads per slot — last token,
    position, remaining budget, stop id, ``done``, block table row,
    temperature, top-k, top-p, logit-bias row, root key — and the
    dispatch key live on the device in ONE structure (``rows`` (S,
    10 + max_blocks) int32, ``bias`` (S, vocab) float32, ``key``).  The
    decode, burst and verify programs take it donated, like the cache,
    and return it advanced: a burst's carry ends holding each slot's
    next last token, position and ``done``, so a dispatch that follows
    no change uploads nothing.  The host keeps the same rows as numpy
    (the authority for :meth:`reset`, a failed dispatch and
    introspection), advances them by the same arithmetic from the tokens
    it pulls, and edits the device's copy BY ROW where a join, a leave or
    the batcher's own arrays say otherwise (:meth:`_slot_state`).

    Every program takes the whole cache DONATED (the engine owns it and
    rebinds the returned buffers), so XLA updates the cache in place.
    The cache is single-writer by contract: only the continuous
    batcher's worker thread dispatches.  Free slots still flow through
    ``decode`` (their table is all null block, where writes are
    harmless, and the layers are told they are not live), which is what
    keeps the program count at one.

    The serving contract is determinism: greedy (temperature 0) cached
    decode matches the full re-forward token-for-token, and a seeded
    sampled run replays bit-identically through per-step decode, bursts
    and speculative verify.

    **The model behind it** supplies the serving layer interface
    (``kv_layout`` / ``serve_embed`` / ``serve_layers`` / ``serve_head``,
    and ``serve_prefill`` / ``serve_cached`` on each layer;
    docs/serving.md "The layer interface").  The programs call that and
    nothing of a layer's insides, and allocate the pools from the
    model's :class:`~.kvcache.KVLayout` — KV heads, head size, type,
    window per layer.  ``models.gpt.GPTModel`` and
    ``models.afmoe.AFMoEModel`` implement it.

    Decode attention on a TPU is the Pallas kernel that reads the pool
    in place up to each slot's write head, on the CPU the lax gather
    that is its reference (``program_inventory()["paged_attention"]``
    says which).
    """

    def __init__(self, block, *, name: Optional[str] = None,
                 max_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 paged: Optional[bool] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 scan_steps: Optional[int] = None,
                 logprobs_topn: Optional[int] = None,
                 state_snapshot_tokens: Optional[int] = None,
                 state_snapshot_rows: Optional[int] = None,
                 ctx=None):
        import jax
        from ..base import getenv_int, getenv_bool
        ensure_compile_cache()
        for attr in ("kv_layout", "serve_embed", "serve_layers",
                     "serve_head"):
            if not hasattr(block, attr):
                raise MXNetError(
                    "GenerationEngine needs a block with the serving "
                    "layer interface (kv_layout/serve_embed/serve_layers/"
                    "serve_head, docs/serving.md); "
                    f"{type(block).__name__} has no {attr!r}")
        self.block = block
        self.name = str(name or getattr(block, "name", "gpt"))
        self._ctx = ctx if ctx is not None else current_context()
        #: what the paged attention entry points picked when a decode
        #: program was last traced ("pallas" | "lax_gather"); None until
        #: then
        self._paged_attention = None
        self._paged_impls = set()
        #: the layers whose paged calls take the grouped kernel, which
        #: fetches a step's blocks in one copy where the table names them
        #: in a row (layer -> its window), the blocks a group and the
        #: groups a step of it takes, and each slot's table reduced to
        #: what the run flags need (:meth:`_count_paged_groups`: each
        #: group's run length and the full runs before it, a row a slot,
        #: redone for the slots whose table changed)
        self._run_layers = {}
        self._run_step = None
        self._run_rows = None
        self._run_stale = set()
        #: what ``held_experts_impl`` answered when each program was
        #: traced (program -> "pallas[_sorted]" | "lax_loop"; one without an
        #: expert layer leaves it empty), and the dispatches by it
        self._experts_impl = {}
        self._expert_paths = {}
        self.max_slots = int(max_slots
                             or getenv_int("MXNET_GEN_MAX_SLOTS", 8))
        if self.max_slots < 1:
            raise MXNetError(f"max_slots must be >= 1: {self.max_slots}")
        #: the model's own statement of what its layers cache
        #: (:class:`~.kvcache.KVLayout`): pools are allocated from it
        self.layout = KVLayout.of(block.kv_layout())
        blk_len = self.layout.max_length
        self.max_len = min(int(max_len
                               or getenv_int("MXNET_GEN_MAX_LEN", blk_len)),
                           blk_len)
        if self.max_len < 2:
            raise MXNetError(f"max_len must be >= 2: {self.max_len}")
        self._layers = list(block.serve_layers())
        self.num_layers = self.layout.num_layers
        if len(self._layers) != self.num_layers:
            raise MXNetError(
                f"{self.name}: kv_layout() states {self.num_layers} "
                f"layers, serve_layers() gives {len(self._layers)}")
        #: KV heads and their size — what a pool's blocks hold (a model
        #: with grouped heads has more query heads than these)
        self.num_heads = self.layout.kv_heads
        self.head_dim = self.layout.head_dim
        #: integer counters the model's layers return from the decode
        #: programs, added on the host to ``metrics.MODEL_COUNTERS``
        self._counters = tuple(getattr(block, "serve_counters", ()))
        for n in self._counters:
            if n not in _m.MODEL_COUNTERS:
                raise MXNetError(
                    f"{self.name}: the model counts {n!r}, which "
                    "serving/metrics.py MODEL_COUNTERS does not register")
        #: the window of the model's windowed layers (the narrowest, were
        #: they to differ), None where every layer reads every position
        self._window = min(
            (w for w in self.layout.windows if w is not None), default=None)
        self._decode_counts = dict.fromkeys(
            self._counters + ("decode_context_tokens",)
            + (("decode_window_tokens",) if self._window else ()), 0)
        if prefill_buckets:
            self.prefill_buckets = tuple(sorted(
                {int(b) for b in prefill_buckets}))
            if self.prefill_buckets[0] < 1 \
                    or self.prefill_buckets[-1] > self.max_len:
                raise MXNetError(
                    f"prefill buckets must be in [1, {self.max_len}]: "
                    f"{self.prefill_buckets}")
        else:
            self.prefill_buckets = derive_prefill_buckets(self.max_len)
        if paged is not None and not paged:
            # the keyword outlives the mode only until benchmark/chip's
            # callers stop passing it (ROADMAP D2)
            raise MXNetError(
                f"{self.name}: paged=False: the dense KV mode is gone; "
                "the engine has one cache, the block pool")
        self.block_size = int(block_size
                              or getenv_int("MXNET_KV_BLOCK_SIZE", 16))
        if self.block_size < 1:
            raise MXNetError(f"block_size must be >= 1: {self.block_size}")
        self.prefix_cache_enabled = bool(
            getenv_bool("MXNET_KV_PREFIX_CACHE", True)
            if prefix_cache is None else prefix_cache)
        self.max_blocks_per_slot = -(-self.max_len // self.block_size)
        nb = int(num_blocks or getenv_int("MXNET_KV_NUM_BLOCKS", 0)) \
            or 1 + self.max_slots * self.max_blocks_per_slot
        if nb < 1 + self.max_blocks_per_slot:
            raise MXNetError(
                f"num_blocks {nb} cannot hold even one max_len slot "
                f"({self.max_blocks_per_slot} blocks + null block)")
        self.num_blocks = nb
        #: one form a layer: what it caches, where, how written and read
        self._forms, self._n_pools = cache_forms(
            self.layout, self._layers, self.block_size,
            self.max_blocks_per_slot, self._note_paged_attention)
        #: the layers that keep a state of constant size a sequence
        self._state_layers = tuple(
            f.layer for f in self._forms if f.keeps_state)
        # (benchmark/chip/tools read these two off an engine)
        self._pool_ids = {f.layer: f.ids for f in self._forms
                          if not f.keeps_state}
        self._n_kv = len(self._pool_ids)
        #: the layers that choose the keys they read (layer -> how many)
        self._select_layers = {f.layer: f.select for f in self._forms
                               if f.select is not None}
        if self._select_layers:
            self._decode_counts.update(index_keys_scored=0,
                                       index_keys_selected=0)
        # the state store's rows: one a slot (row s is slot s's), then the
        # snapshot rows the pool hands out, then one null row, where a
        # program writes a state nobody keeps
        self.state_snapshot_tokens = self.state_snapshot_rows = 0
        if self._state_layers and self.prefix_cache_enabled:
            self.state_snapshot_tokens = int(
                state_snapshot_tokens or 128 * self.block_size)
            self.state_snapshot_rows = int(
                4 * self.max_slots if state_snapshot_rows is None
                else state_snapshot_rows)
        self._null_row = self.max_slots + self.state_snapshot_rows
        #: bytes of ONE sequence's state over the state-space layers
        #: (those that state ``ssm_layers``), 0 for a model without any:
        #: ``mxtpu_ssm_state_bytes``, and the switch of the two counters
        self._ssm_bytes = sum(
            int(_np.prod(shape)) * _np.dtype(dtype).itemsize
            for l in self._state_layers
            if getattr(self._layers[l], "ssm_layers", 0)
            for shape, dtype in self._forms[l].leaves)
        if self._ssm_bytes:
            _m.SSM_STATE_BYTES.set(self._ssm_bytes, model=self.name)
            self._decode_counts.update(ssm_step_rows=0,
                                       ssm_step_rows_skipped=0,
                                       ssm_prefill_tokens=0)
        self.pool = BlockPool(nb, self.block_size,
                              prefix_cache=self.prefix_cache_enabled,
                              model=self.name,
                              snapshot_every=self.state_snapshot_tokens,
                              snapshot_rows=self.state_snapshot_rows,
                              first_snapshot_row=self.max_slots)
        #: the shape each K and V pool is stored in on this device and
        #: whether that is position-major (``KVLayout.pool_shape``); the
        #: forms are handed them at every use, so a caller may assign them
        self._pool_shape, self._position_major = grouped_pool_shape(
            self.layout, self._forms, self.num_blocks, self.block_size,
            self._ctx.jax_device())
        self._warming = False
        # multi-token decode bursts (docs/serving.md): lax.scan
        # ``scan_steps`` decode steps into ONE dispatch with in-program
        # termination.  0 disables the burst program entirely; the value
        # is baked into the trace at first dispatch, so it must be set
        # (ctor / attach_draft) BEFORE warmup.
        self.scan_steps = int(scan_steps if scan_steps is not None
                              else getenv_int("MXNET_DECODE_SCAN_STEPS",
                                              8))
        if self.scan_steps < 0:
            raise MXNetError(
                f"scan_steps must be >= 0: {self.scan_steps}")
        # health plane (health.py): captured at construction so the jit
        # cache never mixes output arities — flipping MXNET_HEALTH_PLANE
        # mid-process takes effect on the next engine, not this one
        self._health_on = _health.enabled()
        self._last_decode_health = None
        self._params_lock = threading.Lock()
        self._settle_params()
        # sampling plane (serving/sampling.py, docs/serving.md
        # "Sampling"): per-slot temperature / top-k / top-p / bias row /
        # RNG root key are TRACED OPERANDS of the same compiled
        # programs — the defaults (temperature 0, zero bias) reproduce
        # the pre-sampling greedy argmax bit-for-bit, and flipping any
        # of them never recompiles.  The logprobs top-N is baked at
        # construction like the health plane: it changes every
        # program's output arity, so it must never vary per request
        # (per-request N is a host-side slice up to this cap).
        self.vocab_size = int(block._vocab_size)
        self.logprobs_topn = max(0, min(
            int(logprobs_topn if logprobs_topn is not None
                else getenv_int("MXNET_SAMPLING_LOGPROBS_TOPN", 5)),
            self.vocab_size))
        # the slot state's host side: one int32 row a slot (columns
        # _LAST.._KEY1, then its block table) and one bias row; the
        # named mirrors below are views of it
        self._rows = _np.zeros(
            (self.max_slots, _TABLE + self.max_blocks_per_slot), _np.int32)
        self._tables = self._rows[:, _TABLE:]
        self._samp_temp = self._rows[:, _TEMP].view(_np.float32)
        self._samp_topk = self._rows[:, _TOPK]
        self._samp_topp = self._rows[:, _TOPP].view(_np.float32)
        self._samp_keys = self._rows[:, _KEY0:_KEY1 + 1].view(_np.uint32)
        self._samp_topp[:] = 1.0
        self._samp_bias = _np.zeros((self.max_slots, self.vocab_size),
                                    _np.float32)
        #: the slot state on the device (None: the next dispatch builds
        #: it whole from the rows), the slots whose row / bias row there
        #: is behind the host's, and what brought it up to date since the
        #: last dispatch (``mxtpu_serve_operands``)
        self._state = None
        self._bias_unset = None     # the row edit's operand for "no bias"
        self._dirty, self._dirty_bias = set(), set()
        self._rebuilt, self._edited = False, 0
        self._operand_sources = dict.fromkeys(
            ("carried", "patched", "rebuilt", "rows"), 0)
        #: decode, burst and verify dispatches by the branch their
        #: sampling step starts on (``mxtpu_sample_dispatches``)
        self._sample_branches = dict.fromkeys(("greedy", "full"), 0)
        self._last_logprobs = None
        self._last_prefill_logprobs = None
        self._last_verify_logprobs = None
        self._prefill_jit = jax.jit(self._prefill_paged_pure,
                                    donate_argnums=(0,))
        self._prefill_ext_jit = jax.jit(self._prefill_ext_pure,
                                        donate_argnums=(0,))
        self._decode_jit = jax.jit(self._decode_paged_pure,
                                   donate_argnums=(0, 1))
        self._decode_burst_jit = jax.jit(self._decode_burst_paged_pure,
                                         donate_argnums=(0, 1))
        self._verify_jit = jax.jit(self._verify_paged_pure,
                                   donate_argnums=(0, 1))
        self._slot_edit_jit = jax.jit(self._slot_edit_pure,
                                      donate_argnums=(0, 1))
        self._prefill = _telemetry.instrument_jit(
            "serving:" + self.name + ":prefill", self._prefill_jit)
        self._prefill_ext = _telemetry.instrument_jit(
            "serving:" + self.name + ":prefill_ext",
            self._prefill_ext_jit)
        self._decode = _telemetry.instrument_jit(
            "serving:" + self.name + ":decode", self._decode_jit)
        self._decode_burst = _telemetry.instrument_jit(
            "serving:" + self.name + ":decode_burst",
            self._decode_burst_jit)
        self._verify = _telemetry.instrument_jit(
            "serving:" + self.name + ":verify", self._verify_jit)
        self._slot_edit = _telemetry.instrument_jit(
            "serving:" + self.name + ":slot_edit", self._slot_edit_jit)
        for program in ("prefill", "prefill_ext", "decode", "decode_burst",
                        "verify"):
            getattr(self, "_" + program).program = program
        # speculative decoding: a draft engine attached via attach_draft
        # proposes spec_k tokens per slot; THE verify program scores all
        # spec_k + 1 positions in one dispatch (exactly one extra
        # compiled program — Q is baked from spec_k, never per-request)
        self.draft: Optional["GenerationEngine"] = None
        self.spec_k = 0
        self._warmup_done = False
        self._cache = self._recur = ()
        self._prefilled = dict.fromkeys(("miss", "hit", "prefix_hit"), 0)
        self.reset()
        _register_device_observers(self)

    # -- parameters -----------------------------------------------------
    def _settle_params(self):
        from .. import ndarray as nd
        from .. import autograd as _ag
        params = list(self.block.collect_params().values())
        if any(p._deferred_init is not None or p._data is None
               for p in params):
            probe = nd.array(_np.zeros((1, 2), _np.int32), ctx=self._ctx)
            with _ag.pause(train_mode=False):
                self.block(probe)
            params = list(self.block.collect_params().values())
        self._trainable = [p for p in params if p.grad_req != "null"]
        self._aux = [p for p in params if p.grad_req == "null"]

    def _param_fn(self):
        # not while a trace has its tracers in the Parameters
        # (:meth:`_with_params`): a worker that replaces a hung one may
        # dispatch while the old one is still tracing its first burst
        with self._params_lock:
            return (tuple(p._data._data for p in self._trainable),
                    tuple(p._data._data for p in self._aux))

    def _with_params(self, param_vals, aux_vals, key, body, program):
        """functional_call's substitution mechanics with a custom body:
        swap jax values/tracers into the Parameters, run ``body`` in
        inference mode under the traced RNG stream, restore.  What the
        expert layers traced inside it picked is kept under ``program``
        (:meth:`_count_expert_dispatch`)."""
        from .. import autograd as _ag
        from .. import random as _random
        from ..models.moe import traced_expert_impls
        all_params = self._trainable + self._aux
        all_vals = list(param_vals) + list(aux_vals)
        with self._params_lock:
            saved = [p._data._data for p in all_params]
            try:
                for p, v in zip(all_params, all_vals):
                    p._data._set_data(v)
                with _ag.pause(train_mode=False), \
                        _random.trace_stream(key), \
                        traced_expert_impls() as seen:
                    out = body()
                if seen:
                    self._experts_impl[program] = "+".join(sorted(seen))
                return out
            finally:
                for p, v in zip(all_params, saved):
                    p._data._set_data(v)

    # -- sampling plane --------------------------------------------------
    # Host side: a slot's parameters are columns of its row of the slot
    # state (and its bias row), edited on the device by row.  Traced
    # side: the token at sequence position t is sampled with
    # ``step_keys(root, t)`` — the key depends only on (root, position),
    # never on which program produced the logits, which is what makes
    # seeded runs bit-identical across per-step decode, scanned bursts,
    # and speculative verify (the Gumbel-coupled acceptance argument in
    # :meth:`spec_step`).

    def set_slot_sampling(self, slot: int, params=None) -> None:
        """Install a request's sampling parameters into ``slot`` before
        its prefill (``params`` None → greedy defaults).  Cascades to an
        attached draft engine so draft proposals are drawn from the SAME
        key stream — the coupling that stochastic speculation needs.
        Slots are NOT auto-cleared on release: prefill() itself releases
        a stale slot, so clearing there would clobber parameters set
        just before admission.  Every join sets its slot explicitly."""
        from .sampling import SamplingParams, root_key
        s = int(slot)
        if not 0 <= s < self.max_slots:
            raise MXNetError(f"{self.name}: slot {s} out of range")
        p = params if params is not None else SamplingParams()
        self._samp_temp[s] = float(p.temperature)
        self._samp_topk[s] = int(p.top_k)
        self._samp_topp[s] = float(p.top_p)
        self._samp_keys[s] = root_key(p.seed or 0)
        self._dirty.add(s)
        row = _np.zeros(self.vocab_size, _np.float32)
        if p.logit_bias:
            for t, b in p.logit_bias.items():
                if 0 <= int(t) < self.vocab_size:
                    row[int(t)] = float(b)
        self._set_bias_row(s, row)
        if self.draft is not None:
            self.draft.set_slot_sampling(slot, params)

    def update_slot_bias(self, slot: int, row) -> None:
        """Replace ``slot``'s logit-bias row (constrained-output plane:
        the batcher composes the request's static logit_bias with the
        grammar machine's mask at each emit boundary; the new row is a
        traced operand of the NEXT dispatch).  Cascades to the draft so
        constrained slots never propose illegal tokens."""
        self._set_bias_row(int(slot), _np.asarray(row, _np.float32).reshape(
            self.vocab_size))
        if self.draft is not None:
            self.draft.update_slot_bias(slot, row)

    def _set_bias_row(self, s: int, row) -> None:
        if not _np.array_equal(self._samp_bias[s], row):
            self._samp_bias[s] = row
            self._dirty_bias.add(s)

    def last_logprobs(self):
        """Device arrays from the most recent decode/burst dispatch when
        ``logprobs_topn > 0``: ``(values, token ids)`` shaped (S, N) for
        per-step decode or (k, S, N) for a burst; None when disabled.
        Like :meth:`last_decode_health`, the token read already synced
        the dispatch, so pulling these costs no extra round-trip."""
        return self._last_logprobs

    def last_prefill_logprobs(self):
        """``(values, ids)`` each shaped (N,) for the most recent
        prefill's first sampled token; None when disabled."""
        return self._last_prefill_logprobs

    def last_verify_logprobs(self):
        """``(values, ids)`` each shaped (S, Q, N) for the most recent
        verify dispatch; None when disabled."""
        return self._last_verify_logprobs

    # -- the slot state ----------------------------------------------------
    def rebuild_slot_state(self) -> None:
        """Forget the device's copy of the slot state: the next dispatch
        builds it whole from the host's rows (after :meth:`reset` and a
        failed dispatch, whose donated state is gone; a test calls it
        before every dispatch to hold the carried state against)."""
        self._state = None

    def _put(self, array):
        """``array`` on the engine's device, committed like the pools:
        for what STAYS there.  A dispatch's own operands (a prompt, its
        integers, edited rows) go to the program as numpy arrays, which
        the call itself uploads — one trip into the runtime, not two."""
        import jax
        return jax.device_put(array, self._ctx.jax_device())

    def _slot_state(self):
        """The slot state on the device, brought up to the host's rows:
        built whole where there is none, else edited by row — ONE
        dispatch of a small program (:meth:`_slot_edit_pure`) for up to
        ``_EDIT_ROWS`` changed slots, its operand their int32 rows, and
        one more for each slot whose bias row changed, which carries
        that row.  More changed rows than that (a draft's burst advances
        every slot past what the target accepts) go as the whole
        ``rows`` matrix in one upload; the bias matrix never does."""
        if self._state is not None \
                and not (self._dirty or self._dirty_bias):
            return self._state
        with _m.loop_step("edit", "serve.edit"):
            self._upload_slot_state()
        return self._state

    def _upload_slot_state(self) -> None:
        """:meth:`_slot_state` where something has to reach the device
        (the worker loop's ``edit`` step)."""
        from .. import random as _random
        if self._state is None:
            self._state = {"rows": self._put(self._rows),
                           "bias": self._put(self._samp_bias),
                           "key": self._put(_random.new_key(self._ctx))}
            if self._bias_unset is None:
                self._bias_unset = self._put(
                    _np.zeros(self.vocab_size, _np.float32))
            self._dirty.clear()
            self._dirty_bias.clear()
            self._rebuilt = True
            return
        self._edited += len(self._dirty | self._dirty_bias)
        if len(self._dirty) > _EDIT_ROWS:
            self._state["rows"] = self._put(self._rows)
            self._dirty.clear()
        # a slot whose bias row changed leads an edit of its own; the
        # others ride with the first where they fit
        edits = [[s] for s in sorted(self._dirty_bias)]
        plain = sorted(self._dirty - self._dirty_bias)
        if plain and edits and len(plain) < _EDIT_ROWS:
            edits[0] += plain
        elif plain:
            edits.append(plain)
        try:
            for slots in edits:
                self._edit_slots(slots, slots[0] in self._dirty_bias)
        except Exception:
            self.rebuild_slot_state()   # an edit donates what it edits
            raise
        self._dirty.clear()
        self._dirty_bias.clear()

    def _edit_slots(self, slots, with_bias: bool) -> None:
        """Write the host's rows of ``slots`` (at most ``_EDIT_ROWS``)
        into the device's slot state, and with them the first one's
        bias row.  The rows go to the program as they are, a numpy
        array: the call uploads them."""
        state = self._state
        slots = list(slots) + [slots[0]] * (_EDIT_ROWS - len(slots))
        edits = _np.empty((_EDIT_ROWS, 2 + self._rows.shape[1]), _np.int32)
        edits[:, 0] = slots
        edits[:, 1] = with_bias
        edits[:, 2:] = self._rows[slots]
        with _donating():
            state["rows"], state["bias"] = self._slot_edit(
                state["rows"], state["bias"], edits,
                self._put(self._samp_bias[slots[0]]) if with_bias
                else self._bias_unset)

    def _slot_edit_pure(self, rows, bias, edits, bias_row):
        """The row edit: each of ``edits`` int32 (_EDIT_ROWS, 2 + W) is
        ``[slot, set_bias, *row]``; ``bias_row`` (V,) replaces the FIRST
        one's bias row where its ``set_bias``."""
        import jax.numpy as jnp
        from jax import lax
        for i in range(_EDIT_ROWS):
            rows = lax.dynamic_update_slice(rows, edits[i:i + 1, 2:],
                                            (edits[i, 0], 0))
        slot = edits[0, 0]
        kept = lax.dynamic_slice(bias, (slot, 0), (1, bias.shape[1]))
        bias = lax.dynamic_update_slice(
            bias, jnp.where(edits[0, 1] != 0, bias_row[None], kept),
            (slot, 0))
        return rows, bias

    def _carry(self, want) -> None:
        """Hold what the batcher derived from its requests for this
        dispatch (``want``: column → (S,) array) against what the device
        carries from the last one, and mark the slots where they differ
        (a join, a leave, a host-side stop or cancel, a change of path
        between step and burst) for a row edit."""
        for col, arr in want.items():
            held = self._rows[:, col]
            diff = _np.flatnonzero(held != arr)
            if diff.size:
                held[diff] = arr[diff]
                self._dirty.update(diff.tolist())

    def _count_operands(self) -> None:
        """One decode, burst or verify dispatch: where its slot state
        came from (``mxtpu_serve_operands{source}``).  Warm-up traffic
        is not counted."""
        source = "rebuilt" if self._rebuilt else \
            "patched" if self._edited else "carried"
        rows, self._rebuilt, self._edited = self._edited, False, 0
        if self._warming:
            return
        _m.SERVE_OPERANDS.inc(model=self.name, source=source)
        self._operand_sources[source] += 1
        if rows:
            _m.SERVE_OPERAND_ROWS.inc(rows, model=self.name)
            self._operand_sources["rows"] += rows

    def operand_sources(self) -> dict:
        """Lifetime counts of :meth:`_count_operands`, and the rows
        edited, for ``GET /v1/models``."""
        return dict(self._operand_sources)

    def _count_sample_branch(self, live) -> None:
        """One decode, burst or verify dispatch over the slots that hold
        a table (a burst: those of them not ``done``, ``live`` (S,)
        bool): the branch its sampling step takes
        (``mxtpu_sample_dispatches{branch}``), which is the program's own
        predicate — a live slot with a temperature — evaluated on the
        host's rows; nothing is pulled from the device.  A burst counts
        as it starts: its later steps fall to ``greedy`` once its last
        sampled slot is done.  Warm-up traffic is not counted."""
        if self._warming:
            return
        live = (self._tables[:, 0] != 0) & live
        branch = "full" if (self._samp_temp[live] > 0.0).any() \
            else "greedy"
        _m.SAMPLE_DISPATCHES.inc(model=self.name, branch=branch)
        self._sample_branches[branch] += 1

    def sample_dispatches(self) -> dict:
        """Lifetime counts of :meth:`_count_sample_branch`, for ``GET
        /v1/models``."""
        return dict(self._sample_branches)

    def _count_expert_dispatch(self, program) -> None:
        """One dispatch of ``program`` (a prefill, decode, burst or
        verify) of a model with an expert layer, by what its grouped
        expert product is (``mxtpu_moe_expert_dispatches{path}``:
        ``kernel`` or ``loop``): the answer ``held_experts_impl`` gave
        when the program was traced, kept with it — nothing is pulled
        from the device.  Warm-up traffic is not counted."""
        impl = self._experts_impl.get(program)
        if impl is None or self._warming:
            return
        for path in impl.split("+"):
            path = {"lax_loop": "loop"}.get(path, "kernel")
            _m.MOE_EXPERT_DISPATCHES.inc(model=self.name, path=path)
            self._expert_paths[path] = self._expert_paths.get(path, 0) + 1

    def expert_dispatches(self) -> dict:
        """Lifetime counts of :meth:`_count_expert_dispatch`, for ``GET
        /v1/models``: empty for a model without an expert layer."""
        return dict(self._expert_paths)

    def _slot_operands(self, state):
        """Traced: the slot state's columns as the programs use them —
        ``(last (S, 1), pos, budget, eos, done, tables, samp)``."""
        import jax.numpy as jnp
        from jax import lax
        rows = state["rows"]
        f32 = lambda c: lax.bitcast_convert_type(rows[:, c], jnp.float32)
        samp = (f32(_TEMP), rows[:, _TOPK], f32(_TOPP), state["bias"],
                lax.bitcast_convert_type(rows[:, _KEY0:_KEY1 + 1],
                                         jnp.uint32))
        return (rows[:, _LAST:_LAST + 1], rows[:, _POS], rows[:, _BUDGET],
                rows[:, _EOS], rows[:, _DONE] != 0, rows[:, _TABLE:], samp)

    def _slot_row(self, state, slot):
        """Traced: slot ``slot``'s table row and scalar sampling operands
        for the prefill programs (temp (), top_k (), top_p (), bias (V,),
        root (2,)), sliced out of the slot state."""
        from jax import lax
        one = {k: lax.dynamic_slice_in_dim(state[k], slot, 1)
               for k in ("rows", "bias")}
        *_, tables, samp = self._slot_operands(one)
        return tables[0], tuple(a[0] for a in samp)

    @staticmethod
    def _advanced(state, key, *cols):
        """Traced: the slot state with its leading columns (``_LAST``
        on) set to ``cols`` and the dispatch key moved on."""
        import jax.numpy as jnp
        from jax import lax
        block = jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)
        return {"rows": lax.dynamic_update_slice(state["rows"], block,
                                                 (0, 0)),
                "bias": state["bias"], "key": key}

    # traced helpers (called from inside the pure programs): each is
    # sampling.sample_tokens, whose one branch of the whole batch skips
    # the sampler where no live slot samples (``live``: the slots whose
    # token is read; a freed slot keeps its last request's temperature)
    def _sample_prefill(self, last, first_pos, samp):
        """First generated token from prefill logits ``last`` (V,);
        ``first_pos`` is the sequence position it will occupy."""
        from .sampling import sample_tokens, step_keys, topn_logprobs
        temp, topk, topp, bias, root = samp
        first = sample_tokens(last, temp, topk, topp, bias,
                              step_keys(root, first_pos))
        lp = topn_logprobs(last, bias, self.logprobs_topn) \
            if self.logprobs_topn else None
        return first, lp

    def _sample_step(self, lg, key_idx, samp, live):
        """Next token per slot from decode logits ``lg`` (S, V);
        ``key_idx`` (S,) the sequence positions the sampled tokens will
        occupy (write-head + 1 — the burst scan's position carry feeds
        this per step, which IS the in-program key split)."""
        from .sampling import step_keys, sample_tokens
        temps, topks, topps, biases, roots = samp
        return sample_tokens(lg, temps, topks, topps, biases,
                             step_keys(roots, key_idx), live)

    def _sample_verify(self, logits, pos_q, samp, live):
        """Per-position sampled tokens for the verify program: logits
        (S, Q, V), ``pos_q`` (S, Q) the positions of the consumed
        tokens; output (S, Q) — column j is the token AFTER consuming
        position pos_q[:, j], keyed at pos_q + 1, so each column is
        bit-identical to what per-step decode would sample there."""
        from .sampling import step_keys, sample_tokens
        temps, topks, topps, biases, roots = samp
        return sample_tokens(logits, temps, topks, topps, biases,
                             step_keys(roots[:, None, :], pos_q + 1), live)

    # -- pure programs --------------------------------------------------
    # The five bodies below know nothing of a model's insides nor of how
    # its cache is stored: they call the layer interface (docs/serving.md
    # "The layer interface") — the model embeds, its layers project and mix
    # — and hand a layer its cache form's ``attend`` (``kvcache``).
    def _note_paged_attention(self, layer, impl, step=None):
        """What a form tells the engine as a program is traced: what
        ``layer``'s read picked (``program_inventory``) and the ``step`` of
        the kernel that fetches by runs (``mxtpu_paged_groups_total``)."""
        self._paged_impls.add(impl)
        self._paged_attention = "+".join(sorted(self._paged_impls))
        if step:
            self._run_layers[layer] = self.layout.windows[layer]
            self._run_step = step
            for fetch in ("run", "blocks"):
                self._decode_counts.setdefault("paged_groups_" + fetch, 0)
            if self._run_rows is None:
                n_groups = -(-self.max_blocks_per_slot // step[0])
                self._run_rows = (
                    _np.zeros((self.max_slots, n_groups), _np.int64),
                    _np.zeros((self.max_slots, n_groups + 1), _np.int64))
                self._run_stale.update(range(self.max_slots))

    def _recur_prefill(self, caches, start, slot, snap_rows):
        """The state layers' hand in a prefill program: a layer starts
        from row ``start`` of its leaves in ``caches`` (None: zeros, a
        sequence's beginning), its state after the prompt goes to row
        ``slot`` and the states at the snapshot boundaries to the rows
        ``snap_rows`` names, boundary by boundary (the null row where none
        is kept).  ``caches`` is the program's list, updated in place."""
        import jax.numpy as jnp
        from jax import lax

        def put(arr, row, at):
            return lax.dynamic_update_slice(
                arr, row.astype(arr.dtype), (at,) + (0,) * (arr.ndim - 1))

        def recur(l, layer, h, pos, live):
            leaves = self._forms[l].ids
            rows = tuple(
                jnp.zeros((1,) + caches[i].shape[1:], caches[i].dtype)
                if start is None
                else lax.dynamic_slice_in_dim(caches[i], start, 1)
                for i in leaves)
            h, new, snaps, counts = layer.serve_recurrent(
                h, pos, rows, live, self.state_snapshot_tokens)
            for j, i in enumerate(leaves):
                if snaps is not None:       # in order: a later boundary
                    for b in range(snaps[j].shape[1]):  # may reuse a row
                        caches[i] = put(caches[i], snaps[j][:, b],
                                        snap_rows[b])
                caches[i] = put(caches[i], new[j], slot)
            return h, counts
        return recur

    def _recur_decode(self, caches):
        """The state layers' hand in the decode programs: rows ``0 .. S -
        1`` of a layer's leaves are the slots' own, read and written in
        place (a slot that is not live comes back as it was).  A layer
        that states ``state_in_place`` is handed the leaves whole and
        gives them back whole: its step writes the slots' rows where they
        lie, and nothing slices them out."""
        from jax import lax
        S = self.max_slots

        def recur(l, layer, h, pos, live):
            leaves = self._forms[l].ids
            whole = getattr(layer, "state_in_place", False)
            rows = tuple(caches[i] if whole
                         else lax.slice_in_dim(caches[i], 0, S)
                         for i in leaves)
            h, new, _, counts = layer.serve_recurrent(h, pos, rows, live)
            for j, i in enumerate(leaves):
                caches[i] = new[j] if whole else lax.dynamic_update_slice(
                    caches[i], new[j].astype(caches[i].dtype),
                    (0,) * caches[i].ndim)
            return h, counts
        return recur

    def _zero_counts(self):
        import jax.numpy as jnp
        return {n: jnp.zeros((), jnp.int32) for n in self._counters}

    def _sum_counts(self, total, counts):
        return {n: total[n] + counts.get(n, 0) for n in self._counters}

    def _cached_layers(self, tokens, pos, attend_for, live, last=None,
                       recur=None):
        """Embed, run every layer through its ``serve_cached`` with the
        program's ``attend_for(l)`` — a layer that keeps a state through
        the program's ``recur`` — and project: ``(logits, counts)`` — of
        every position, or of position ``last`` alone (a prefill samples
        from one row: a head over the whole vocabulary for every position
        of a prompt is gigabytes that nothing reads)."""
        h = self.block.serve_embed(tokens, pos)
        counts = self._zero_counts()
        for l, layer in enumerate(self._layers):
            if l in self._state_layers:
                h, c = recur(l, layer, h, pos, live)
            else:
                h, c = layer.serve_cached(h, pos, attend_for(l), live)
            counts = self._sum_counts(counts, c)
        return self.block.serve_head(self._row(h, last)), counts

    @staticmethod
    def _row(h, last):
        """h (B, T, d) -> its position ``last`` alone, (B, 1, d); all of
        it for None."""
        import jax.numpy as jnp
        if last is None:
            return h
        return jnp.take(h, last, axis=1)[:, None]

    def _prefill_paged_pure(self, cache, state, tokens, at, param_vals,
                            aux_vals):
        """Prefix-cache MISS prefill: the model's own whole-prompt layers
        (``serve_prefill``, which return each layer's K/V), with the
        slot's K/V scattered into the blocks its table row names.
        ``at`` int32 is ``[n_valid, slot]``: the table and the sampling
        operands are the slot's row of ``state``, which is read and not
        donated.  Positions past the table's reservation redirect to the
        null block.  For a model with state layers ``at`` goes on with the
        row for the state at each snapshot boundary of the bucket; those
        layers start from zeros and leave their last state in the slot's
        row (:meth:`_recur_prefill`)."""
        import jax.numpy as jnp
        Tb = tokens.shape[1]
        n_valid = at[0]
        table, samp = self._slot_row(state, at[1])
        key = state["key"]

        out = list(cache)
        recur = self._recur_prefill(out, None, at[1], at[2:]) \
            if self._state_layers else None

        def body():
            pos = jnp.arange(Tb, dtype=jnp.int32)[None]
            h = self.block.serve_embed(tokens, pos)
            kept = {}
            for l, layer in enumerate(self._layers):
                if l in self._state_layers:
                    h, _ = recur(l, layer, h, pos, pos < n_valid)
                else:
                    h, *kept[l] = layer.serve_prefill(h, pos, pos < n_valid)
            return self.block.serve_head(self._row(h, n_valid - 1)), kept

        logits, kept = self._with_params(param_vals, aux_vals, key, body,
                                         "prefill")
        for l, rows in kept.items():    # K and V (H, Tb, D), or the rows
            self._forms[l].write_prompt(out, [r[0] for r in rows], table, 0,
                                        False, self._position_major)
        first, lp = self._sample_prefill(logits[0, 0], n_valid, samp)
        if lp is not None:
            return tuple(out), first, lp
        return tuple(out), first

    def _prefill_ext_pure(self, cache, state, tokens, at, param_vals,
                          aux_vals):
        """Prefix-cache HIT prefill: ``ctx`` leading positions (always a
        multiple of block_size) already hold valid K/V in shared blocks;
        run the layers over only the SUFFIX ``tokens`` (1, Tb), appending
        K/V at positions [ctx, ctx+Tb) and attending through the block
        table (each layer's form, ``suffix_attend``).  ``at`` int32 is
        ``[n_valid, slot, ctx]`` — operands, so one program per suffix
        bucket serves every hit length and every slot.  For a model with
        state layers ``at`` goes on with the snapshot row those layers
        start from (the state after ``ctx`` positions) and the rows for
        the boundaries of the bucket, which lie at ``ctx`` + multiples of
        the snapshot spacing: a hit always ends at a snapshot."""
        import jax.numpy as jnp
        Tb = tokens.shape[1]
        caches = list(cache)
        n_valid, ctx = at[0], at[2]
        table, samp = self._slot_row(state, at[1])
        key = state["key"]
        j0 = ctx // self.block_size
        recur = self._recur_prefill(caches, at[3], at[1], at[4:]) \
            if self._state_layers else None

        def attend_for(l):
            return self._forms[l].suffix_attend(caches, table, ctx, j0, Tb,
                                                self._position_major)

        def body():
            q_idx = jnp.arange(Tb, dtype=jnp.int32)
            pos = jnp.minimum(ctx + q_idx, self.max_len - 1)[None]  # (1, Tb)
            return self._cached_layers(tokens, pos, attend_for,
                                       (q_idx < n_valid)[None],
                                       n_valid - 1, recur)[0]

        logits = self._with_params(param_vals, aux_vals, key, body,
                                   "prefill_ext")
        first, lp = self._sample_prefill(logits[0, 0], ctx + n_valid, samp)
        if lp is not None:
            return tuple(caches), first, lp
        return tuple(caches), first

    def _decode_paged_pure(self, cache, state, param_vals, aux_vals):
        """The decode program, paged: one token for EVERY slot, each
        slot's K/V write landing in block ``tables[s, pos//bs]`` at
        offset ``pos % bs`` and attention reading through the tables
        (each layer's form, ``step_attend``).  Last tokens, positions, tables
        (S, max_blocks) and the sampling operands are the slot
        ``state``'s — join/leave never recompiles.  A free slot's table
        is all null block: it rides along, and the layers are told it is
        not live.  The state comes back advanced for the slots that hold
        a table: the sampled token as their last, position + 1, budget
        - 1 (what the next dispatch would be handed)."""
        import jax
        import jax.numpy as jnp
        last_tokens, positions, budgets, _, _, tables, samp = \
            self._slot_operands(state)
        key_next, key = jax.random.split(state["key"])
        S = last_tokens.shape[0]
        bs = self.block_size
        caches = list(cache)
        self._paged_impls = set()
        rows = jnp.arange(S)
        blk = tables[rows, positions // bs]                    # (S,)
        off = positions % bs                                   # (S,)

        def attend_for(l):
            return self._forms[l].step_attend(
                caches, blk, off, tables, positions, self._position_major)

        def body():
            return self._cached_layers(
                last_tokens, positions.reshape(S, 1), attend_for,
                (tables[:, 0] != 0)[:, None],
                recur=self._recur_decode(caches))

        logits, counts = self._with_params(param_vals, aux_vals, key, body,
                                           "decode")
        lg = logits[:, 0, :]
        live = tables[:, 0] != 0
        nxt = self._sample_step(lg, positions + 1, samp, live)
        out = (tuple(caches),
               self._advanced(state, key_next,
                              jnp.where(live, nxt, last_tokens[:, 0]),
                              positions + live, budgets - live),
               nxt)
        if self._health_on:
            out = out + (_health.decode_health(lg),)
        if self.logprobs_topn:
            from .sampling import topn_logprobs
            out = out + (topn_logprobs(lg, samp[3], self.logprobs_topn),)
        if self._counters:
            out = out + (tuple(counts[n] for n in self._counters),)
        return out

    def _decode_burst_paged_pure(self, cache, state, param_vals, aux_vals):
        """``scan_steps`` decode steps captured as ONE program
        (:func:`jax.lax.scan` over the exact :meth:`_decode_paged_pure`
        body) with in-program termination riding the carry.

        Per slot, from the slot ``state``: the budget caps the tokens
        this burst may emit (the request's remaining budget), the stop
        id is the stop token (-1: none), ``done`` marks slots that must
        not emit at all (free slots).  A slot whose step hits EOS or
        exhausts its budget flips ``done``; from then on its
        ``(last_token, position)`` carry is FROZEN and its K/V writes are
        redirected to the null block 0, so a finished slot's replayed
        steps can never touch a live block, its own or (through any
        future sharing scheme) anyone else's.  Live slots are untouched
        by their neighbors' freezes: the token stream is bit-identical
        to ``scan_steps`` per-step :meth:`_decode_paged_pure` dispatches.
        Decode positions sit strictly past the shared prompt blocks, so
        the burst composes with the BlockPool prefix cache unchanged.

        Returns ``(cache', state', tokens (k, S), emitted (S,))`` — row
        ``j`` of ``tokens`` is step ``j``'s token; slot ``s``'s valid
        prefix is ``tokens[:emitted[s], s]``; ``state'`` holds the
        carry's end (last token, position, ``done``) and budget -
        emitted, which is what the next burst starts from.  With the
        health plane on, the
        per-step logit stats are folded across the burst in-program
        (max / mean / all) to the same (S,) triplet one decode returns
        (frozen steps replay their final live step's logits, so the fold
        is dominated by live emissions).  A model's counters ride the
        carry and come back summed over the steps."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        last_tokens, positions, budgets, eos_ids, done0, tables, samp = \
            self._slot_operands(state)
        key_next, key = jax.random.split(state["key"])
        S = last_tokens.shape[0]
        bs = self.block_size
        k = int(self.scan_steps)
        self._paged_impls = set()
        rows = jnp.arange(S)
        # a draft's burst runs every slot (spec_step): one that holds no
        # table is still nobody's token
        held = tables[:, 0] != 0

        def run_scan():
            def step(carry, _):
                caches, lt, pos, done, emitted, counts = carry
                caches = list(caches)
                blk = jnp.where(done, 0, tables[rows, pos // bs])  # (S,)
                off = pos % bs                                     # (S,)
                logits, c = self._cached_layers(
                    lt, pos.reshape(S, 1),
                    lambda l: self._forms[l].step_attend(
                        caches, blk, off, tables, pos, self._position_major),
                    (~done)[:, None], recur=self._recur_decode(caches))
                counts = self._sum_counts(counts, c)
                lg = logits[:, 0, :]
                # keyed at pos + 1 (the position this token will
                # occupy): the carry IS the per-step key split
                emit = ~done
                nxt = self._sample_step(lg, pos + 1, samp, emit & held)
                emitted2 = emitted + emit.astype(jnp.int32)
                done2 = done | (emit & ((nxt == eos_ids)
                                        | (emitted2 >= budgets)))
                lt2 = jnp.where(done2[:, None], lt, nxt[:, None])
                pos2 = jnp.where(done2, pos, pos + 1)
                ys = (nxt,) if not self._health_on \
                    else (nxt,) + _health.decode_health(lg)
                if self.logprobs_topn:
                    from .sampling import topn_logprobs
                    ys = ys + topn_logprobs(lg, samp[3],
                                            self.logprobs_topn)
                return (tuple(caches), lt2, pos2, done2, emitted2,
                        counts), ys

            carry0 = (cache, last_tokens, positions, done0,
                      jnp.zeros(S, jnp.int32), self._zero_counts())
            return lax.scan(step, carry0, None, length=k)

        (caches, lt, pos, done, emitted, counts), ys = self._with_params(
            param_vals, aux_vals, key, run_scan, "decode_burst")
        state = self._advanced(state, key_next, lt[:, 0], pos,
                               budgets - emitted, eos_ids, done)
        ys = list(ys)
        if self.logprobs_topn:
            lpi = ys.pop()
            lpv = ys.pop()
        if self._health_on:
            toks, lmax, ent, fin = ys
            out = (caches, state, toks, emitted,
                   (lmax.max(axis=0), ent.mean(axis=0), fin.all(axis=0)))
        else:
            (toks,) = ys
            out = (caches, state, toks, emitted)
        if self.logprobs_topn:
            out = out + ((lpv, lpi),)
        if self._counters:
            out = out + (tuple(counts[n] for n in self._counters),)
        return out

    def _verify_paged_pure(self, cache, state, tokens, positions,
                           param_vals, aux_vals):
        """The verify program, paged: ``tokens`` (S, Q) — column 0 each
        slot's last accepted token, the rest the draft's proposals — at
        positions ``positions + j``, each slot's Q writes routed through
        its block table (tables and sampling operands are the slot
        ``state``'s; it comes back with only the dispatch key moved on:
        what was accepted is decided on the host).  Positions past a
        slot's reservation (table
        padding) or past ``max_len`` redirect to the null block — overrun
        rows near the budget edge land in the sink, never in a neighbor's
        block.  With Q == 1 this is exactly decode."""
        import jax
        import jax.numpy as jnp
        tables, samp = self._slot_operands(state)[5:]
        key_next, key = jax.random.split(state["key"])
        state = dict(state, key=key_next)
        S, Q = tokens.shape
        bs = self.block_size
        NB = self.max_blocks_per_slot
        caches = list(cache)
        self._paged_impls = set()
        rows = jnp.arange(S)
        pos_q = positions[:, None] \
            + jnp.arange(Q, dtype=jnp.int32)[None, :]          # (S, Q)
        col = pos_q // bs
        ok = (col < NB) & (pos_q < self.max_len)
        blk = jnp.where(ok, tables[rows[:, None],
                                   jnp.minimum(col, NB - 1)], 0)  # (S, Q)
        off = pos_q % bs                                          # (S, Q)

        def attend_for(l):
            return self._forms[l].verify_attend(
                caches, blk, off, tables, positions, self._position_major)

        def body():
            return self._cached_layers(
                tokens, jnp.minimum(pos_q, self.max_len - 1), attend_for,
                jnp.broadcast_to((tables[:, 0] != 0)[:, None], (S, Q)))[0]

        logits = self._with_params(param_vals, aux_vals, key, body,
                                   "verify")
        nxt = self._sample_verify(logits, pos_q, samp, tables[:, 0] != 0)
        if self.logprobs_topn:
            from .sampling import topn_logprobs
            lp = topn_logprobs(logits, samp[3][:, None, :],
                               self.logprobs_topn)
            return tuple(caches), state, nxt, lp
        return tuple(caches), state, nxt

    # -- cache lifecycle ------------------------------------------------
    def reset(self):
        """(Re)allocate the cache: all slots free, all rows zero.  Called
        at construction and by the continuous batcher after a watchdog
        restart (a replaced worker must not trust donated buffers that a
        dying dispatch may have consumed).  Also rewipes the block pool,
        every block table, and the prefix cache — cached K/V must never
        outlive the params that computed it."""
        import jax.numpy as jnp
        if getattr(self, "draft", None) is not None:
            self.draft.reset()
        # committed to the engine's device, like the parameters: an
        # uncommitted pool would follow jax's default device instead
        dev = self._ctx.jax_device()
        # the old pools go first: two sets need not fit side by side
        # (deleted, not unbound: a replaced worker's late dispatch
        # still finds a cache of the programs' shape, and fails on it)
        for c in self._cache + self._recur:
            c.delete()
        # the state rows go with the blocks: a snapshot must never outlive
        # the params that computed it either
        arrays = {}
        for form in self._forms:
            for i, (shape, dtype) in zip(form.ids, form.allocate(
                    self.num_blocks, dev, self._pool_shape,
                    self._null_row + 1)):
                arrays[i] = jnp.zeros(shape, jnp.dtype(dtype), device=dev)
        self._rebind([arrays[i] for i in range(len(arrays))])
        self.pool.reset()
        # bytes behind one block across all layers, as stored — lets
        # the pool report occupancy in bytes (device-memory
        # attribution)
        self.pool.block_bytes = self.cache_bytes // self.num_blocks
        _m.KV_BYTES_PER_TOKEN.set(self.layout.block_bytes(1),
                                  model=self.name)
        self._slot_blocks = [[] for _ in range(self.max_slots)]
        self._tables[:] = 0
        self._run_stale.update(range(self.max_slots))
        self._rows[:, :_TOPK] = _FREE
        self.rebuild_slot_state()
        self._note_state_rows()

    def _rebind(self, cache) -> None:
        """Take back what a program returned for its donated cache: the
        pools, then the state layers' leaves."""
        n = self._n_pools
        self._cache, self._recur = tuple(cache[:n]), tuple(cache[n:])

    @property
    def state_bytes(self) -> int:
        """Bytes of the state layers' rows (slots, snapshots, null)."""
        return sum(int(c.size) * c.dtype.itemsize for c in self._recur)

    def state_rows_in_use(self) -> int:
        """Rows of the state store that hold something: a slot with a
        request, a snapshot the pool keeps."""
        if not self._state_layers:
            return 0
        return sum(1 for b in self._slot_blocks if b) \
            + self.pool.snapshots_in_use

    def _note_state_rows(self) -> None:
        if self._state_layers:
            _m.STATE_ROWS_IN_USE.set(self.state_rows_in_use(),
                                     model=self.name)

    @property
    def pool_layout(self):
        """How the K and V pools are stored, for ``/programs``."""
        return _pool_layout(self.layout, self.num_blocks, self.block_size,
                            self._pool_shape, self._position_major)

    @property
    def cache_bytes(self) -> int:
        return sum(int(c.size) * c.dtype.itemsize for c in self._cache)

    # DynamicBatcher compatibility: the slot count plays the role of the
    # batch cap, the prefill buckets the role of the shape buckets
    @property
    def max_batch_size(self) -> int:
        return self.max_slots

    @property
    def buckets(self):
        return self.prefill_buckets

    def prefill_bucket_for(self, n: int) -> Optional[int]:
        for b in self.prefill_buckets:
            if b >= int(n):
                return b
        return None

    # -- host-side dispatch ---------------------------------------------
    def _guarded(self, call, *args):
        """Enqueue ``call`` over the cache, the slot state brought up to
        the host's rows, ``args`` and the current parameters."""
        with _m.loop_step("params", "serve.params"):
            param_vals, aux_vals = self._param_fn()
        state = self._slot_state()
        try:
            with _m.loop_step("enqueue", "serve.enqueue"), _donating():
                out = call(self._cache + self._recur, state, *args,
                           param_vals, aux_vals)
            self._count_expert_dispatch(getattr(call, "program", None))
            return out
        except Exception as e:
            # RESOURCE_EXHAUSTED here is the device running out of HBM
            # mid-dispatch: publish the oom FAULT so the flight recorder
            # writes one debounced postmortem carrying the memory
            # breakdown, program inventory, and per-slot KV occupancy.
            if _telemetry_device.is_oom(e):
                _telemetry_device.report_oom("serving." + self.name, e,
                                             model=self.name)
            raise

    @contextlib.contextmanager
    def _advancing(self, call, *args, live=True):
        """Enqueue a decode, burst or verify program, which takes the
        slot state donated, and yield its (future) results without the
        cache and the state, which are rebound here (``live``: the slots
        a burst starts with, for :meth:`_count_sample_branch`).  What follows pulls
        them, so the worker loop's ``operands`` phase ends at the enqueue
        and ``decode_wait`` begins.  If the dispatch or a pull fails the
        donated state is gone with it: the next dispatch rebuilds it
        from the host's rows, which advance only after the pulls."""
        try:
            out = list(self._guarded(call, *args))
            self._count_operands()
            self._count_sample_branch(live)
            _m.loop_phase_switch("decode_wait", "serve.decode.wait")
            self._rebind(out[0])
            self._state = out[1]
            yield out[2:]
        except Exception:
            self.rebuild_slot_state()
            raise

    def _pop_extras(self, out: list):
        """Take what a decode or burst program returns past its tokens
        off the end of ``out`` — the model's counters (returned), the
        logprobs ((S, N), or (k, S, N) of a burst) and the health
        triplet (stashed) — each there by what the engine was built
        with."""
        counts = out.pop() if self._counters else ()
        if self.logprobs_topn:
            self._last_logprobs = tuple(_np.asarray(a) for a in out.pop())
        if self._health_on:
            self._last_decode_health = out.pop()
        return counts

    def _unpack_prefill(self, out) -> int:
        """Rebind the cache, stash the prefill logprobs (arity is baked
        by ``logprobs_topn``, exactly like the health plane) and pull the
        first token: the host blocks here until the device has done the
        prefill (the worker loop's ``prefill_wait`` phase)."""
        with _m.loop_phase("prefill_wait", "serve.prefill.wait"):
            if self.logprobs_topn:
                cache, first, lp = out
                self._last_prefill_logprobs = tuple(_np.asarray(a)
                                                    for a in lp)
            else:
                cache, first = out
                self._last_prefill_logprobs = None
            self._rebind(cache)
            return int(first)

    def prefill(self, tokens, slot: int,
                reserve_tokens: Optional[int] = None,
                request_id: Optional[str] = None) -> int:
        """Admit a prompt into ``slot``: pad to the prompt-length bucket,
        dispatch the bucket's prefill program, return the FIRST generated
        token.  After this the slot's write head is at ``len(tokens)``
        (the returned token's K/V lands there on its first decode).

        The slot's block table is allocated first — ``reserve_tokens``
        (default ``max_len``) is the worst-case total positions
        (prompt + budget) the request may ever write, so decode
        NEVER allocates and can never fail mid-flight.  A prefix-cache
        hit dispatches the suffix program instead, skipping the shared
        span's prefill work entirely.

        The ``serve.prefill`` span (stamped with ``request_id`` when the
        caller has one) covers all of it: block allocation, padding,
        uploads and the program's enqueue, then — as its
        ``serve.prefill.wait`` child — the pull of the first token,
        which is where the host waits for the device."""
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        n = int(toks.shape[0])
        if not 0 <= int(slot) < self.max_slots:
            raise MXNetError(f"{self.name}: slot {slot} out of range "
                             f"(max_slots {self.max_slots})")
        if n < 1:
            raise MXNetError(f"{self.name}: empty prompt")
        if n > self.max_len - 1:
            raise MXNetError(
                f"{self.name}: prompt length {n} leaves no room to "
                f"generate (max_len {self.max_len})")
        ids = {"request_id": request_id} if request_id is not None else {}
        with _telemetry.trace_span("serve.prefill", cat="serving",
                                   model=self.name, slot=int(slot),
                                   tokens=n, **ids) as span:
            return self._prefill_slot(toks, n, int(slot), reserve_tokens,
                                      span)

    def _prefill_slot(self, toks, n: int, slot: int, reserve_tokens,
                      span):
        import jax.numpy as jnp
        if self.draft is not None:
            # The draft mirrors the target's slot layout: prefill it with
            # the same prompt so its write head tracks ours.  Its own
            # first-token output is discarded — only the target's argmax
            # is ever emitted.  Reserve spec_k extra positions on BOTH
            # engines: a verify near the budget edge writes up to k
            # positions past the last consumed token.
            self.draft._warming = self._warming
            self.draft.prefill(toks, slot,
                               reserve_tokens=int(
                                   reserve_tokens or self.max_len)
                               + self.spec_k)
        with _m.loop_step("alloc", "serve.join.alloc"):
            if self._slot_blocks[slot]:
                self.release_slot(slot)
            reserve = int(reserve_tokens or self.max_len) \
                + (self.spec_k if self.draft is not None else 0)
            reserve = max(n + 1, min(reserve, self.max_len))
            table, m, plan = self.pool.allocate(toks, n, reserve,
                                                share=not self._warming)
            self._slot_blocks[slot] = table
            self._set_table(slot, table)
            self._note_state_rows()
        try:
            return self._prefill_paged_dispatch(toks, n, m, slot, span,
                                                plan)
        except Exception:
            # The fresh (non-shared) blocks never got their K/V written;
            # allocate() already registered the full ones in the prefix
            # cache, so unregister them before release parks them idle —
            # a later same-prefix request must prefill cold, not "hit"
            # garbage.
            self.pool.invalidate(table[m // self.pool.block_size:], plan)
            self.release_slot(slot)
            raise

    def _set_table(self, slot: int, blocks) -> None:
        """``blocks`` as ``slot``'s block table row (null block past
        them)."""
        row = self._tables[slot]
        row[:len(blocks)] = blocks
        row[len(blocks):] = 0
        self._dirty.add(slot)
        self._run_stale.add(slot)

    def _prefill_paged_dispatch(self, toks, n: int, m: int, slot: int,
                                span, plan) -> int:
        """The slot's table and sampling operands reach the program as
        its row of the slot state (edited just before, by
        :meth:`_guarded`): what the call uploads is the padded prompt
        and a few integers — for a model with state layers the snapshot
        rows of the pool's plan among them, so a hit starts from its
        snapshot inside the one dispatch.

        What is left to compute of a prompt — all of a miss, a hit's
        suffix — may be longer than the largest bucket: it then goes as
        consecutive CHUNKS of that bucket through the hit program, which
        is a chunk's program already (a suffix at offset ``ctx`` over what
        the slot's blocks hold), ``ctx`` advancing, back to back; the
        last chunk's token is the request's first.  (Not for a model with
        state layers, whose snapshot plan is per dispatch: its prompts
        fit a bucket or are refused, as before.)"""
        big = self.prefill_buckets[-1]
        with _m.loop_step("enqueue", "serve.enqueue"):
            if n - m > big and not self._state_layers \
                    and big % self.block_size == 0:
                if span is not None:
                    span.attrs["prefix_hit_tokens"] = m
                    span.attrs["chunks"] = -(-(n - m) // big)
                for at in range(m, n - big, big):
                    self._rebind(self._guarded(
                        self._prefill_ext, toks[None, at:at + big],
                        self._prefill_at(plan, big, big, slot, at))[0])
                    last = at + big
                padded = self._padded(toks[last:], n - last, span)
                out = self._guarded(
                    self._prefill_ext, padded, self._prefill_at(
                        plan, padded.shape[1], n - last, slot, last))
            elif m == 0:
                padded = self._padded(toks, n, span)
                out = self._guarded(self._prefill, padded, self._prefill_at(
                    plan, padded.shape[1], n, slot))
            else:
                if span is not None:
                    span.attrs["prefix_hit_tokens"] = m
                padded = self._padded(toks[m:], n - m, span)
                out = self._guarded(
                    self._prefill_ext, padded, self._prefill_at(
                        plan, padded.shape[1], n - m, slot, m))
        if not self._warming:
            path = "hit" if m else "miss"
            _m.PREFILL_TOKENS.inc(n - m, model=self.name, path=path)
            self._prefilled[path] += n - m
            if self._ssm_bytes:     # every computed position is scanned
                _m.SSM_PREFILL_TOKENS.inc(n - m, model=self.name)
                self._decode_counts["ssm_prefill_tokens"] += n - m
            if m:
                _m.PREFIX_HIT_TOKENS.inc(m, model=self.name)
                self._prefilled["prefix_hit"] += m
        return self._unpack_prefill(out)

    def _prefill_at(self, plan, bucket: int, n_valid: int, slot: int,
                    ctx=None):
        """A prefill program's integer operand: ``[n_valid, slot]``, for
        the hit program ``[n_valid, slot, ctx]``, and for a model with
        state layers, from the pool's ``plan`` for this prompt
        (``BlockPool.allocate``; :data:`~.kvcache.NO_SNAPSHOTS` for
        warm-up's prompts): the snapshot row a hit starts from, then the
        row for the state at each snapshot boundary of the bucket — the
        null row where none is kept."""
        head = (n_valid, slot) if ctx is None else (n_valid, slot, ctx)
        if not self._state_layers:
            return _np.asarray(head, _np.int32)
        restore, keep = plan
        if ctx is not None:
            head += (self._null_row if restore is None else restore,)
        every = self.state_snapshot_tokens
        rows = [keep.get((ctx or 0) + (j + 1) * every, self._null_row)
                for j in range(bucket // every if every else 0)]
        return _np.asarray(head + tuple(rows), _np.int32)

    def _padded(self, toks, n: int, span):
        """``toks`` padded to its prompt-length bucket, (1, bucket)."""
        bucket = self.prefill_bucket_for(n)
        if span is not None:
            span.attrs["bucket"] = bucket
        padded = _np.zeros((1, bucket), _np.int32)
        padded[0, :n] = toks
        return padded

    def decode(self, last_tokens, positions):
        """Advance EVERY slot one position in one dispatch: last_tokens
        (S,) int32 (free slots: 0), positions (S,) int32 (free slots: 0).
        Returns the next token per slot as a host int32 array.

        On a generation worker's thread the call spans two phases of its
        loop: ``operands`` (the arrays held against what the device
        carries, a row edit where they differ, the parameter operands,
        the program's enqueue) and, from the enqueue on
        (:meth:`_advancing`), ``decode_wait`` — the host blocked on the
        device's result."""
        S = self.max_slots
        _m.loop_phase_switch("operands", "serve.operands")
        with _m.loop_step("carry", "serve.carry"):
            self._carry(
                {_LAST: _np.asarray(last_tokens, _np.int32).reshape(S),
                 _POS: _np.asarray(positions, _np.int32).reshape(S)})
        with self._advancing(self._decode) as out:
            counts = self._pop_extras(out)
            nxt = _np.asarray(out[0])
        # the rows follow the program: a slot that holds a table moved on
        rows = self._rows
        live = self._tables[:, 0] != 0
        rows[live, _LAST] = nxt[live]
        rows[:, _POS] += live
        rows[:, _BUDGET] -= live
        self._count_decode(counts, _np.asarray(positions, _np.int64)
                           .reshape(-1), live.astype(_np.int64), 1)
        return nxt

    def decode_burst(self, last_tokens, positions, budgets, eos_ids,
                     active):
        """Advance every slot up to ``scan_steps`` positions in ONE
        dispatch (docs/serving.md "Multi-token decode bursts"):
        ``last_tokens``/``positions`` (S,) int32 as in :meth:`decode`,
        ``budgets`` (S,) int32 the per-slot cap on tokens this burst may
        emit, ``eos_ids`` (S,) int32 the per-slot stop token (-1: none),
        ``active`` (S,) bool False for free slots.  Returns host arrays
        ``(tokens (k, S) int32, emitted (S,) int32)``; slot ``s``'s
        emitted tokens are ``tokens[:emitted[s], s]``, bit-identical to
        the same number of per-step :meth:`decode` calls."""
        k = int(self.scan_steps)
        if k < 1:
            raise MXNetError(
                f"{self.name}: decode bursts disabled (scan_steps "
                f"{self.scan_steps}; set MXNET_DECODE_SCAN_STEPS >= 1)")
        S = self.max_slots
        _m.loop_phase_switch("operands", "serve.operands")
        with _m.loop_step("carry", "serve.carry"):
            self._carry(
                {_LAST: _np.asarray(last_tokens, _np.int32).reshape(S),
                 _POS: _np.asarray(positions, _np.int32).reshape(S),
                 _BUDGET: _np.asarray(budgets, _np.int32).reshape(S),
                 _EOS: _np.asarray(eos_ids, _np.int32).reshape(S),
                 _DONE: ~_np.asarray(active, bool).reshape(S)})
        with self._advancing(self._decode_burst,
                             live=self._rows[:, _DONE] == 0) as out:
            counts = self._pop_extras(out)
            toks, emitted = _np.asarray(out[0]), _np.asarray(out[1])
        self._follow_burst(toks, emitted)
        self._count_decode(counts, _np.asarray(positions, _np.int64)
                           .reshape(-1), emitted.astype(_np.int64), k)
        return toks, emitted

    def _follow_burst(self, toks, emitted) -> None:
        """Move the host's rows to where the burst program's carry
        ended, from the tokens it returned: a slot that met its stop id
        or used its budget froze BEFORE consuming its last token (that
        token is not its last token, nor its position counted), any
        other consumed all it emitted."""
        rows, cols = self._rows, _np.arange(self.max_slots)
        final = toks[_np.maximum(emitted - 1, 0), cols]
        ended = (emitted > 0) & ((final == rows[:, _EOS])
                                 | (emitted >= rows[:, _BUDGET]))
        moved = emitted - ended
        rows[:, _LAST] = _np.where(
            moved > 0, toks[_np.maximum(moved - 1, 0), cols],
            rows[:, _LAST])
        rows[:, _POS] += moved
        rows[:, _BUDGET] -= emitted
        rows[:, _DONE] |= ended

    def _count_decode(self, counts, positions, steps, dispatch_steps) -> None:
        """After a decode or burst dispatch of ``dispatch_steps`` steps,
        with its results already on the host: add what the model's layers
        counted in the program to their series, and the context the steps
        had behind them to ``mxtpu_decode_context_tokens`` — slot ``s`` was
        live for ``steps[s]`` steps from write head ``positions[s]``, so
        its written positions sum to ``steps * (pos + 1) + steps * (steps
        - 1) / 2`` (nothing is pulled from the device for this one).  For
        a model with windowed layers the same sum with each step's
        written positions capped at the window goes to
        ``mxtpu_decode_window_tokens``: a batch holds contexts on both
        sides of the window, so min(mean context, window) would
        overstate what a windowed layer reads.  Warm-up traffic is not
        counted."""
        if self._warming:
            return

        def ramp(first, n):         # first + (first + 1) + ... n terms
            return int(_np.sum(n * first + n * (n - 1) // 2))

        def capped(cap):            # the same, each term at most cap
            under = _np.clip(cap - positions, 0, steps)
            return ramp(positions + 1, under) \
                + int(_np.sum((steps - under) * cap))

        ctx = ramp(positions + 1, steps)
        _m.DECODE_CONTEXT_TOKENS.inc(ctx, model=self.name)
        self._decode_counts["decode_context_tokens"] += ctx
        if self._ssm_bytes:
            # a live slot a step: one row updated; the step's work list
            # leaves every other slot's row out
            rows = int(_np.sum(steps))
            skipped = self.max_slots * dispatch_steps - rows
            _m.SSM_STEP_ROWS.inc(rows, model=self.name)
            _m.SSM_STEP_ROWS_SKIPPED.inc(skipped, model=self.name)
            self._decode_counts["ssm_step_rows"] += rows
            self._decode_counts["ssm_step_rows_skipped"] += skipped
        if self._window:
            win = capped(self._window)
            _m.DECODE_WINDOW_TOKENS.inc(win, model=self.name)
            self._decode_counts["decode_window_tokens"] += win
        for n, v in zip(self._counters, counts):
            v = int(v)
            _m.MODEL_COUNTERS[n].inc(v, model=self.name)
            self._decode_counts[n] += v
        if self._select_layers:
            # a layer that chooses its keys scores every written position
            # and reads min(written, k) of them
            scored = len(self._select_layers) * ctx
            chosen = sum(capped(k) for k in self._select_layers.values())
            _m.INDEX_KEYS_SCORED.inc(scored, model=self.name)
            _m.INDEX_KEYS_SELECTED.inc(chosen, model=self.name)
            self._decode_counts["index_keys_scored"] += scored
            self._decode_counts["index_keys_selected"] += chosen
        if self._run_layers:
            self._count_paged_groups(positions, steps)

    def _count_paged_groups(self, positions, steps) -> None:
        """``mxtpu_paged_groups_total{fetch}``: the steps of the grouped
        paged kernel's work list that the live slots' decode steps made —
        a layer that takes it, a live slot and a step, the groups of
        128 keys from the first of the kernel step that holds the
        window's first key to the write head's — by how the kernel
        fetched them: ``run`` (the group's live columns name blocks in a
        row: one copy) or ``blocks`` (a copy a column).  The kernel's own
        predicate (``kernels.flash_attention._paged_work_list``) on the
        host's tables: a table is fixed from its join on, so what is
        kept a slot is each group's run length and the full runs before
        each group (``paged_run_lengths``)."""
        from collections import Counter
        from ..kernels.flash_attention import paged_run_lengths
        bs, (P, per_step) = self.block_size, self._run_step
        lengths, full_before = self._run_rows
        for s in self._run_stale:
            lengths[s] = paged_run_lengths(self._tables[s], P,
                                           self.num_blocks)
            full_before[s, 1:] = _np.cumsum(lengths[s] == P)
        self._run_stale.clear()
        k = _np.arange(int(steps.max(initial=0)))[None, :]
        on = k < steps[:, None]                      # (S, steps): live
        pos = positions[:, None] + k
        last = _np.minimum(pos // bs, self.max_blocks_per_slot - 1)
        head = last // P                     # the write head's group
        slot = _np.arange(self.max_slots)[:, None]
        to_head = full_before[slot, head] \
            + (lengths[slot, head] >= last - head * P + 1)
        run = blocks = 0
        for window, layers in Counter(self._run_layers.values()).items():
            first = 0 * pos if window is None else _np.maximum(
                pos - window + 1, 0) // (bs * P * per_step) * per_step
            runs = int(_np.sum((to_head - full_before[slot, first])[on]))
            run += layers * runs
            blocks += layers * (int(_np.sum((head - first + 1)[on])) - runs)
        for fetch, n in (("run", run), ("blocks", blocks)):
            _m.PAGED_GROUPS.inc(n, model=self.name, fetch=fetch)
            self._decode_counts["paged_groups_" + fetch] += n

    def decode_counters(self) -> dict:
        """Lifetime totals of :meth:`_count_decode`'s series, for
        ``GET /v1/models``."""
        return dict(self._decode_counts)

    def last_decode_health(self):
        """Device arrays from the most recent decode dispatch when the
        health plane is on (``(logit_max (S,), entropy (S,), finite
        (S,))`` — see :func:`health.decode_health`), else None.  The
        token read in :meth:`decode` already synced the dispatch, so
        pulling these is free of extra device round-trips."""
        return self._last_decode_health

    # -- speculative decoding -------------------------------------------
    def attach_draft(self, draft: "GenerationEngine",
                     spec_k: Optional[int] = None) -> None:
        """Attach a (small) draft engine for speculative decoding.

        The draft proposes ``spec_k`` tokens per slot (default
        ``MXNET_SPEC_K``); the target scores all ``spec_k + 1`` positions
        in ONE verify dispatch.  The draft must mirror the target's slot
        layout and position space — same ``max_slots``, ``max_len`` at
        least the target's, same vocabulary (argmax ids are compared).
        Attach BEFORE :meth:`warmup` so the verify program joins the
        warmed set."""
        from ..base import getenv_int
        if draft is self:
            raise MXNetError(f"{self.name}: a model cannot draft itself")
        for eng in (self, draft):
            for form in eng._forms:     # a cache no verify program reads
                if form.no_verify:
                    raise MXNetError(f"{eng.name}: {form.no_verify}")
        if int(draft.max_slots) != self.max_slots:
            raise MXNetError(
                f"{self.name}: draft max_slots {draft.max_slots} != "
                f"target max_slots {self.max_slots}")
        if int(draft.max_len) < self.max_len:
            raise MXNetError(
                f"{self.name}: draft max_len {draft.max_len} < target "
                f"max_len {self.max_len} (the draft decodes at the same "
                f"positions)")
        tv = getattr(self.block, "_vocab_size", None)
        dv = getattr(draft.block, "_vocab_size", None)
        if tv is not None and dv is not None and int(tv) != int(dv):
            raise MXNetError(
                f"{self.name}: draft vocab {dv} != target vocab {tv}")
        k = int(spec_k if spec_k is not None
                else getenv_int("MXNET_SPEC_K", 4))
        if k < 1:
            raise MXNetError(f"spec_k must be >= 1, got {k}")
        self.draft = draft
        self.spec_k = k
        # draft outputs are never surfaced (only target verify columns
        # are emitted), so zero its logprobs top-N before its first
        # dispatch bakes the output arity — spec bursts skip the extra
        # per-step top_k work entirely
        if draft.compiled_programs() == 0:
            draft.logprobs_topn = 0
        # scan the k autoregressive draft decodes into one dispatch
        # (spec drops from k+1 to 2 dispatches per burst).  The draft's
        # burst width must equal spec_k, so override its default here —
        # before warmup bakes the trace.  scan_steps == 0 (the
        # MXNET_DECODE_SCAN_STEPS kill switch) keeps the host loop.
        if draft.scan_steps != 0:
            draft.scan_steps = k

    def verify(self, tokens, positions):
        """Score ``spec_k + 1`` positions for EVERY slot in one dispatch:
        ``tokens`` (S, Q) int32 — column 0 each slot's last accepted
        token, columns 1..Q-1 the draft proposals; ``positions`` (S,)
        int32 base write heads.  Returns the target's argmax (S, Q) as a
        host array: ``out[s, j]`` is the next token after consuming
        ``tokens[s, :j + 1]``."""
        _m.loop_phase_switch("operands", "serve.operands")
        toks = _np.asarray(tokens, _np.int32).reshape(self.max_slots, -1)
        pos = _np.asarray(positions, _np.int32).reshape(self.max_slots)
        with self._advancing(self._verify, toks, pos) as res:
            self._last_verify_logprobs = tuple(
                _np.asarray(a) for a in res[1]) \
                if self.logprobs_topn else None     # (S, Q, N)
            return _np.asarray(res[0])

    def spec_step(self, last_tokens, positions):
        """One speculative step for EVERY slot: the draft proposes
        ``spec_k`` tokens autoregressively — ONE scanned draft dispatch
        when its burst program is enabled (the default; ``spec_k`` host
        dispatches otherwise) — then ONE target verify dispatch scores
        all ``spec_k + 1`` positions.

        Acceptance is **Gumbel-coupled stochastic speculative
        sampling**.  Both engines sample with the SAME per-slot root
        key and position-indexed key stream (:meth:`set_slot_sampling`
        cascades to the draft), so at every position they share one
        gumbel noise vector; the verify program returns the target's
        keyed sample at each position, and acceptance is the longest
        prefix where the draft's sample equals the target's.  Every
        emitted token is a target sample under the target's own
        filtered distribution, and because the key depends only on
        (root, position), each one is bit-identical to what a no-draft
        sampled run emits at that position — at ANY accept rate.  This
        is the shared-noise form of the accept/reject + residual
        resample scheme (distributionally equivalent: the coupled
        target sample IS the residual draw when the proposals
        diverge), and greedy acceptance is its ``temperature == 0``
        special case, where sample == argmax on both sides.

        Returns ``(out, accepted)``: ``out`` (S, spec_k + 1) int32 —
        ``out[s, :accepted[s] + 1]`` are this step's emitted tokens,
        every one of them a target sample (bit-identical to plain
        decode by construction); ``accepted`` (S,) int64 in
        ``[0, spec_k]`` counts the draft tokens accepted per slot.
        Rejected positions' K/V is rolled back: the cursor simply does
        not advance past them (stale entries are masked and then
        overwritten by the next dispatch at the same position), and the
        pool's :meth:`~.kvcache.BlockPool.rewind` COW guard keeps the
        overwrite out of any shared block."""
        if self.draft is None:
            raise MXNetError(f"{self.name}: no draft attached "
                             "(attach_draft first)")
        k = self.spec_k
        S = self.max_slots
        last = _np.asarray(last_tokens, _np.int32).reshape(S)
        pos = _np.asarray(positions, _np.int32).reshape(S)
        if self.draft.scan_steps == k:
            # one scanned dispatch replaces the k-step host loop below,
            # bit-identically: done0 all-False with budgets k+1 and
            # eos -1 can never flip a slot's done mask, so every slot —
            # free ones included — advances (lt, pos) exactly as the
            # loop's unconditional ``lt, pv = nxt, pv + 1`` does.
            toks_ks, _ = self.draft.decode_burst(
                last, pos,
                budgets=_np.full(S, k + 1, _np.int32),
                eos_ids=_np.full(S, -1, _np.int32),
                active=_np.ones(S, bool))
            drafted = _np.ascontiguousarray(toks_ks.T)         # (S, k)
        else:
            drafted = _np.zeros((S, k), _np.int32)
            lt, pv = last, pos
            for j in range(k):
                nxt = _np.asarray(self.draft.decode(lt, pv),
                                  _np.int32).reshape(S)
                drafted[:, j] = nxt
                lt, pv = nxt, pv + 1
        toks = _np.concatenate([last[:, None], drafted], axis=1)
        out = self.verify(toks, pos)
        match = out[:, :k] == drafted                          # (S, k)
        accepted = _np.where(match.all(axis=1), k,
                             _np.argmin(match, axis=1))
        self._rollback_rejected(pos, accepted)
        return out, accepted

    def _rollback_rejected(self, base_positions, accepted) -> None:
        """Rollback after a verify: for every slot that rejected
        draft tokens, run the pool's COW guard over the dirty tail so
        the next dispatch's overwrites cannot touch a shared block.
        Block tables are per-slot operands, so a neighbor never observes
        another slot's rollback."""
        for s in range(self.max_slots):
            if int(accepted[s]) >= self.spec_k:
                continue
            keep = int(base_positions[s]) + int(accepted[s]) + 1
            for eng in (self, self.draft):
                if not eng._slot_blocks[s]:
                    continue
                blocks = eng._slot_blocks[s]
                new = eng.pool.rewind(blocks, keep)
                if new != blocks:
                    eng._slot_blocks[s] = new
                    eng._set_table(s, new)

    # -- block-pool bookkeeping ------------------------------------------
    def release_slot(self, slot: int) -> None:
        """Return ``slot``'s blocks to the pool (decref — shared prefix
        blocks stay live for their other readers / the prefix cache).
        Cascades to the draft engine's mirrored slot."""
        with _m.loop_step("alloc", "serve.join.alloc"):
            if self.draft is not None:
                self.draft.release_slot(slot)
            blocks = self._slot_blocks[int(slot)]
            if blocks:
                self.pool.release(blocks)
            self._slot_blocks[int(slot)] = []
            self._set_table(int(slot), ())
            self._rows[int(slot), :_TOPK] = _FREE
            self._note_state_rows()

    def can_admit(self, tokens, reserve_tokens: int,
                  reserved_blocks: int = 0) -> bool:
        """Admission check: can the pool reserve worst-case capacity for
        this prompt right now?  ``reserved_blocks`` discounts capacity
        promised to earlier admits in the same scheduling step.  With a
        draft attached both pools must fit the reservation (plus the
        spec_k verify headroom)."""
        if self.draft is not None and not self.draft.can_admit(
                tokens, int(reserve_tokens) + self.spec_k,
                reserved_blocks):
            return False
        toks = _np.asarray(tokens, _np.int32).reshape(-1)
        n = int(toks.shape[0])
        reserve = int(reserve_tokens) \
            + (self.spec_k if self.draft is not None else 0)
        reserve = max(n + 1, min(reserve, self.max_len))
        return self.pool.can_admit(toks, n, reserve, reserved_blocks)

    def reserve_estimate(self, reserve_tokens: int) -> int:
        """Worst-case blocks a request reserving ``reserve_tokens``
        positions can take (no sharing assumed) — the scheduler's
        discount unit for multi-admit steps."""
        reserve = int(reserve_tokens) \
            + (self.spec_k if self.draft is not None else 0)
        return blocks_for(min(reserve, self.max_len), self.block_size)

    def kv_capacity_tokens(self) -> int:
        """Total token positions the KV cache can hold across all
        requests — the backpressure unit for admission control."""
        return (self.num_blocks - 1) * self.block_size

    def kv_stats(self) -> dict:
        """Cache-utilization facts for ``GET /v1/models`` and
        ``stats()``."""
        out = {"kv_capacity_tokens": self.kv_capacity_tokens(),
               "prefill_tokens": dict(self._prefilled)}
        out.update(self.pool.stats())
        if self._state_layers:
            out.update(state_rows_total=self._null_row,
                       state_rows_in_use=self.state_rows_in_use(),
                       state_bytes=self.state_bytes)
        return out

    def slot_occupancy(self) -> List[dict]:
        """Per-slot KV occupancy: blocks held and reserved token capacity
        per live slot — the flight-dump view of who holds the pool when
        an OOM hits."""
        out = []
        for slot, blocks in enumerate(self._slot_blocks):
            if blocks:
                out.append({"slot": slot, "blocks": len(blocks),
                            "reserved_tokens":
                                len(blocks) * self.block_size})
        return out

    def program_inventory(self) -> dict:
        """Runtime program-set inventory (``GET /programs``, merged into
        ``/v1/models``, woven into flight dumps): the closed-set
        accounting (expected vs AOT-compiled programs) next to the
        per-program dispatch-ledger rows — what actually ran, how often,
        how long ago — plus per-slot KV occupancy.  Recurses into an
        attached draft engine."""
        prefix = "serving:" + self.name + ":"
        inv = {
            "model": self.name,
            "expected_programs": self.expected_programs,
            "compiled_programs": self.compiled_programs(),
            "warm": self.warm,
            "paged_attention": self._paged_attention,
            "pool_layout": self.pool_layout,
            "experts_impl": dict(self._experts_impl) or None,
            "scan_steps": self.scan_steps,
            "spec_k": self.spec_k if self.draft is not None else 0,
            "programs": _telemetry.dispatch_ledger(prefix=prefix),
            "slots": self.slot_occupancy(),
        }
        if self.draft is not None:
            inv["draft"] = self.draft.program_inventory()
        return inv

    # -- warmup / introspection -----------------------------------------
    @property
    def expected_programs(self) -> int:
        """Size of the CLOSED program set: one prefill per bucket (plus
        one suffix-prefill per bucket when the prefix cache can hit),
        ONE decode, ONE decode burst (when ``scan_steps >= 1`` — the
        scan length is baked, budgets/eos/done are operands, so one
        program serves every k-step burst), ONE row edit of the slot
        state (the slot is an operand), and — with a draft attached —
        ONE verify (the query width is baked from ``spec_k``, so no
        per-accept-length programs exist)."""
        per_bucket = 2 if self.prefix_cache_enabled else 1
        return per_bucket * len(self.prefill_buckets) + 2 \
            + (1 if self.scan_steps >= 1 else 0) \
            + (1 if self.draft is not None else 0)

    def warmup(self) -> int:
        """AOT-compile the whole closed program set — every prefill
        bucket (miss AND, with the prefix cache on, suffix/hit variants)
        plus THE decode program — then reset the cache (warmup traffic
        must not look like live slots or poison the prefix cache).
        Returns the number of programs warmed."""
        self._warming = True
        try:
            for b in self.prefill_buckets:
                self.prefill(_np.zeros(max(1, min(b, self.max_len - 1)),
                                       _np.int32), 0)
                self.release_slot(0)
            # the row edit, whatever the joins above happened to send
            self._slot_state()
            self._edit_slots([0], True)
            if self.prefix_cache_enabled:
                # suffix programs take ctx and the slot as OPERANDS: one
                # dummy dispatch per bucket (slot 0 is released: its
                # table is all null block, where the writes land)
                for b in self.prefill_buckets:
                    sn = max(1, min(b, self.max_len - 1))
                    self._unpack_prefill(self._guarded(
                        self._prefill_ext, _np.zeros((1, b), _np.int32),
                        self._prefill_at(NO_SNAPSHOTS, b, sn, 0, 0)))
            self.decode(_np.zeros(self.max_slots, _np.int32),
                        _np.zeros(self.max_slots, _np.int32))
            if self.scan_steps >= 1:
                # budgets of 1 exercise the in-program done path; the
                # post-warmup reset wipes whatever the burst wrote
                self.decode_burst(
                    _np.zeros(self.max_slots, _np.int32),
                    _np.zeros(self.max_slots, _np.int32),
                    _np.ones(self.max_slots, _np.int32),
                    _np.full(self.max_slots, -1, _np.int32),
                    _np.ones(self.max_slots, bool))
            if self.draft is not None:
                self.verify(
                    _np.zeros((self.max_slots, self.spec_k + 1),
                              _np.int32),
                    _np.zeros(self.max_slots, _np.int32))
        finally:
            self._warming = False
        self.reset()
        if self.draft is not None:
            self.draft.warmup()
        # closed-set accounting must balance HERE, loudly: a warmup that
        # compiled more programs than expected_programs predicts means
        # the program set is not closed (a per-request shape leaked into
        # a trace); fewer means the inventory over-promises and the
        # readiness gate would wait forever on real cache misses.
        compiled = self.compiled_programs()
        if compiled and compiled != self.expected_programs:
            raise MXNetError(
                f"{self.name}: program accounting drift after warmup — "
                f"compiled {compiled} programs, expected "
                f"{self.expected_programs} (closed program set violated)")
        self._warmup_done = True
        return self.expected_programs

    def compiled_programs(self) -> int:
        try:
            return int(self._prefill_jit._cache_size()) \
                + int(self._prefill_ext_jit._cache_size()) \
                + int(self._decode_jit._cache_size()) \
                + int(self._decode_burst_jit._cache_size()) \
                + int(self._verify_jit._cache_size()) \
                + int(self._slot_edit_jit._cache_size())
        except Exception:
            return 0

    @property
    def warm(self) -> bool:
        if self._warmup_done:
            return True
        return self.compiled_programs() >= self.expected_programs

    # -- reference path --------------------------------------------------
    def generate(self, tokens, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None,
                 speculative: Optional[bool] = None,
                 sampling=None):
        """Solo generation through the SERVING programs (slot 0) — the
        engine-level convenience used by tests and the bench; the
        continuous batcher drives the same programs for many slots.
        With a draft attached the speculative step loop is the default
        (``speculative=False`` forces plain decode); every emitted token
        is a target sample either way, so the outputs are identical.
        ``sampling`` is an optional :class:`~.sampling.SamplingParams`
        (None: greedy) installed into slot 0 for the run."""
        toks = list(_np.asarray(tokens, _np.int32).reshape(-1))
        n = len(toks)
        budget = min(int(max_new_tokens), self.max_len - n)
        if budget < 1:
            raise MXNetError(
                f"{self.name}: no token budget (prompt {n}, max_len "
                f"{self.max_len})")
        spec = self.draft is not None if speculative is None \
            else bool(speculative) and self.draft is not None
        self.set_slot_sampling(0, sampling)
        out = [self.prefill(toks, 0, reserve_tokens=n + budget)]
        try:
            lt = _np.zeros(self.max_slots, _np.int32)
            pv = _np.zeros(self.max_slots, _np.int32)
            while len(out) < budget and (eos_id is None
                                         or out[-1] != int(eos_id)):
                lt[0] = out[-1]
                pv[0] = n + len(out) - 1
                if spec:
                    burst, acc = self.spec_step(lt, pv)
                    for j in range(int(acc[0]) + 1):
                        out.append(int(burst[0, j]))
                        if len(out) >= budget or (
                                eos_id is not None
                                and out[-1] == int(eos_id)):
                            break
                else:
                    nxt = self.decode(lt, pv)
                    out.append(int(nxt[0]))
        finally:
            self.release_slot(0)
        return out

    def __repr__(self):
        return (f"<GenerationEngine {self.name!r}: slots={self.max_slots}, "
                f"max_len={self.max_len}, layers={self.num_layers}, "
                f"heads={self.num_heads}, "
                f"prefill_buckets={list(self.prefill_buckets)}, "
                f"programs={self.compiled_programs()}>")
