"""SPMD training: ONE compiled train step over a device mesh.

This is the TPU-native replacement for the reference's entire distributed
stack (SURVEY §2.4, §5.8): DataParallelExecutorGroup batch slicing +
KVStore push/pull + ps-lite servers (reference:
python/mxnet/module/executor_group.py, src/kvstore/kvstore_dist.h) collapse
into a single ``jax.jit`` over a Mesh:

* batch sharded over the 'data' axis  → gradient allreduce is compiled in
  (GSPMD inserts psum over ICI/DCN; no server round-trips);
* parameters sharded by regex rules   → tensor parallelism, strictly more
  than the reference's manual group2ctx placement;
* the optimizer runs inside the step  → the reference's "server-side
  optimizer" (update_on_kvstore) with the compiled program as the server;
* aux state (BatchNorm stats) flows functionally through the step.

Multi-host: same code — initialize jax.distributed (parallel.distributed),
build the mesh over all processes' devices, feed each process its local
batch shard.
"""
from __future__ import annotations

import re
import time as _time
import weakref as _weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as _np

from ..base import MXNetError
from ..compile_cache import ensure_compile_cache
from ..context import current_context
from .. import health as _health
from .. import telemetry as _telemetry
from .. import telemetry_device as _telemetry_device
from ..ndarray.ndarray import NDArray
from ..gluon.block import functional_call
from . import mesh as mesh_mod
from . import optim as fopt

__all__ = ["SPMDTrainer", "shard_params", "data_sharding",
           "exact_rule", "fsdp_rules"]


def _fetch_full(v):
    """Materialize a (possibly sharded) jax array as full numpy.
    Multi-host: shards on other processes are not addressable; allgather
    over DCN first (single-host path is a plain copy)."""
    if getattr(v, "is_fully_addressable", True):
        return _np.asarray(v)
    from jax.experimental import multihost_utils
    return _np.asarray(multihost_utils.process_allgather(v, tiled=True))


def _placed_copy(x, s):
    """Place ``x`` per sharding ``s`` as a FRESH buffer.  device_put may
    ALIAS the input (even via a distinct Array object) when placement
    already matches — a later donated step would then delete the source
    array; always copy so the source stays usable (the copy is reclaimed
    by donation on the first step)."""
    import jax
    import jax.numpy as jnp
    return jnp.copy(jax.device_put(x, s))


def exact_rule(param, spec):
    """One exact-name sharding rule ``("^<name>$", spec)`` for a
    Parameter (or anything with ``.name``) — the building block every
    ``*_rules(block=...)`` derivation uses; immune to custom prefixes,
    unlike the auto-prefix regex rule lists."""
    return (f"^{re.escape(param.name)}$", spec)


def data_sharding(mesh, data_axis="data"):
    """Batch-dim sharding for input arrays."""
    return mesh_mod.named_sharding(mesh, data_axis)


def shard_params(params: Dict[str, object], mesh, rules=None):
    """Apply (regex, PartitionSpec) rules to a name→array dict; first match
    wins, default replicated.  Returns name→NamedSharding.

    Warns on DEAD rules (patterns matching no parameter): a sharding rule
    that silently matches nothing replicates the weights it was meant to
    shard — the failure mode of auto-prefix regexes applied to a
    custom-``prefix=`` model (use the family's ``tp_rules(block=net)``).
    Patterns carrying a ``(?#optional)`` regex comment (a model-variant
    rule, e.g. an untied-head rule on a tied model) are exempt."""
    from jax.sharding import NamedSharding, PartitionSpec
    out = {}
    rules = list(rules or [])
    hit = [False] * len(rules)
    for name in params:
        spec = None
        for i, (pat, s) in enumerate(rules):
            if re.search(pat, name):
                # FIRST match decides the spec, but every matching rule
                # counts as live — a rule shadowed by an earlier one is
                # not dead (its weights are sharded, just by the earlier
                # rule)
                hit[i] = True
                if spec is None:
                    spec = s if isinstance(s, PartitionSpec) \
                        else PartitionSpec(*s)
        out[name] = NamedSharding(mesh, spec or PartitionSpec())
    # a "(?#optional)" regex comment inside the pattern marks the rule
    # as covering a model VARIANT (e.g. an untied-head rule on a tied
    # model) — exempt from the dead warning; any other dead rule means
    # the weights it targets silently replicate
    dead = [rules[i][0] for i in range(len(rules))
            if not hit[i] and "(?#optional)" not in rules[i][0]]
    if dead:
        import warnings
        warnings.warn(
            "sharding rules matched no parameter (their weights stay "
            f"REPLICATED): {dead}; with custom prefix= models derive "
            "exact-name rules via tp_rules(block=net)", stacklevel=2)
    return out


def fsdp_rules(block, axis="data", min_size=1 << 16, mesh=None):
    """Fully-sharded data parallelism (ZeRO-3 class) as sharding rules.

    Every parameter of at least ``min_size`` elements gets its largest
    (mesh-divisible, when ``mesh`` is given) axis sharded over the DATA
    axis, so each device stores 1/N of the big weights; GSPMD then
    compiles the FSDP communication schedule automatically — all-gather
    of each layer's weights before its compute, reduce-scatter of its
    gradients in the backward — while the batch stays sharded over the
    same axis.  Small parameters (biases, norms) remain replicated,
    standard FSDP practice: their all-gather latency would exceed the
    memory saved.

    Compose with ``shard_optimizer_state=True``: optimizer-state leaves
    inherit each param's sharding, so moments for FSDP-sharded weights
    are already distributed and ZeRO-1 covers the replicated remainder
    (see ``_make_state_shardings``).

    Reference analog: none — the reference's kvstore replicates all
    weights per device (SURVEY §2.4); beyond-parity with
    dp/tp/sp/ep/pp.  Pattern: GSPMD ("automatic sharding propagation")
    + the ZeRO paper's stage-3 partitioning, expressed as
    PartitionSpecs instead of a runtime."""
    from jax.sharding import PartitionSpec as P
    if mesh is not None and axis not in mesh.shape:
        raise MXNetError(
            f"fsdp_rules: mesh has no axis {axis!r} "
            f"(axes: {tuple(mesh.shape)})")
    rules = []
    n = mesh.shape[axis] if mesh is not None else None
    for p in block.collect_params().values():
        if p._data is None:
            raise MXNetError(
                "initialize the net and run one forward before deriving "
                "fsdp_rules (deferred shapes must be settled)")
        v = p.data()
        if v.size < min_size:
            continue
        shape = tuple(v.shape)
        pick = None
        for d in sorted(range(len(shape)), key=lambda i: -shape[i]):
            if n is None or (shape[d] > 0 and shape[d] % n == 0):
                pick = d
                break
        if pick is None:
            continue           # no divisible axis: stays replicated
        spec = [None] * len(shape)
        spec[pick] = axis
        rules.append(exact_rule(p, P(*spec)))
    return rules


class SPMDTrainer:
    """Compile a Block + loss + functional optimizer into one sharded step.

    Usage::

        mesh = parallel.make_mesh({"data": -1})
        trainer = SPMDTrainer(net, loss_fn, "adam",
                              {"learning_rate": 1e-3}, mesh=mesh)
        for x, y in loader:
            loss = trainer.step(x, y)   # one XLA program, psum inside
        trainer.sync_to_block()         # write params back to net
    """

    def __new__(cls, *args, **kwargs):
        # pipeline_axis= switches to the GPipe trainer (stacked-stage
        # parameter storage over a data x pipe mesh) — one entry point
        # for every parallel axis; see parallel/pipeline.py
        if cls is SPMDTrainer and kwargs.get("pipeline_axis"):
            from .pipeline import PipelineTrainer
            return object.__new__(PipelineTrainer)
        return object.__new__(cls)

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, data_axis="data", sharding_rules=None,
                 extra_input_shardings=None, donate=True,
                 shard_optimizer_state=False, zero1=None,
                 pipeline_axis=None,
                 pipeline_microbatches=None, pipeline_schedule=None,
                 accum_steps=None):
        import jax
        from ..base import getenv_bool
        if pipeline_axis is not None:
            # only reachable from a subclass that didn't override
            # __init__ — SPMDTrainer itself dispatches in __new__
            raise MXNetError(
                "pipeline_axis is handled by parallel.PipelineTrainer")
        if pipeline_microbatches is not None:
            raise MXNetError(
                "pipeline_microbatches without pipeline_axis — pass "
                "pipeline_axis=<mesh axis> to request pipelining")
        if pipeline_schedule is not None:
            raise MXNetError(
                "pipeline_schedule without pipeline_axis — pass "
                "pipeline_axis=<mesh axis> to request pipelining")
        ensure_compile_cache()
        self._net = net
        self._loss = loss_fn
        self._mesh = mesh or mesh_mod.current_mesh()
        if self._mesh is None:
            raise MXNetError("SPMDTrainer needs a mesh (parallel.make_mesh)")
        self._data_axis = data_axis
        self._donate = donate
        self._opt = fopt.create(optimizer, **(optimizer_params or {}))
        self._zero1 = getenv_bool("MXNET_ZERO1", False) if zero1 is None \
            else bool(zero1)
        if self._zero1 and shard_optimizer_state:
            raise MXNetError(
                "zero1 and shard_optimizer_state are two spellings of "
                "the same memory optimization (flat contiguous shards "
                "vs per-leaf axis sharding) — pick one")
        if self._zero1 and not getattr(self._opt, "elementwise", True):
            if zero1 is not None:
                raise MXNetError(
                    "zero1: this optimizer's update is not elementwise "
                    "(per-tensor reductions, e.g. LAMB's trust ratio, "
                    "straddle shard boundaries) — drop zero1= or pick "
                    "an elementwise rule")
            # env-driven request (MXNET_ZERO1=1): degrade gracefully,
            # mirroring the eager Trainer's fused-path fallback
            import warnings
            warnings.warn(
                "MXNET_ZERO1=1 ignored: optimizer update is not "
                "elementwise (per-tensor reductions straddle shard "
                "boundaries); training proceeds unsharded", stacklevel=2)
            self._zero1 = False

        params_all = list(net.collect_params().values())
        for p in params_all:
            if p._data is None:
                raise MXNetError(
                    "initialize the net and run one forward before "
                    "building an SPMDTrainer (deferred shapes must be "
                    "settled)")
        self._trainable = [p for p in params_all if p.grad_req != "null"]
        self._aux = [p for p in params_all if p.grad_req == "null"]

        shardings = shard_params(
            {p.name: p.data()._data for p in self._trainable + self._aux},
            self._mesh, sharding_rules)
        self._tr_shardings = tuple(shardings[p.name]
                                   for p in self._trainable)
        self._aux_shardings = tuple(shardings[p.name] for p in self._aux)

        # place parameter values on the mesh per their shardings (see
        # _placed_copy for why a fresh buffer is mandatory here)
        self._tr_vals = tuple(
            _placed_copy(p.data()._data, s)
            for p, s in zip(self._trainable, self._tr_shardings))
        self._aux_vals = tuple(
            _placed_copy(p.data()._data, s)
            for p, s in zip(self._aux, self._aux_shardings))
        # ZeRO-1 weight-update sharding (paper: "Automatic Cross-Replica
        # Sharding of Weight Update in Data-Parallel Training",
        # arXiv:2004.13336) — two tiers of the same idea:
        #   zero1=True: parallel/zero1.Zero1Optimizer flattens the param
        #     tree into contiguous padded segments, shards the flat state
        #     + update over the data axis and all-gathers the new weights
        #     in-program (exactly the paper's scheme);
        #   shard_optimizer_state=True: per-leaf axis sharding of the
        #     state tree (coarser — leaves with no divisible dim stay
        #     replicated — but composes with FSDP rules).
        if self._zero1:
            from . import zero1 as _z1mod
            self._opt = _z1mod.Zero1Optimizer(self._opt, self._mesh,
                                              data_axis)
        # zeros_like inside opt.init makes each state leaf inherit its
        # param's sharding (XLA propagates NamedSharding through zeros_like)
        self._opt_state = self._opt.init(self._tr_vals)
        self._shard_opt_state = bool(shard_optimizer_state)
        self._opt_state_shardings = None
        if self._zero1:
            # pin the flat state to P(data) in out_shardings so XLA
            # materializes 1/N state bytes per replica
            self._opt_state_shardings = self._make_state_shardings()
            from . import zero1 as _z1mod
            _telemetry.gauge(
                "mxtpu_optimizer_state_bytes",
                "optimizer-state bytes ONE replica materializes "
                "(replicated state: the full tree; zero1: its 1/N "
                "shard)").set(
                    _z1mod.per_replica_state_bytes(self._opt_state))
            _telemetry.gauge(
                "mxtpu_zero1_allgather_bytes",
                "per-step per-replica inbound all-gather volume the "
                "zero1 weight-update sharding adds").set(
                    _z1mod.zero1_allgather_bytes(self._opt.spec))
        elif self._shard_opt_state:
            self._opt_state_shardings = self._make_state_shardings()
            self._opt_state = jax.tree.map(
                lambda v, s: jax.device_put(v, s),
                self._opt_state, self._opt_state_shardings)
        self._accum = 1 if accum_steps is None else int(accum_steps)
        if self._accum < 1:
            raise MXNetError(f"accum_steps={accum_steps} must be >= 1")
        self._step_count = 0
        self._jit_cache = {}
        # health plane (health.py): per-leaf grad norms / finite mask /
        # update ratios + loss traced as extra step outputs, drained at
        # step boundaries.  Captured at construction so the jit cache
        # never mixes program shapes.
        self._health = _health.HealthMonitor(
            [p.name for p in self._trainable], src="spmd") \
            if _health.enabled() else None
        # device-plane attribution (telemetry_device): report THIS
        # trainer's live optimizer state — zero1: the 1/N flat shard —
        # under owner "optimizer".  weakref so the registration never
        # keeps a discarded trainer's state trees alive.
        wref = _weakref.ref(self)

        def _opt_state_bytes():
            tr = wref()
            if tr is None:
                return 0
            from . import zero1 as _z1mod
            return _z1mod.per_replica_state_bytes(tr._opt_state)

        _telemetry_device.register_owner("optimizer", _opt_state_bytes)

    def _make_state_shardings(self):
        """Per-leaf shardings for the optimizer state: each leaf keeps
        its own inherited sharding (zeros_like in opt.init propagates
        the param's) with the data axis added on the first unsharded,
        divisible dim; leaves already sharded over the data axis (FSDP-
        style rules) are left as they are.  Under zero1 every state leaf
        is a flat padded segment — always P(data)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if self._zero1:
            return self._opt.state_shardings(self._opt_state)
        n = self._mesh.shape[self._data_axis]

        def _axes_in(entry):
            if entry is None:
                return ()
            return entry if isinstance(entry, tuple) else (entry,)

        def leaf_sharding(v):
            base = getattr(v, "sharding", None)
            spec = list(base.spec) if base is not None \
                and hasattr(base, "spec") else []
            spec += [None] * (v.ndim - len(spec))
            used = {a for e in spec for a in _axes_in(e)}
            if self._data_axis not in used:
                for d in range(v.ndim):
                    if spec[d] is None and v.shape[d] > 0 \
                            and v.shape[d] % n == 0:
                        spec[d] = self._data_axis
                        break
            return NamedSharding(self._mesh, P(*spec))
        import jax
        return jax.tree.map(leaf_sharding, self._opt_state)

    def _state_out_shardings(self):
        """out_shardings for the optimizer-state output of the step
        program.  When no sharding policy pinned them (plain replicated
        runs: ``_opt_state_shardings is None``) the state must still
        leave the program with the SAME shardings it entered with: the
        state is donated, and with the output left unconstrained GSPMD
        is free to shard any data-axis-divisible leaf — the donated
        (replicated) input buffer then cannot alias the (sharded)
        output and XLA rejects the executable (seen with BN-channel-
        sized momentum leaves, 64 % 8 == 0)."""
        if self._opt_state_shardings is not None:
            return self._opt_state_shardings
        import jax
        try:
            return jax.tree.map(lambda v: v.sharding, self._opt_state)
        except AttributeError:
            return None

    # ------------------------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    @property
    def params(self) -> Dict[str, object]:
        return {p.name: v
                for p, v in zip(self._trainable, self._tr_vals)}

    def _make_loss_of(self):
        """The per-(micro)batch loss as a pure function of trainable and
        aux values — the trace core shared by the per-step program, the
        accumulation scan, and CompiledLoop's k-step chunk program."""
        import jax.numpy as jnp
        net, loss_blk = self._net, self._loss
        trainable, aux = self._trainable, self._aux

        def loss_of(tr, aux_cur, rng_i, xs, label):
            nds = [NDArray(b) for b in xs]
            out_vals, new_aux = functional_call(
                net, trainable, tr, aux, aux_cur, nds, True, rng_i)
            # multi-output nets (e.g. MLM+NSP heads) pass every output
            # to the loss block: loss(out0, out1, ..., label)
            out_nds = [NDArray(v) for v in out_vals]
            with_label = NDArray(label)
            from .. import autograd as _ag
            with _ag.pause(train_mode=True):
                loss_nd = loss_blk(*out_nds, with_label)
            loss = jnp.mean(loss_nd._data)
            return loss, tuple(new_aux)

        return loss_of

    def _make_grad_fn(self):
        """loss+grad of one FULL batch (microbatch-accumulated when
        accum_steps > 1) as a pure function
        ``grad_of(tr_vals, aux_vals, rng, xs, label) ->
        (loss, new_aux, grads)`` — everything in a train step except the
        optimizer update, so per-step and k-step-chunk programs share one
        definition."""
        import jax
        import jax.numpy as jnp
        loss_of = self._make_loss_of()
        k = self._accum

        def grad_of(tr_vals, aux_vals, rng, xs, label):
            if k == 1:
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(tr_vals, aux_vals, rng, xs,
                                           label)
            else:
                # gradient accumulation: grads computed and consumed
                # PER microbatch inside the scan body, so activation
                # memory is one microbatch's, not the whole batch's —
                # the point of accumulation.  Microbatches interleave
                # (reshape + leading-axis swap) so each one spans every
                # data shard evenly.
                def mb_split(a):
                    rest = a.shape[1:]
                    return a.reshape(a.shape[0] // k, k, *rest).swapaxes(
                        0, 1)

                xs_mb = [mb_split(x) for x in xs]
                label_mb = mb_split(label)
                g0 = jax.tree.map(jnp.zeros_like, tr_vals)

                def micro(carry, mb):
                    g_acc, aux_cur, loss_acc, rng_cur = carry
                    *mb_xs, mb_label = mb
                    rng_i, rng_next = jax.random.split(rng_cur)
                    (l, new_aux), g = jax.value_and_grad(
                        loss_of, has_aux=True)(tr_vals, aux_cur, rng_i,
                                               mb_xs, mb_label)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, new_aux, loss_acc + l, rng_next), None

                (g_sum, new_aux, loss_sum, _), _ = jax.lax.scan(
                    micro, (g0, aux_vals, jnp.zeros((), jnp.float32),
                            rng),
                    tuple(xs_mb) + (label_mb,))
                grads = jax.tree.map(lambda g: g / k, g_sum)
                loss = loss_sum / k
            return loss, new_aux, grads

        return grad_of

    def _build_step(self):
        import jax
        opt = self._opt
        grad_of = self._make_grad_fn()
        health_on = self._health is not None

        def pure_step(tr_vals, aux_vals, opt_state, step, rng, *batch):
            *xs, label = batch
            loss, new_aux, grads = grad_of(tr_vals, aux_vals, rng, xs,
                                           label)
            new_tr, new_opt = opt.update(tr_vals, grads, opt_state, step)
            if health_on:
                h = _health.train_step_health(list(grads), list(tr_vals),
                                              list(new_tr), loss=loss)
                return loss, new_tr, new_aux, new_opt, h
            return loss, new_tr, new_aux, new_opt

        donate = (0, 1, 2) if self._donate else ()
        outsh = (None, self._tr_shardings, self._aux_shardings,
                 self._state_out_shardings())
        if health_on:
            outsh += (None,)
        return _telemetry.instrument_jit("spmd", jax.jit(
            pure_step, out_shardings=outsh, donate_argnums=donate))

    def _shard_batch(self, arr):
        import jax
        if isinstance(arr, NDArray):
            arr = arr._data
        sharding = mesh_mod.named_sharding(self._mesh, self._data_axis)
        if jax.process_count() > 1:
            # multi-host: each process feeds its LOCAL batch shard; the
            # global array is assembled across processes (DCN path —
            # reference analog: each dist worker computes on its own
            # partition, kvstore_dist.h)
            import numpy as _np
            return jax.make_array_from_process_local_data(
                sharding, _np.asarray(arr))
        return jax.device_put(arr, sharding)

    def step(self, *batch) -> float:
        """Run one train step; returns the (replicated) scalar loss as a
        jax array (non-blocking — async dispatch)."""
        observe = bool(_telemetry.TRAINER.subscribers)
        t0 = _time.perf_counter() if observe else 0.0
        with _telemetry.trace_span("spmd.step", cat="trainer"):
            out = self._step_impl(*batch)
        if observe:
            _telemetry.TRAINER.publish(
                phase="step", seconds=_time.perf_counter() - t0)
        return out

    def _step_impl(self, *batch):
        from .. import random as _random
        import jax.numpy as jnp
        with _telemetry.trace_span("spmd.shard_batch", cat="transfer"):
            sharded = tuple(self._shard_batch(b) for b in batch)
        if self._accum > 1:
            B = sharded[0].shape[0]
            dp = self._mesh.shape[self._data_axis]
            if B % (self._accum * dp):
                raise MXNetError(
                    f"global batch {B} must divide by accum_steps "
                    f"{self._accum} x data axis {dp} for even "
                    "microbatch sharding")
        key = self._build_key(sharded)
        if key not in self._jit_cache:
            self._jit_cache[key] = self._build_step()
        self._step_count += 1
        step_arr = jnp.asarray(self._step_count, jnp.int32)
        rng = _random.new_key()
        if self._health is not None:
            loss, self._tr_vals, self._aux_vals, self._opt_state, hst = \
                self._jit_cache[key](self._tr_vals, self._aux_vals,
                                     self._opt_state, step_arr, rng,
                                     *sharded)
            # queued device stats; drained only when already finished
            self._health.submit(self._step_count - 1, 1, hst)
        else:
            loss, self._tr_vals, self._aux_vals, self._opt_state = \
                self._jit_cache[key](self._tr_vals, self._aux_vals,
                                     self._opt_state, step_arr, rng,
                                     *sharded)
        # the whole step (fwd + bwd + update) is ONE compiled program
        _telemetry.gauge("mxtpu_optimizer_dispatches_per_step").set(1)
        return loss

    def _build_key(self, arrs):
        return tuple((a.shape, str(a.dtype)) for a in arrs)

    def sync_to_block(self):
        """Copy current parameter/aux values back into the Block's
        Parameters, gathered onto each Parameter's own device so eager
        execution keeps working."""
        import jax
        if self._health is not None:
            self._health.sync()
        fetch = _fetch_full
        for p, v in zip(self._trainable, self._tr_vals):
            dev = p.data().ctx.jax_device()
            p._data._set_data(jax.device_put(fetch(v), dev))
        for p, v in zip(self._aux, self._aux_vals):
            dev = p.data().ctx.jax_device()
            p._data._set_data(jax.device_put(fetch(v), dev))

    def reload_params(self):
        """Re-place parameter/aux values from the Block's current
        Parameters — the inverse of :meth:`sync_to_block`, used after a
        checkpoint restore wrote fresh arrays into the net
        (``AsyncCheckpointer.restore_into``) so the compiled step resumes
        from the restored weights."""
        self._tr_vals = tuple(
            _placed_copy(p.data()._data, s)
            for p, s in zip(self._trainable, self._tr_shardings))
        self._aux_vals = tuple(
            _placed_copy(p.data()._data, s)
            for p, s in zip(self._aux, self._aux_shardings))
