"""Pipeline parallelism as ONE SPMD program — GPipe and 1F1B schedules,
composing with tensor parallelism into 3D (data x pipe x model)
(reference analog: the reference had no pipeline engine — its
distributed story was data parallelism over kvstore; this is the
beyond-parity axis completing dp/tp/sp/ep/pp/fsdp.  Pattern: the
pipelined-scan recipe of the TPU scaling playbook — stack homogeneous
stage parameters, shard the stack over a mesh axis, stream microbatches
around the ring with ppermute inside lax.scan; 1F1B writes the backward
out explicitly for O(S) activation memory; tensor axes ride GSPMD auto
mode inside the pipe-explicit schedule).

Design:
  * stage parameters are STACKED pytrees — every leaf (S, ...) — and
    sharded over the ``pipe`` mesh axis, so placement is a
    PartitionSpec, exactly like tensor/expert parallelism here;
  * the schedule runs M + S - 1 ticks; every device runs the SAME
    program each tick (SPMD — idle bubble ticks compute on garbage and
    are masked), activations hop stage->stage+1 via ppermute over ICI;
  * differentiable end to end: lax.scan + ppermute transpose cleanly,
    so jax.grad/SPMDTrainer-style training through the pipeline needs
    nothing special;
  * microbatches enter replicated; outputs are collected on the last
    stage and replicated back with a psum — callers see a plain
    (M, ...) array.
"""
from __future__ import annotations

from typing import Any, Callable

from ..base import MXNetError
from ..compile_cache import ensure_compile_cache


def _require_single_output(outs):
    """The stage protocol carries ONE activation tensor between pipe
    ranks; anything else (e.g. MoE's (y, aux)) would be silently
    truncated at outs[0]."""
    if len(outs) != 1:
        raise MXNetError(
            "pipeline stages must return exactly one activation "
            f"tensor, got {len(outs)} outputs — multi-output cells "
            "(e.g. MoE's (y, aux)) cannot ride the stage protocol; "
            "use expert parallelism (moe.ep_rules) instead")
    return outs[0]

__all__ = ["gpipe", "stack_stage_params", "pipe_specs",
           "stack_block_stages", "PipelineTrainer"]


def stack_block_stages(blocks, training=False, rng_key=None):
    """Turn a list of same-architecture (initialized, shape-settled)
    Blocks into pipeline stages: returns ``(stage_fn, stacked_params)``
    for :func:`gpipe`.  The first block is the template whose forward
    runs functionally with each stage's parameter values substituted —
    the ONE place the cell-as-stage recipe lives (used by the driver
    dryrun and the tests alike).

    ``training`` selects the train-mode forward.  Stage calls are pure
    fn(params, x): STOCHASTIC layers would get the one ``rng_key`` on
    every call and AUXILIARY state (BatchNorm running stats) has no way
    out of the schedule — so training=True REFUSES blocks with active
    Dropout or aux state rather than silently mis-sampling/stale-ing
    them.  Build pipelined stages from deterministic, stateless layers
    (LayerNorm etc.), the standard pipeline practice."""
    import jax
    from ..gluon.block import functional_call
    from ..ndarray.ndarray import NDArray
    if not blocks:
        raise MXNetError("stack_block_stages needs >= 1 block")
    template = blocks[0]
    if training:
        _refuse_impure(template, "stack_block_stages(training=True)")
    trainable = list(template.collect_params().values())
    if any(p.grad_req == "null" for p in trainable) and training:
        raise MXNetError(
            "stack_block_stages(training=True) with auxiliary state "
            "(BatchNorm running stats): the pure stage contract cannot "
            "carry aux updates out of the schedule — use stateless "
            "normalization (LayerNorm/GroupNorm) in pipelined stages")
    # readable keys: strip the template's own prefix; stages align by
    # POSITION (collect_params order is construction order, identical
    # for same-architecture blocks), so a key collision — possible with
    # prefix='' where child names carry no shared block prefix — falls
    # back to enumerated keys rather than silently merging params
    pfx = getattr(template, "prefix", "") or ""
    names = [p.name[len(pfx):] if pfx and p.name.startswith(pfx)
             else p.name for p in trainable]
    if len(set(names)) != len(names):
        names = [f"p{i}_{n}" for i, n in enumerate(names)]
    trees = []
    for b in blocks:
        ps = list(b.collect_params().values())
        if len(ps) != len(names):
            raise MXNetError("stage blocks differ in parameter count")
        trees.append({n: p.data()._data for n, p in zip(names, ps)})
    stacked = stack_stage_params(trees)
    key = rng_key if rng_key is not None else jax.random.PRNGKey(0)

    def stage_fn(p, x):
        outs, _ = functional_call(template, trainable,
                                  [p[n] for n in names], [], [],
                                  [NDArray(x)], training, key)
        return _require_single_output(outs)

    return stage_fn, stacked


def stack_stage_params(param_trees):
    """Stack per-stage parameter pytrees (a list of S same-structure
    trees) into one tree whose leaves carry a leading stage axis."""
    import jax
    import jax.numpy as jnp
    if not param_trees:
        raise MXNetError("stack_stage_params needs >= 1 stage tree")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_trees)


def pipe_specs(stacked_params, axis="pipe"):
    """PartitionSpecs sharding every leaf's leading (stage) axis."""
    import jax
    from jax.sharding import PartitionSpec as P

    def leaf(v):
        return P(axis, *([None] * (v.ndim - 1)))
    return jax.tree.map(leaf, stacked_params)


def gpipe(stage_fn: Callable[[Any, Any], Any], stacked_params, xs,
          mesh, axis: str = "pipe"):
    """Apply S pipeline stages to M microbatches.

    stage_fn(params, x) -> y : one stage's computation (same shape in
    and out — the transformer-layer contract); ``stacked_params``:
    pytree with leading stage dim S == mesh.shape[axis];
    ``xs``: (M, ...) microbatched activations.  Returns (M, ...) — the
    composition stage_{S-1}(...stage_0(x)) per microbatch, replicated.

    Wall-clock is (M + S - 1)/M of the ideal — the GPipe bubble; raise
    M to amortize.  Gradients flow through (scan + ppermute transpose).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    if axis not in mesh.shape:
        raise MXNetError(f"mesh has no axis {axis!r}")
    S = mesh.shape[axis]
    M = xs.shape[0]
    leading = {v.shape[0] for v in jax.tree.leaves(stacked_params)}
    if leading != {S}:
        raise MXNetError(
            f"stacked_params leading dims {sorted(leading)} != pipe "
            f"axis size {S}")

    def body(params_local, xs_rep):
        stage = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)  # this stage's
        buf = jnp.zeros_like(xs_rep[0])
        ys0 = jnp.zeros_like(xs_rep)

        def tick(carry, t):
            buf, ys = carry
            # stage 0 ingests microbatch t (clipped reads during the
            # drain phase are masked out downstream)
            inp = jnp.where(stage == 0,
                            xs_rep[jnp.clip(t, 0, M - 1)], buf)
            out = stage_fn(p, inp)
            # the last stage owns microbatch t - stage at this tick
            idx = jnp.clip(t - stage, 0, M - 1)
            valid = (stage == S - 1) & (t >= stage) & (t < stage + M)
            ys = ys.at[idx].set(jnp.where(valid, out, ys[idx]))
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, ys), None

        (_, ys), _ = jax.lax.scan(tick, (buf, ys0),
                                  jnp.arange(M + S - 1))
        # only the last stage holds real outputs; psum replicates them
        ys = jnp.where(stage == S - 1, ys, jnp.zeros_like(ys))
        return jax.lax.psum(ys, axis)

    in_specs = (pipe_specs(stacked_params, axis), P())
    return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(stacked_params, xs)


def _refuse_impure(net, what):
    """The pure-stage contract shared with stack_block_stages: stochastic
    layers would reuse one RNG key across stages/microbatches and aux
    state (BatchNorm stats) has no way out of the schedule."""
    from ..gluon import nn as _nn
    drops = []
    net.apply(lambda b: drops.append(b) if isinstance(b, _nn.Dropout)
              and getattr(b, "_rate", 0) else None)
    if drops:
        raise MXNetError(
            f"{what} with active Dropout: build the net with dropout=0 "
            "(the pure stage contract cannot thread per-stage RNG)")


from .spmd import SPMDTrainer as _SPMDTrainer  # noqa: E402


class PipelineTrainer(_SPMDTrainer):
    """GPipe pipeline-parallel TRAINING as one compiled SPMD program over
    a ``data`` x ``pipe`` mesh (typically reached via
    ``SPMDTrainer(..., pipeline_axis="pipe")``).

    Stage assignment is Megatron's: every stage runs an equal contiguous
    slice of the model's transformer cells; stage 0 additionally runs
    the embedding ("first") work and the LAST stage the final-norm +
    head ("last") work plus the loss, so activations crossing stages are
    uniformly (b, T, C) and the collected per-microbatch output is a
    scalar loss.  The model describes that split via
    ``pipeline_split() -> (first_params, first_fn, cells, last_params,
    last_fn)`` where ``first_fn(first_vals, ids) -> x`` embeds a
    microbatch and ``last_fn(last_vals, first_vals, x) -> outputs``
    produces what the loss block consumes (``first_vals`` is passed back
    so tied heads — GPT's logits through the embedding matrix — stay
    tied; both gradient contributions sum via the pipe-axis psum the
    shard_map transpose inserts).

    Parameter placement is pure sharding, like every other axis here:
    cell parameters are STACKED (S, ...) pytrees sharded over ``pipe``
    (each device holds only its stages' weights — the memory win
    pipeline parallelism exists for); first/last parameters ride
    replicated.  The optimizer state inherits each leaf's sharding, so
    cell-state memory also scales 1/S.  The batch axis shards over
    ``data`` exactly as in SPMDTrainer; grad all-reduce is the compiled
    psum.

    Schedules (``pipeline_schedule=``):
      * ``"gpipe"`` (default) — M microbatches forward over M + S - 1
        ticks, backward via AD's scan transpose; peak activation memory
        grows with M (every tick's residuals are saved).
      * ``"1f1b"`` — one forward AND one backward microbatch per tick,
        backward hand-written (per-stage vjp, explicit cotangent hops,
        remat of the stage forward from a 2S-deep input stash); peak
        activation memory is O(S), INDEPENDENT of M — raise
        ``pipeline_microbatches`` to shrink the bubble for free.
    Both schedules compute identical math (the trainer tests prove
    loss- and trained-parameter-parity against the 1-device oracle).
    Every tick every device runs the same program (SPMD): non-owning
    stages compute first/last work into a discarded ``where`` branch —
    wasted FLOPs linear in (first+last)/stage cost, the price of
    single-program form.

    Restrictions (all raise): dropout > 0 anywhere in the net, aux state
    (BatchNorm) in cells, ``lamb`` (its per-TENSOR trust ratio sees the
    stacked (S, ...) tensor, changing the math vs the unstacked oracle),
    len(cells) % S != 0, and local batch % microbatches != 0.

    Reference analog: none — the reference's distributed story stops at
    data parallelism over kvstore (SURVEY §2.4); this is the pp axis of
    the beyond-parity dp/tp/sp/ep/pp set, trained end to end.
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, data_axis="data", sharding_rules=None,
                 extra_input_shardings=None, donate=True,
                 shard_optimizer_state=False, zero1=None,
                 pipeline_axis="pipe",
                 pipeline_microbatches=None, pipeline_schedule=None,
                 accum_steps=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from . import mesh as mesh_mod
        from . import optim as fopt

        if accum_steps not in (None, 1):
            raise MXNetError(
                "accum_steps does not apply to the pipeline trainer — "
                "pipeline_microbatches already streams the batch in "
                "microbatches (raise it for the same memory effect)")
        if extra_input_shardings or shard_optimizer_state or zero1:
            raise MXNetError(
                "pipeline_axis does not compose with "
                "extra_input_shardings / shard_optimizer_state / zero1 "
                "yet — cell params are already sharded over the pipe "
                "axis (their optimizer state with them).  sharding_rules "
                "DO compose: tensor-parallel specs apply on top of the "
                "stage stacking (3D dp x pipe x model parallelism)")
        self._rules = list(sharding_rules or [])
        ensure_compile_cache()
        self._net = net
        self._loss = loss_fn
        self._mesh = mesh or mesh_mod.current_mesh()
        if self._mesh is None:
            raise MXNetError("PipelineTrainer needs a mesh")
        for ax in (data_axis, pipeline_axis):
            if ax not in self._mesh.shape:
                raise MXNetError(f"mesh has no axis {ax!r}")
        from jax.sharding import PartitionSpec as _P
        for _pat, _sp in self._rules:
            entries = tuple(_sp) if isinstance(_sp, (list, tuple, _P)) \
                else (_sp,)
            for entry in entries:
                for ax in (entry if isinstance(entry, tuple)
                           else (entry,)):
                    if ax is None:
                        continue
                    if ax not in self._mesh.shape:
                        raise MXNetError(
                            f"sharding_rules: axis {ax!r} (rule {_pat!r})"
                            f" not in the mesh {tuple(self._mesh.shape)} "
                            "— a 3D pipeline needs the tensor axis in "
                            "the mesh, e.g. make_mesh({'data': d, "
                            "'pipe': s, 'model': t})")
                    if ax in (data_axis, pipeline_axis):
                        raise MXNetError(
                            f"sharding_rules: axis {ax!r} (rule {_pat!r})"
                            " is a schedule-owned (manual) axis — the "
                            "pipeline already shards stages over "
                            f"{pipeline_axis!r} and the batch over "
                            f"{data_axis!r}; tensor rules may only use "
                            "other mesh axes (e.g. 'model')")
        self._data_axis = data_axis
        self._pipe_axis = pipeline_axis
        self._S = S = self._mesh.shape[pipeline_axis]
        self._donate = donate
        if optimizer == "lamb":
            raise MXNetError(
                "lamb is not stage-stacking-safe (per-tensor trust "
                "ratio over the stacked (S, ...) tensor differs from "
                "per-stage); use sgd/adam")
        self._opt = fopt.create(optimizer, **(optimizer_params or {}))

        if not hasattr(net, "pipeline_split"):
            raise MXNetError(
                f"{type(net).__name__} does not implement "
                "pipeline_split(); see models/gpt.py for the protocol")
        (self._first_params, self._first_fn, cells,
         self._last_params, self._last_fn) = net.pipeline_split()
        _refuse_impure(net, "PipelineTrainer")
        sp_axes = set()
        net.apply(lambda b: sp_axes.add(getattr(b, "_seq_axis", None)))
        if sp_axes - {None}:
            raise MXNetError(
                "pipeline does not compose with sequence parallelism "
                f"(net carries seq_axis={sorted(sp_axes - {None})}): "
                "ring/ulysses build their own shard_map inside the "
                "stage body — nested manual collectives; build the net "
                "without seq_axis and use tensor parallelism "
                "(sharding_rules=tp_rules(block=net)) for the "
                "attention instead")
        if len(cells) % S:
            raise MXNetError(
                f"{len(cells)} cells do not split over pipe axis {S}")
        self._L = L = len(cells) // S
        self._cells = cells
        self._cell_trainables = []
        n_per_cell = None
        for c in cells:
            ps = list(c.collect_params().values())
            if any(p.grad_req == "null" for p in ps):
                raise MXNetError(
                    "pipelined cells with auxiliary state (BatchNorm "
                    "running stats) are unsupported — use stateless "
                    "normalization (LayerNorm)")
            if n_per_cell is None:
                n_per_cell = len(ps)
            elif len(ps) != n_per_cell:
                raise MXNetError("cells differ in parameter count")
            self._cell_trainables.append(ps)
        for p in (list(self._first_params) + list(self._last_params)
                  + [q for ps in self._cell_trainables for q in ps]):
            if p._data is None:
                raise MXNetError(
                    "initialize the net and run one forward before "
                    "building a PipelineTrainer")

        # one matcher for the whole trainer: shard_params gives
        # first-match resolution AND the dead-rule warning the tp_rules
        # docstrings promise (a rule matching nothing silently
        # replicates the weights it meant to shard).  EVERY trainable
        # name participates so per-stage exact-name rules count as live.
        from .spmd import shard_params as _shard_params
        all_named = {p.name: p.data()._data
                     for p in (list(self._first_params)
                               + list(self._last_params)
                               + [q for ps in self._cell_trainables
                                  for q in ps])}
        rule_sh = _shard_params(all_named, self._mesh, self._rules)

        def _tp_spec(name, ndim):
            """The matched rule's spec, None-padded to ndim (all-None =
            replicated on the tensor axes)."""
            entries = list(rule_sh[name].spec)
            entries += [None] * (ndim - len(entries))
            return tuple(entries)

        def pipe_sh(tp_spec):
            # stage axis first, then the cell param's own TP spec —
            # 3D parallelism is just this composition of PartitionSpecs
            return NamedSharding(self._mesh, P(pipeline_axis, *tp_spec))

        # placed COPIES (same donation-safety reasoning as SPMDTrainer)
        self._first_vals = tuple(
            jnp.copy(jax.device_put(p.data()._data, rule_sh[p.name]))
            for p in self._first_params)
        self._last_vals = tuple(
            jnp.copy(jax.device_put(p.data()._data, rule_sh[p.name]))
            for p in self._last_params)
        stacked = {}
        for j in range(L):
            for i in range(n_per_cell):
                vals = [self._cell_trainables[s * L + j][i].data()._data
                        for s in range(S)]
                v = jnp.stack(vals)
                # the TP spec comes from the TEMPLATE cell's param name;
                # same-architecture stages shard identically (rules from
                # tp_rules(block=net) carry exact per-cell names — the
                # template's is the canonical one for its position)
                tp = _tp_spec(self._cell_trainables[j][i].name,
                              v.ndim - 1)
                stacked[f"c{j}_p{i}"] = jnp.copy(
                    jax.device_put(v, pipe_sh(tp)))
        self._stacked = stacked
        self._opt_state = self._opt.init(
            (self._first_vals, self._stacked, self._last_vals))
        self._M = S if pipeline_microbatches is None \
            else int(pipeline_microbatches)
        if self._M < 1:
            raise MXNetError("pipeline_microbatches must be >= 1")
        self._schedule = pipeline_schedule or "gpipe"
        if self._schedule not in ("gpipe", "1f1b"):
            raise MXNetError(
                f"unknown pipeline_schedule {self._schedule!r} "
                "(gpipe | 1f1b)")
        self._step_count = 0
        self._jit_cache = {}

    # _shard_batch / mesh come from SPMDTrainer (whose __init__ this
    # class REPLACES rather than extends — the parameter storage is
    # stacked-by-stage, not per-Parameter)

    @property
    def params(self):
        out = {p.name: v for p, v in
               zip(self._first_params, self._first_vals)}
        out.update({p.name: v for p, v in
                    zip(self._last_params, self._last_vals)})
        from .spmd import _fetch_full
        L, S = self._L, self._S
        for j in range(L):
            for i in range(len(self._cell_trainables[0])):
                # allgather first: pipe-sharded stacked leaves are not
                # fully addressable on a multi-host mesh (same routing
                # sync_to_block uses)
                leaf = _fetch_full(self._stacked[f"c{j}_p{i}"])
                for s in range(S):
                    out[self._cell_trainables[s * L + j][i].name] = \
                        leaf[s]
        return out

    def _build_step(self):
        if self._schedule == "1f1b":
            return self._build_step_1f1b()
        return self._build_step_gpipe()

    def _stage_closures(self):
        """The per-stage forward + loss-head closures shared by both
        schedules (templates captured once; pure fn(params, x))."""
        import jax
        import jax.numpy as jnp
        from ..gluon.block import functional_call
        from ..ndarray.ndarray import NDArray
        from .. import autograd as _ag

        L = self._L
        templates = self._cells[:L]
        tmpl_params = self._cell_trainables[:L]
        n_per_cell = len(tmpl_params[0])
        last_fn, loss_blk = self._last_fn, self._loss
        key = jax.random.PRNGKey(0)   # dropout refused: never consumed

        def stage_fn(tree, x):
            for j in range(L):
                vals = [tree[f"c{j}_p{i}"] for i in range(n_per_cell)]
                outs, _ = functional_call(
                    templates[j], tmpl_params[j], vals, [], [],
                    [NDArray(x)], True, key)
                x = _require_single_output(outs)
            return x

        def mb_loss(lv, fv, out, labels):
            outs = last_fn(lv, fv, out)
            outs = outs if isinstance(outs, (list, tuple)) else [outs]
            with _ag.pause(train_mode=True):
                l_nd = loss_blk(*[NDArray(o) for o in outs],
                                NDArray(labels))
            return jnp.mean(l_nd._data)

        return stage_fn, mb_loss

    def _build_step_1f1b(self):
        """The 1F1B schedule: each tick runs ONE forward and ONE backward
        microbatch per stage, with the backward written out explicitly
        (per-stage ``jax.vjp`` + manual cotangent hops) instead of
        differentiating through the whole forward scan.

        Why it exists: under ``jax.grad``-over-scan (the GPipe path),
        every tick's residuals are saved for the transpose — peak
        activation memory grows with the microbatch count M.  Here the
        only activation state is a circular stash of the last 2S stage
        INPUTS (the forward is recomputed inside each stage's vjp —
        remat-style), so peak memory is O(S), independent of M: raising
        M to shrink the bubble no longer costs memory.

        Timing: stage s forwards microbatch f at tick s + f and backwards
        microbatch b at tick (2S - 1 - s) + b — the classic 1F1B offsets;
        in-flight activations per stage = 2(S - s) - 1 <= 2S - 1 (hence
        the 2S stash).  Total ticks M + 2S - 1 covering forward AND
        backward, vs GPipe's (M + S - 1) forward ticks plus the same
        again in the AD-generated reverse sweep.

        Equivalence: identical math to GPipe, reordered — the trainer
        test proves loss-parity against the 1-device oracle for both
        schedules.  (Reference analog: none — SURVEY §2.4; PipeDream/
        Megatron 1F1B re-derived for the SPMD single-program form.)"""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        mesh, S, M = self._mesh, self._S, self._M
        pipe, data = self._pipe_axis, self._data_axis
        first_fn = self._first_fn
        stage_fn, mb_loss = self._stage_closures()
        D = 2 * S                       # stash depth >= max in-flight

        def body(fv, sv, lv, ids_l, labels_l):
            stage = jax.lax.axis_index(pipe)
            p_stage = jax.tree.map(lambda a: a[0], sv)
            b_l = ids_l.shape[0]
            ids_mb = ids_l.reshape(M, b_l // M, *ids_l.shape[1:])
            labels_mb = labels_l.reshape(M, b_l // M,
                                         *labels_l.shape[1:])
            x0_shape = jax.eval_shape(first_fn, fv, ids_mb[0])
            zx = jnp.zeros(x0_shape.shape, x0_shape.dtype)
            stash0 = jnp.zeros((D,) + x0_shape.shape, x0_shape.dtype)

            def tick(carry, t):
                (stash, f_buf, b_buf, g_sv, g_fv, g_lv,
                 loss_acc) = carry
                # ---- forward lane: microbatch t - stage
                f_mb = t - stage
                f_ok = (f_mb >= 0) & (f_mb < M)
                f_idx = jnp.clip(f_mb, 0, M - 1)
                x0 = first_fn(fv, ids_mb[f_idx])
                in_f = jnp.where(stage == 0, x0, f_buf)
                out_f = stage_fn(p_stage, in_f)
                slot_f = f_idx % D
                stash = stash.at[slot_f].set(
                    jnp.where(f_ok, in_f, stash[slot_f]))
                # ---- backward lane: microbatch t - (2S - 1 - stage)
                b_mb = t - (2 * S - 1 - stage)
                b_ok = (b_mb >= 0) & (b_mb < M)
                b_idx = jnp.clip(b_mb, 0, M - 1)
                x_in = stash[b_idx % D]
                out_b, stage_vjp = jax.vjp(stage_fn, p_stage, x_in)
                lb = labels_mb[b_idx]
                loss_b, (g_lv_h, g_fv_h, cot_head) = jax.value_and_grad(
                    lambda a: mb_loss(a[0], a[1], a[2], lb))(
                        (lv, fv, out_b))
                is_last = stage == S - 1
                cot_out = jnp.where(is_last, cot_head, b_buf)
                g_p_inc, d_in = stage_vjp(cot_out)
                # stage-0 embed backward chains the returned input
                # cotangent into first_fn's params (tied-head grads for
                # fv come from the head vjp on the last stage; both
                # contributions accumulate, psum'd over pipe after)
                _, emb_vjp = jax.vjp(
                    lambda f: first_fn(f, ids_mb[b_idx]), fv)
                (g_fv_e,) = emb_vjp(d_in)

                def acc(ok):
                    return lambda g, inc: g + jnp.where(
                        ok, inc, jnp.zeros_like(inc))
                g_sv = jax.tree.map(acc(b_ok), g_sv, g_p_inc)
                g_lv = jax.tree.map(acc(b_ok & is_last), g_lv, g_lv_h)
                g_fv = jax.tree.map(acc(b_ok & is_last), g_fv, g_fv_h)
                g_fv = jax.tree.map(acc(b_ok & (stage == 0)),
                                    g_fv, g_fv_e)
                loss_acc = loss_acc + jnp.where(b_ok & is_last,
                                                loss_b, 0.0)
                f_nxt = jax.lax.ppermute(
                    out_f, pipe, [(i, (i + 1) % S) for i in range(S)])
                b_nxt = jax.lax.ppermute(
                    d_in, pipe, [(i, (i - 1) % S) for i in range(S)])
                return (stash, f_nxt, b_nxt, g_sv, g_fv, g_lv,
                        loss_acc), None

            carry0 = (stash0, zx, zx,
                      jax.tree.map(jnp.zeros_like, p_stage),
                      jax.tree.map(jnp.zeros_like, fv),
                      jax.tree.map(jnp.zeros_like, lv),
                      jnp.zeros((), jnp.float32))
            (_, _, _, g_sv, g_fv, g_lv, loss_acc), _ = jax.lax.scan(
                tick, carry0, jnp.arange(M + 2 * S - 1))
            # mean over microbatches (the GPipe objective) + data axis;
            # fv/lv contributions live on stages 0 / S-1 -> psum(pipe)
            loss = jax.lax.pmean(jax.lax.psum(loss_acc / M, pipe), data)
            g_fv = jax.tree.map(
                lambda g: jax.lax.pmean(jax.lax.psum(g / M, pipe), data),
                g_fv)
            g_lv = jax.tree.map(
                lambda g: jax.lax.pmean(jax.lax.psum(g / M, pipe), data),
                g_lv)
            g_sv = jax.tree.map(
                lambda g: jax.lax.pmean(g / M, data)[None], g_sv)
            return loss, g_fv, g_sv, g_lv

        fv_specs = jax.tree.map(lambda _: P(), self._first_vals)
        lv_specs = jax.tree.map(lambda _: P(), self._last_vals)
        sv_specs = pipe_specs(self._stacked, pipe)

        def batch_spec(x):
            return P(data, *([None] * (x.ndim - 1)))

        opt = self._opt

        def pure_step(fv, sv, lv, opt_state, step, ids, labels):
            sharded = shard_map(
                body, mesh=mesh,
                in_specs=(fv_specs, sv_specs, lv_specs,
                          batch_spec(ids), batch_spec(labels)),
                out_specs=(P(), fv_specs, sv_specs, lv_specs),
                check_vma=False,
                # data/pipe are MANUAL (the schedule psums over them);
                # every other mesh axis (e.g. a tensor-parallel 'model')
                # stays AUTO — GSPMD shards the stage matmuls over it
                # from the parameter shardings alone (3D parallelism)
                axis_names=frozenset({data, pipe}))
            loss, g_fv, g_sv, g_lv = sharded(fv, sv, lv, ids, labels)
            (nf, ns, nl), nstate = opt.update(
                (fv, sv, lv), (g_fv, g_sv, g_lv), opt_state, step)
            return loss, nf, ns, nl, nstate

        donate = (0, 1, 2, 3) if self._donate else ()
        fv_sh = tuple(v.sharding for v in self._first_vals)
        lv_sh = tuple(v.sharding for v in self._last_vals)
        sv_sh = {k: v.sharding for k, v in self._stacked.items()}
        from .. import telemetry as _telemetry
        return _telemetry.instrument_jit(
            "pipeline:1f1b",
            jax.jit(pure_step,
                    out_shardings=(None, fv_sh, sv_sh, lv_sh, None),
                    donate_argnums=donate))

    def _build_step_gpipe(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map

        mesh, S, M = self._mesh, self._S, self._M
        pipe, data = self._pipe_axis, self._data_axis
        first_fn = self._first_fn
        stage_fn, mb_loss = self._stage_closures()

        def body(fv, sv, lv, ids_l, labels_l):
            stage = jax.lax.axis_index(pipe)
            p_stage = jax.tree.map(lambda a: a[0], sv)
            b_l = ids_l.shape[0]
            ids_mb = ids_l.reshape(M, b_l // M, *ids_l.shape[1:])
            labels_mb = labels_l.reshape(M, b_l // M,
                                         *labels_l.shape[1:])
            x0_shape = jax.eval_shape(first_fn, fv, ids_mb[0])
            buf = jnp.zeros(x0_shape.shape, x0_shape.dtype)
            losses0 = jnp.zeros((M,), jnp.float32)

            def tick(carry, t):
                buf, losses = carry
                mb_in = jnp.clip(t, 0, M - 1)
                # non-0 stages compute-and-discard the embed (the price
                # of single-program SPMD form; see class docstring)
                x0 = first_fn(fv, ids_mb[mb_in])
                inp = jnp.where(stage == 0, x0, buf)
                out = stage_fn(p_stage, inp)
                idx = jnp.clip(t - stage, 0, M - 1)
                loss_t = mb_loss(lv, fv, out, labels_mb[idx])
                valid = ((stage == S - 1) & (t >= stage)
                         & (t < stage + M))
                losses = losses.at[idx].set(
                    jnp.where(valid, loss_t, losses[idx]))
                nxt = jax.lax.ppermute(
                    out, pipe, [(i, (i + 1) % S) for i in range(S)])
                return (nxt, losses), None

            (_, losses), _ = jax.lax.scan(
                tick, (buf, losses0), jnp.arange(M + S - 1))
            # only the last stage wrote real losses; psum replicates
            loss = jax.lax.psum(jnp.sum(losses) / M, pipe)
            return jax.lax.pmean(loss, data)

        fv_specs = jax.tree.map(lambda _: P(), self._first_vals)
        lv_specs = jax.tree.map(lambda _: P(), self._last_vals)
        sv_specs = pipe_specs(self._stacked, pipe)

        def batch_spec(x):
            return P(data, *([None] * (x.ndim - 1)))

        opt = self._opt

        def pure_step(fv, sv, lv, opt_state, step, ids, labels):
            sharded = shard_map(
                body, mesh=mesh,
                in_specs=(fv_specs, sv_specs, lv_specs,
                          batch_spec(ids), batch_spec(labels)),
                out_specs=P(), check_vma=False,
                # see _build_step_1f1b: non-data/pipe axes stay auto
                axis_names=frozenset({data, pipe}))

            def loss_of(tr):
                f, s, l = tr
                return sharded(f, s, l, ids, labels)

            loss, grads = jax.value_and_grad(loss_of)((fv, sv, lv))
            (nf, ns, nl), nstate = opt.update((fv, sv, lv), grads,
                                              opt_state, step)
            return loss, nf, ns, nl, nstate

        donate = (0, 1, 2, 3) if self._donate else ()
        fv_sh = tuple(v.sharding for v in self._first_vals)
        lv_sh = tuple(v.sharding for v in self._last_vals)
        sv_sh = {k: v.sharding for k, v in self._stacked.items()}
        from .. import telemetry as _telemetry
        return _telemetry.instrument_jit(
            "pipeline:gpipe",
            jax.jit(pure_step,
                    out_shardings=(None, fv_sh, sv_sh, lv_sh, None),
                    donate_argnums=donate))

    def step(self, *batch):
        """One pipelined train step (ids, labels); returns the scalar
        loss (replicated, async)."""
        import jax.numpy as jnp
        ids, labels = batch
        sharded = tuple(self._shard_batch(b) for b in batch)
        dp = self._mesh.shape[self._data_axis]
        b_local = sharded[0].shape[0] // dp
        if sharded[0].shape[0] % dp or b_local % self._M:
            raise MXNetError(
                f"global batch {sharded[0].shape[0]} must split over "
                f"data axis {dp} x microbatches {self._M}")
        cache_key = tuple((a.shape, str(a.dtype)) for a in sharded)
        if cache_key not in self._jit_cache:
            self._jit_cache[cache_key] = self._build_step()
        self._step_count += 1
        step_arr = jnp.asarray(self._step_count, jnp.int32)
        (loss, self._first_vals, self._stacked, self._last_vals,
         self._opt_state) = self._jit_cache[cache_key](
            self._first_vals, self._stacked, self._last_vals,
            self._opt_state, step_arr, *sharded)
        return loss

    def sync_to_block(self):
        """Write trained values back into the net's Parameters (cell
        leaves unstacked to their per-stage owners; multi-host shards
        allgathered first, like SPMDTrainer.sync_to_block)."""
        import jax
        from .spmd import _fetch_full
        for p, v in zip(
                list(self._first_params) + list(self._last_params),
                list(self._first_vals) + list(self._last_vals)):
            dev = p.data().ctx.jax_device()
            p._data._set_data(jax.device_put(_fetch_full(v), dev))
        L, S = self._L, self._S
        for j in range(L):
            for i in range(len(self._cell_trainables[0])):
                leaf = _fetch_full(self._stacked[f"c{j}_p{i}"])
                for s in range(S):
                    p = self._cell_trainables[s * L + j][i]
                    dev = p.data().ctx.jax_device()
                    p._data._set_data(jax.device_put(leaf[s], dev))
