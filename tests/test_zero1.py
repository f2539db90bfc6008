"""ZeRO-1 weight-update sharding (arXiv:2004.13336) tests.

Covers the PR's contract: ShardSpec layout bookkeeping (uneven padding
round-trip, dtype grouping, per-leaf scalar expansion), parity of
the zero1 fused step vs the replicated fused step for every elementwise
rule on the 8-virtual-device dp mesh (to rounding: `_assert_mesh_parity`),
the ONE-donated-dispatch
invariant (jit-cache counters at the ``zero1_update`` site), the
memory / traffic gauges (state bytes >= 4x reduction, all-gather
volume), LAMB fallback to the replicated path, flush/rehydrate of the
flat shards around out-of-envelope steps, SPMDTrainer + CompiledLoop
wiring (dp-sharded state leaves, k-step chunk parity), shard-count-
agnostic checkpoints (save at N=8, resume at N=4, interop with
non-zero1 trainers), and the reduce-scatter-shaped kvstore pushpull.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu import fault, parallel, telemetry
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.checkpoint import AsyncCheckpointer
from incubator_mxnet_tpu.gluon import Trainer, loss as gloss, nn
from incubator_mxnet_tpu.parallel import zero1 as z1
from incubator_mxnet_tpu.parallel.loop import CompiledLoop


@pytest.fixture(autouse=True)
def _clean_state():
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    yield
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()


def _devices():
    import jax
    return jax.devices()


def _assert_mesh_parity(a, b):
    """The sharded update against the replicated one on a mesh: the same
    formula over the same operands (on the CPU mesh both sides all-reduce
    the gradients alike: the first step's weights ARE bit-equal), compiled
    as two programs — a flat ``padded / N`` shard a device against a loop a
    leaf — in which the backend contracts different multiply-adds into
    FMAs (measured, PR 46: nag's bias leaf is the all-FMA result on one side
    and the every-op-rounded one on the other).  One rounding an op apart,
    so a tolerance of a few float32 ulps; bit-equality only where one
    program runs (a single device, a shard's round trip)."""
    np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


# ------------------------------------------------- ShardSpec bookkeeping
def test_shard_spec_uneven_padding_roundtrip():
    """Leaf sizes that do not divide the shard count are zero-padded to
    the next multiple; flatten/unflatten is the exact inverse."""
    rng = np.random.default_rng(0)
    leaves = [rng.standard_normal(s).astype(np.float32)
              for s in [(5,), (3, 4), (2, 1, 3)]]          # total 23
    spec = z1.build_shard_spec(leaves, 8)
    assert spec.n_shards == 8 and spec.n_leaves == 3
    (seg,) = spec.segments
    assert seg.total == 23 and seg.padded == 24
    assert seg.padded % 8 == 0
    flat = np.asarray(z1.flatten_segment(seg, leaves))
    assert flat.shape == (24,)
    np.testing.assert_array_equal(flat[23:], 0.0)          # the padding
    back = z1.unflatten_tree(spec, (flat,))
    for a, b in zip(leaves, back):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_shard_spec_groups_by_dtype_and_empty_pad():
    """Mixed dtypes split into per-dtype segments (order preserved);
    an exactly-divisible segment gets no padding."""
    leaves = [np.zeros((4,), np.float32), np.zeros((2, 3), np.float16),
              np.zeros((4,), np.float32), np.zeros((2,), np.float16)]
    spec = z1.build_shard_spec(leaves, 8)
    assert len(spec.segments) == 2
    f32, f16 = spec.segments
    assert f32.idx == (0, 2) and f32.total == 8 and f32.padded == 8
    assert f16.idx == (1, 3) and f16.total == 8 and f16.padded == 8
    with pytest.raises(MXNetError):
        z1.build_shard_spec(leaves, 0)


def test_expand_per_leaf_matches_broadcast():
    """Per-leaf scalars expanded over the flat layout multiply exactly
    like broadcasting each scalar over its own leaf."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    leaves = [rng.standard_normal(s).astype(np.float32)
              for s in [(3,), (2, 2)]]                     # total 7
    scalars = [jnp.float32(0.5), jnp.float32(-2.0)]
    spec = z1.build_shard_spec(leaves, 4)
    (seg,) = spec.segments
    flat = z1.flatten_segment(seg, leaves)
    vec = z1.expand_per_leaf(seg, scalars)
    prod = np.asarray(flat * vec)
    back = z1.unflatten_tree(spec, (prod,))
    for leaf, s, got in zip(leaves, scalars, back):
        np.testing.assert_array_equal(leaf * np.float32(s),
                                      np.asarray(got))


def test_state_and_allgather_byte_accounting():
    leaves = [np.zeros((10,), np.float32), np.zeros((3,), np.float32)]
    assert z1.per_replica_state_bytes({"m": tuple(leaves)}) == 13 * 4
    spec = z1.build_shard_spec(leaves, 8)                  # padded 16
    assert z1.zero1_allgather_bytes(spec) == 16 * 4 * 7 // 8


# --------------------------------------------- Trainer zero1 bit parity
def _make_net(dtype="float32"):
    np.random.seed(7)
    mx.random.seed(7)
    net = nn.Sequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.nd.array(np.random.randn(5, 6).astype(dtype))
    y = mx.nd.array(np.random.randn(5, 3).astype(dtype))
    if dtype != "float32":
        net.cast(dtype)
    net(x)
    return net, x, y


def _train(optimizer, opt_params, zero1, steps=4, dtype="float32"):
    net, x, y = _make_net(dtype)
    trainer = Trainer(net.collect_params(), optimizer, dict(opt_params),
                      fused=True, zero1=zero1)
    loss_fn = gloss.L2Loss()
    for _ in range(steps):
        with ag.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(5)
    params = [p.data().asnumpy()
              for p in net.collect_params().values()]
    return params, trainer


def _states(trainer):
    if trainer._fused is not None:
        trainer._fused.flush_states()
    out = []
    for i in sorted(trainer._updaters.states):
        out.append(_flatten_state(trainer._updaters.states[i]))
    return out


def _flatten_state(s):
    if s is None:
        return []
    if isinstance(s, tuple):
        return [a for x in s for a in _flatten_state(x)]
    return [s.asnumpy()]


ZERO1_CONFIGS = [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-3}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-3}),
    ("adamw", {"learning_rate": 0.01, "wd": 0.01}),
    ("rmsprop", {"learning_rate": 0.01, "wd": 1e-4}),
    ("adagrad", {"learning_rate": 0.05, "wd": 1e-3}),
    ("adam", {"learning_rate": 0.01, "clip_gradient": 0.1}),
]


@pytest.mark.parametrize("optimizer,opt_params", ZERO1_CONFIGS)
def test_zero1_matches_replicated_fused(optimizer, opt_params):
    """The acceptance bar: the sharded update on the 8-device dp mesh is
    the replicated fused step's to rounding — params AND optimizer
    state (flushed back from the flat shards)."""
    z_p, z_tr = _train(optimizer, opt_params, zero1=True)
    r_p, r_tr = _train(optimizer, opt_params, zero1=False)
    assert z_tr._fused._z_mesh is not None
    assert z_tr._fused._z_state is not None        # shards engaged
    for a, b in zip(z_p, r_p):
        _assert_mesh_parity(a, b)
    for sa, sb in zip(_states(z_tr), _states(r_tr)):
        assert len(sa) == len(sb)
        for a, b in zip(sa, sb):
            _assert_mesh_parity(a, b)


def test_zero1_fp16_multi_precision_bitwise():
    cfg = {"learning_rate": 0.1, "momentum": 0.9,
           "multi_precision": True, "clip_gradient": 0.5}
    z_p, z_tr = _train("sgd", cfg, zero1=True, dtype="float16")
    r_p, r_tr = _train("sgd", cfg, zero1=False, dtype="float16")
    assert z_tr._fused._z_state is not None
    for a, b in zip(z_p, r_p):
        assert a.dtype == np.float16 and np.array_equal(a, b)
    for sa, sb in zip(_states(z_tr), _states(r_tr)):
        for a, b in zip(sa, sb):
            assert a.dtype == np.float32 and np.array_equal(a, b)


# ------------------------------------- dispatch count + memory telemetry
def test_zero1_single_dispatch_and_gauges():
    """One donated dispatch per step (jit-cache counters at the
    zero1_update site see every call), state-bytes gauge >= 4x below
    the replicated gauge, all-gather gauge set to the spec's volume."""
    steps = 4
    telemetry.start()
    _train("adam", {"learning_rate": 0.01, "wd": 1e-3}, zero1=False,
           steps=steps)
    full_bytes = telemetry.counters_flat()["mxtpu_optimizer_state_bytes"]
    telemetry.stop()
    telemetry.reset()

    telemetry.start()
    _, z_tr = _train("adam", {"learning_rate": 0.01, "wd": 1e-3},
                     zero1=True, steps=steps)
    flat = telemetry.counters_flat()
    assert flat["mxtpu_optimizer_fused_updates"] == steps
    assert flat["mxtpu_optimizer_dispatches_per_step"] == 1
    hits = telemetry.registry.get("mx_compile_cache_hits_total")
    misses = telemetry.registry.get("mx_compile_cache_misses_total")
    site = (("site", "zero1_update"),)
    n_miss = misses._values.get(site, 0)
    n_hit = hits._values.get(site, 0)
    assert 1 <= n_miss <= 2
    assert n_hit + n_miss == steps
    shard_bytes = flat["mxtpu_optimizer_state_bytes"]
    assert full_bytes / shard_bytes >= 4          # the memory win
    assert shard_bytes * 8 >= full_bytes          # only padding above 1/8
    spec = z_tr._fused._z_spec
    assert flat["mxtpu_zero1_allgather_bytes"] == \
        z1.zero1_allgather_bytes(spec) > 0


def test_zero1_lamb_falls_back_to_replicated_fused():
    """LAMB's trust ratio straddles shard boundaries: a zero1 request
    stays on the replicated fused path (still one dispatch, still
    parity) — counted at the fused_update site, not zero1_update."""
    telemetry.start()
    z_p, z_tr = _train("lamb", {"learning_rate": 0.01, "wd": 0.01},
                       zero1=True)
    flat = telemetry.counters_flat()
    assert z_tr._fused._z_state is None
    assert flat["mxtpu_optimizer_fused_updates"] == 4
    misses = telemetry.registry.get("mx_compile_cache_misses_total")
    assert misses._values.get((("site", "zero1_update"),), 0) == 0
    assert misses._values.get((("site", "fused_update"),), 0) >= 1
    r_p, _ = _train("lamb", {"learning_rate": 0.01, "wd": 0.01},
                    zero1=False)
    for a, b in zip(z_p, r_p):
        _assert_mesh_parity(a, b)


def test_zero1_flush_and_rehydrate_preserves_momentum():
    """flush_states materializes the 1/N shards into the per-param dict
    (checkpoint format unchanged); further steps re-flatten from it and
    stay bit-identical to an uninterrupted replicated run."""
    net, x, y = _make_net()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05, "momentum": 0.9},
                      fused=True, zero1=True)
    loss_fn = gloss.L2Loss()

    def _step():
        with ag.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(5)

    _step(); _step()
    assert trainer._fused._z_state is not None
    trainer._fused.flush_states()
    assert trainer._fused._z_state is None
    mom = [a for i in sorted(trainer._updaters.states)
           for a in _flatten_state(trainer._updaters.states[i])]
    assert mom and all(np.isfinite(m).all() for m in mom)
    _step(); _step()                                # re-engages shards
    assert trainer._fused._z_state is not None
    z_p = [p.data().asnumpy() for p in net.collect_params().values()]
    r_p, _ = _train("sgd", {"learning_rate": 0.05, "momentum": 0.9},
                    zero1=False)
    for a, b in zip(z_p, r_p):
        assert np.array_equal(a, b)


def test_zero1_env_var_engages(monkeypatch):
    monkeypatch.setenv("MXNET_ZERO1", "1")
    net, x, y = _make_net()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    assert trainer._zero1_requested
    with ag.record():
        loss = gloss.L2Loss()(net(x), y)
    loss.backward()
    trainer.step(5)                      # _init_kvstore builds _fused
    assert trainer._fused is not None
    assert trainer._fused._z_mesh is not None
    assert trainer._fused._z_state is not None


# --------------------------------------------------- SPMDTrainer wiring
def _spmd_batches():
    rng = np.random.default_rng(3)
    return (rng.standard_normal((16, 8)).astype(np.float32),
            rng.standard_normal((16, 4)).astype(np.float32))


def _spmd_net(prefix):
    mx.random.seed(11)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, in_units=8, activation="relu"))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(init=mx.init.Xavier())
    return net


def test_spmd_zero1_parity_and_sharded_state():
    from jax.sharding import PartitionSpec
    mesh = parallel.make_mesh({"data": 8})
    X, Y = _spmd_batches()
    vals = {}
    for z in (False, True):
        tr = parallel.SPMDTrainer(_spmd_net(f"sz{int(z)}_"),
                                  gloss.L2Loss(), "adamw",
                                  {"learning_rate": 0.01, "wd": 0.01},
                                  mesh=mesh, zero1=z)
        for _ in range(4):
            tr.step(X, Y)
        vals[z] = [np.asarray(v) for v in tr._tr_vals]
        if z:
            import jax
            leaves = jax.tree.leaves(tr._opt_state)
            assert leaves
            for leaf in leaves:
                assert leaf.sharding.spec == PartitionSpec("data")
                assert leaf.ndim == 1          # flat segment buffers
    for a, b in zip(vals[True], vals[False]):
        _assert_mesh_parity(a, b)


@pytest.mark.parametrize("zero1", [False, True], ids=["replicated", "zero1"])
def test_spmd_bn_momentum_state_sharding_stable(zero1):
    # Regression: with the optimizer-state out_shardings left
    # unconstrained, GSPMD shards data-axis-divisible momentum leaves
    # (BN-channel-sized, 16 % 8 == 0) while the donated input stays
    # replicated — XLA then rejects the executable with an
    # aliased-buffer size mismatch.  The state must leave the step with
    # the shardings it entered with, on both the replicated and the
    # zero1 path.
    import jax
    mx.random.seed(0)
    net = nn.HybridSequential(prefix=f"bnreg{int(zero1)}_")
    with net.name_scope():
        net.add(nn.Conv2D(16, kernel_size=3, padding=1, in_channels=3))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.GlobalAvgPool2D())
        net.add(nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    with mx.autograd.pause():
        net(mx.nd.array(np.zeros((2, 3, 8, 8), np.float32)))
    mesh = parallel.make_mesh({"data": 8})
    tr = parallel.SPMDTrainer(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9},
                              mesh=mesh, zero1=zero1)
    rng = np.random.RandomState(3)
    x = mx.nd.array(rng.randn(16, 3, 8, 8).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 4, size=(16,)).astype(np.float32))
    sh0 = [v.sharding for v in jax.tree.leaves(tr._opt_state)]
    for _ in range(2):
        loss = tr.step(x, y)
    assert np.isfinite(float(loss))
    sh1 = [v.sharding for v in jax.tree.leaves(tr._opt_state)]
    assert sh0 == sh1


def test_spmd_zero1_conflicts_raise():
    mesh = parallel.make_mesh({"data": 8})
    net = _spmd_net("cf_")
    with pytest.raises(MXNetError, match="two spellings"):
        parallel.SPMDTrainer(net, gloss.L2Loss(), "sgd",
                             {"learning_rate": 0.1}, mesh=mesh,
                             zero1=True, shard_optimizer_state=True)
    with pytest.raises(MXNetError, match="not elementwise"):
        parallel.SPMDTrainer(net, gloss.L2Loss(), "lamb",
                             {"learning_rate": 0.01}, mesh=mesh,
                             zero1=True)
    with pytest.raises(MXNetError, match="does not compose"):
        parallel.SPMDTrainer(net, gloss.L2Loss(), "sgd",
                             {"learning_rate": 0.1},
                             pipeline_axis="pipe", zero1=True)


def test_spmd_zero1_env_fallback_warns_for_lamb(monkeypatch):
    """MXNET_ZERO1=1 with a non-elementwise rule degrades gracefully:
    warn once, train unsharded."""
    monkeypatch.setenv("MXNET_ZERO1", "1")
    mesh = parallel.make_mesh({"data": 8})
    with pytest.warns(UserWarning, match="MXNET_ZERO1"):
        tr = parallel.SPMDTrainer(_spmd_net("ev_"), gloss.L2Loss(),
                                  "lamb", {"learning_rate": 0.01},
                                  mesh=mesh)
    assert not tr._zero1
    X, Y = _spmd_batches()
    tr.step(X, Y)                                  # still trains


# ------------------------------------------------- CompiledLoop + ckpt
def _loop_batches(n, b=8):
    rng = np.random.default_rng(0)
    return [(rng.standard_normal((b, 8)).astype(np.float32),
             rng.standard_normal((b, 4)).astype(np.float32))
            for _ in range(n)]


def _loop_params(loop):
    return {n.split("_", 1)[1]: np.asarray(v)
            for n, v in loop.params.items()}


def test_loop_zero1_chunk_parity():
    """k=4 chunked scan with the zero1 update inside is the non-zero1
    loop's on the same dp mesh, to rounding."""
    mesh = parallel.make_mesh({"data": 8})
    batches = _loop_batches(8)
    opt = {"learning_rate": 0.01, "wd": 0.01}
    got = {}
    for z in (False, True):
        net = _spmd_net(f"lp{int(z)}_")
        mx.random.seed(7)
        loop = CompiledLoop(net, gloss.L2Loss(), "adamw", opt,
                            loop_steps=4, mesh=mesh, zero1=z)
        losses = loop.run(batches, prefetch=False)
        assert np.isfinite(losses).all()
        got[z] = (_loop_params(loop), losses)
    for name in got[False][0]:
        _assert_mesh_parity(got[True][0][name], got[False][0][name])
    _assert_mesh_parity(got[True][1], got[False][1])


def _ckpt_run(tmp_path, tag, z_save, z_resume):
    """Train 4 batches on the N=8 mesh, checkpoint, resume the SAME
    logical run on the N=4 mesh for 4 more; return final params."""
    batches = _loop_batches(8)
    opt = {"learning_rate": 0.05, "momentum": 0.9}
    mesh8 = parallel.make_mesh({"data": 8})
    net_a = _spmd_net(f"{tag}_")
    mx.random.seed(5)
    loop_a = CompiledLoop(net_a, gloss.L2Loss(), "sgd", opt,
                          loop_steps=2, mesh=mesh8, zero1=z_save)
    loop_a.run(batches[:4], prefetch=False)
    ck = AsyncCheckpointer(str(tmp_path / tag))
    ck.save_sync(4, dict(loop_a.params), trainer=loop_a, epoch=0)

    mesh4 = parallel.make_mesh({"data": 4})
    net_b = _spmd_net(f"{tag}_")                   # same prefix/names
    loop_b = CompiledLoop(net_b, gloss.L2Loss(), "sgd", opt,
                          loop_steps=2, mesh=mesh4, zero1=z_resume)
    ck2 = AsyncCheckpointer(str(tmp_path / tag))
    assert ck2.restore_into(params=net_b.collect_params(),
                            trainer=loop_b) == 4
    loop_b.reload_params()
    loop_b.run(batches[4:], prefetch=False)
    return _loop_params(loop_b)


def test_zero1_checkpoint_shard_count_agnostic(tmp_path):
    """The blob stores the portable per-leaf layout: save at N=8 and
    resume at N=4 (and interop with non-zero1 loops in BOTH
    directions) all land on the same params as the never-sharded run."""
    ref = _ckpt_run(tmp_path, "ref", z_save=False, z_resume=False)
    for tag, zs, zr in [("zz", True, True), ("zn", True, False),
                        ("nz", False, True)]:
        got = _ckpt_run(tmp_path, tag, z_save=zs, z_resume=zr)
        for name in ref:
            _assert_mesh_parity(ref[name], got[name])


# --------------------------------------------- kvstore reduce-scatter
def test_pushpull_rs_matches_pushpull():
    """Single process: the RS+AG decomposition is the identity sum —
    bit-equal to pushpull, same out-filling contract, uneven shapes
    round-trip through the padded shard layout."""
    rng = np.random.default_rng(9)
    v = rng.standard_normal((3, 5)).astype(np.float32)     # total 15
    kv = mx.kv.create("dist_sync")
    kv.init("a", mx.nd.zeros((3, 5)))
    kv.init("b", mx.nd.zeros((3, 5)))
    out_rs = mx.nd.zeros((3, 5))
    out_pp = mx.nd.zeros((3, 5))
    kv.pushpull_rs("a", mx.nd.array(v), out=out_rs)
    kv.pushpull("b", mx.nd.array(v), out=out_pp)
    np.testing.assert_array_equal(out_rs.asnumpy(), out_pp.asnumpy())
    pulled = mx.nd.zeros((3, 5))
    kv.pull("a", out=pulled)
    np.testing.assert_array_equal(pulled.asnumpy(), v)


def test_pushpull_rs_fault_sites_preserved():
    """The decomposed path keeps the kvstore.push / kvstore.pull fault
    sites: an injected transient at the reduce-scatter is absorbed by
    the same retry envelope."""
    telemetry.start()
    fault.install_plan("kvstore.push:ioerror@1")
    kv = mx.kv.create("dist_sync")
    kv.init(0, mx.nd.zeros((2, 2)))
    out = mx.nd.zeros((2, 2))
    kv.pushpull_rs(0, mx.nd.ones((2, 2)) * 3, out=out)
    np.testing.assert_array_equal(out.asnumpy(), np.full((2, 2), 3.0))
    assert telemetry.counters_flat()["mxtpu_retries"] >= 1


def test_pushpull_rs_rejects_sparse():
    kv = mx.kv.create("dist_sync")
    kv.init("s", mx.nd.zeros((4, 3)))
    rsp = mx.nd.array(np.eye(4, 3, dtype=np.float32)) \
        .tostype("row_sparse")
    with pytest.raises(MXNetError):
        kv.pushpull_rs("s", rsp)
