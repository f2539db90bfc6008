"""In-program health plane (health.py; docs/observability.md "Health
plane").

Covers the PR's contract: the traced stat helpers
(``train_step_health`` per-leaf norms / derived finite mask / update
ratios, ``decode_health`` logit max / entropy / finite), the bounded
StepHealth ring (``MXNET_HEALTH_RING``), the acceptance bar — params
BIT-identical with ``MXNET_HEALTH_PLANE=1`` vs plane-off across the
SPMD step, the k-step CompiledLoop chunk, the fused eager path and
zero1 — NaN-origin forensics (a ``trainer.grad:nonfinite`` fault plan
names the first offending leaf and step, and yields exactly ONE
debounced ``training_anomaly`` flight dump whose ``health`` provider
carries the attribution), the loss-spike / grad-norm-explosion detector
with its rolling-window baselines and FAULT debounce, and the serving
twin: per-decode-step stats riding the decode outputs into
``ContinuousBatcher.stats()``, the ``nonfinite_generation`` anomaly
naming implicated request ids, ``GET /health`` on the model server, the
router's worst-replica fleet summary, and ``mxtpu-stats --health``."""
import glob
import http.client
import json
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd as ag
from incubator_mxnet_tpu import (fault, health, parallel, telemetry,
                                 telemetry_ring)
from incubator_mxnet_tpu.gluon import Trainer, loss as gloss, nn
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.parallel.loop import CompiledLoop
from incubator_mxnet_tpu.serving import (ContinuousBatcher,
                                         GenerationEngine, ModelServer)
from incubator_mxnet_tpu.serving.router import Router

OPT = {"learning_rate": 0.1, "momentum": 0.9}


@pytest.fixture(autouse=True)
def _clean_state():
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    health.reset()
    yield
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    health.reset()


# ------------------------------------------------- traced stat helpers
def test_train_step_health_values():
    import jax
    import jax.numpy as jnp
    g1 = np.array([[3.0, 4.0], [0.0, 0.0]], np.float32)     # norm 5
    g2 = np.array([2.0, -2.0, 1.0], np.float32)             # norm 3
    w1, w2 = np.ones_like(g1) * 2.0, np.ones_like(g2) * 2.0
    nw1, nw2 = w1 - 0.1 * g1, w2 - 0.1 * g2
    out = jax.jit(lambda g, w, nw: health.train_step_health(
        list(g), list(w), list(nw),
        loss=jnp.asarray(1.5)))((g1, g2), (w1, w2), (nw1, nw2))
    np.testing.assert_allclose(np.asarray(out["grad_norms"]),
                               [5.0, 3.0], rtol=1e-6)
    np.testing.assert_allclose(float(out["grad_norm"]),
                               np.sqrt(25.0 + 9.0), rtol=1e-6)
    assert np.asarray(out["finite"]).tolist() == [True, True]
    for i, (w, nw) in enumerate([(w1, nw1), (w2, nw2)]):
        want = np.linalg.norm(nw - w) / np.linalg.norm(w)
        np.testing.assert_allclose(
            float(np.asarray(out["update_ratios"])[i]), want, rtol=1e-5)
    assert float(out["loss"]) == 1.5


def test_train_step_health_derived_finite_mask_flags_leaf():
    """The finite mask is DERIVED from the norm reduction (NaN/Inf
    propagate through the sum of squares) — no dedicated isfinite pass
    over every leaf, same attribution."""
    import jax
    g1 = np.ones((4,), np.float32)
    g2 = np.array([1.0, np.nan], np.float32)
    g3 = np.array([np.inf, 0.0], np.float32)
    ws = [np.ones_like(g) for g in (g1, g2, g3)]
    out = jax.jit(lambda g, w: health.train_step_health(
        list(g), list(w), list(w)))((g1, g2, g3), tuple(ws))
    assert np.asarray(out["finite"]).tolist() == [True, False, False]
    assert not np.isfinite(float(out["grad_norm"]))


def test_decode_health_values():
    import jax
    V = 16
    uniform = np.zeros((1, V), np.float32)
    peaked = np.zeros((1, V), np.float32)
    peaked[0, 3] = 30.0
    bad = np.full((1, V), np.nan, np.float32)
    fn = jax.jit(health.decode_health)
    m, ent, fin = fn(np.concatenate([uniform, peaked, bad]))
    m, ent, fin = np.asarray(m), np.asarray(ent), np.asarray(fin)
    assert m[0] == 0.0 and m[1] == 30.0
    np.testing.assert_allclose(ent[0], np.log(V), rtol=1e-5)
    assert ent[1] < 1e-3                       # near-deterministic
    assert fin.tolist() == [True, True, False]


# --------------------------------------------------- StepHealth ring
def test_health_ring_bounded_by_env(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_RING", "8")
    telemetry.health_ring.clear()              # re-reads the capacity
    for i in range(20):
        telemetry.health_ring.record({"step": i})
    assert len(telemetry.health_ring) == 8
    assert [e["step"] for e in telemetry.health_ring.entries(last=3)] \
        == [17, 18, 19]
    assert telemetry.health_ring.last()["step"] == 19


# ------------------------------------------------ bit-parity: acceptance
def _mesh():
    import jax
    return parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])


def _net(prefix, seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, in_units=8, activation="relu"))
        net.add(nn.Dense(4, in_units=16))
    net.initialize(init=mx.init.Xavier())
    return net


def _batches(n, b=8):
    rng = np.random.default_rng(0)
    return [(rng.standard_normal((b, 8)).astype(np.float32),
             rng.standard_normal((b, 4)).astype(np.float32))
            for _ in range(n)]


def _params(trainer):
    # strip the per-instance prefix so runs over distinct nets compare
    return {n.split("_", 1)[1]: np.asarray(v)
            for n, v in trainer.params.items()}


def _spmd_params(prefix):
    net = _net(prefix)
    mx.random.seed(7)
    tr = parallel.SPMDTrainer(net, gloss.L2Loss(), "sgd", OPT,
                              mesh=_mesh())
    for x, y in _batches(8):
        tr.step(x, y)
    health.sync()       # while the trainer, and with it its monitor, lives
    return _params(tr)


def test_spmd_step_parity_bitwise(monkeypatch):
    ref = _spmd_params("hsoff_")
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    got = _spmd_params("hson_")
    for name in ref:
        assert np.array_equal(ref[name], got[name]), name
    health.sync()
    assert telemetry.health_ring.last()["src"] == "spmd"


def _loop_params(prefix, k=4):
    net = _net(prefix)
    mx.random.seed(7)
    loop = CompiledLoop(net, gloss.L2Loss(), "sgd", OPT, loop_steps=k,
                        mesh=_mesh())
    losses = loop.run(_batches(8), prefetch=False)
    return _params(loop), losses


def test_loop_chunk_parity_bitwise_and_ring_records(monkeypatch):
    ref, losses_ref = _loop_params("hloff_")
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    got, losses = _loop_params("hlon_")
    for name in ref:
        assert np.array_equal(ref[name], got[name]), name
    np.testing.assert_array_equal(losses_ref, losses)
    # run() syncs the monitor: one record per inner scan step, in
    # order, each carrying the loss that rode the ys
    recs = telemetry.health_ring.entries()
    assert [r["step"] for r in recs] == list(range(1, 9))
    assert all(r["src"] == "loop" and r["finite"] for r in recs)
    for r, want in zip(recs, losses):
        assert r["loss"] == pytest.approx(float(want), rel=1e-6)
        assert r["grad_norm"] > 0 and r["max_update_ratio"] > 0
    assert telemetry.counters_flat()["mxtpu_health_steps"] == 8
    rep = health.report(last=4)
    assert rep["enabled"] and rep["status"] == "ok"
    assert rep["anomaly_total"] == 0 and len(rep["ring"]) == 4
    assert rep["ring_depth"] == 8 and rep["last_anomaly"] is None


def _fused_train(prefix, zero1, steps=4):
    mx.random.seed(7)
    np.random.seed(7)
    net = nn.Sequential(prefix=prefix)
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.nd.array(np.random.randn(5, 6).astype(np.float32))
    y = mx.nd.array(np.random.randn(5, 3).astype(np.float32))
    net(x)
    tr = Trainer(net.collect_params(), "adam",
                 {"learning_rate": 0.01, "wd": 1e-3},
                 fused=True, zero1=zero1)
    loss_fn = gloss.L2Loss()
    for _ in range(steps):
        with ag.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        tr.step(5)
    tr.sync_health()
    return [p.data().asnumpy()
            for p in net.collect_params().values()], tr


@pytest.mark.parametrize("zero1", [False, True])
def test_fused_parity_bitwise(monkeypatch, zero1):
    ref, _ = _fused_train("hf_off_", zero1)
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    got, tr = _fused_train("hf_on_", zero1)
    assert tr._fused._health is not None
    if zero1:
        assert tr._fused._z_state is not None   # shards engaged
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)
    # the eager fused path never sees the loss — records carry
    # grad/update stats only
    rec = telemetry.health_ring.last()
    assert rec["src"] == "fused" and rec["loss"] is None
    assert rec["finite"] and rec["step"] == 4


# --------------------------------------------- NaN-origin forensics
def test_nonfinite_attribution_names_first_leaf(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    seen = []
    fh = telemetry.HEALTH.subscribe(lambda **kw: seen.append(kw))
    try:
        fault.install_plan("trainer.grad:nonfinite@2")
        mx.random.seed(7)
        net = nn.Sequential(prefix="hnf_")
        net.add(nn.Dense(4, in_units=3))
        net.initialize()
        x = mx.nd.array(np.ones((2, 3), np.float32))
        y = mx.nd.array(np.ones((2, 4), np.float32))
        net(x)
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1}, fused=True,
                     skip_nonfinite=True)
        loss_fn = gloss.L2Loss()
        for _ in range(3):
            with ag.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(2)
        tr.sync_health()
        first_leaf = tr._updatable[0][1].name   # _poison_grads hits it
        anom = health.last_anomaly()
        assert anom is not None and anom["kind"] == "nonfinite"
        assert anom["step"] == 2 and anom["src"] == "fused"
        assert anom["leaf"] == first_leaf
        assert first_leaf in anom["detail"]
        # the ring record for step 2 carries the same attribution
        bad = [r for r in telemetry.health_ring.entries()
               if not r["finite"]]
        assert len(bad) == 1 and bad[0]["step"] == 2
        assert bad[0]["nonfinite_leaf"] == first_leaf
        # ...and only step 2 went anomalous (the skip guard held the
        # params, so 3 recovers clean)
        assert [kw["kind"] for kw in seen] == ["nonfinite"]
        c = telemetry.registry.get("mxtpu_health_anomalies")
        assert c.sample()["by"]["kind=nonfinite,src=fused"] == 1
        assert health.report()["status"] == "anomalous"
    finally:
        telemetry.HEALTH.unsubscribe(fh)


def test_anomaly_yields_single_debounced_flight_dump(monkeypatch,
                                                     tmp_path):
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    monkeypatch.setenv("MXNET_FLIGHT_DUMP_DIR", str(tmp_path))
    rec = telemetry_ring.recorder
    rec.reset()                                # restore dump budget
    rec.start()
    try:
        # a NaN plateau: every step from 2 on is poisoned — the monitor
        # flags each, but the per-kind debounce means ONE fault, and
        # the flight recorder writes ONE training_anomaly artifact
        fault.install_plan("trainer.grad:nonfinite@2-99")
        mx.random.seed(7)
        net = nn.Sequential(prefix="hfd_")
        net.add(nn.Dense(4, in_units=3))
        net.initialize()
        x = mx.nd.array(np.ones((2, 3), np.float32))
        y = mx.nd.array(np.ones((2, 4), np.float32))
        net(x)
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.1}, fused=True,
                     skip_nonfinite=True)
        loss_fn = gloss.L2Loss()
        for _ in range(5):
            with ag.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            tr.step(2)
        tr.sync_health()
        deadline = time.monotonic() + 10
        dumps = []
        while time.monotonic() < deadline:
            dumps = glob.glob(
                str(tmp_path / "flight_*_training_anomaly.json"))
            if dumps:
                break
            time.sleep(0.05)
        assert len(dumps) == 1
        time.sleep(0.3)                        # a second writer would
        dumps = glob.glob(                     # have landed by now
            str(tmp_path / "flight_*_training_anomaly.json"))
        assert len(dumps) == 1
        with open(dumps[0]) as f:
            payload = json.load(f)
        assert payload["reason"] == "training_anomaly"
        # the health provider carries the forensics: leaf + step
        # attribution, the StepHealth tail, the dispatch ledger
        first_leaf = tr._updatable[0][1].name
        h = payload["health"]
        assert h["last_anomaly"]["kind"] == "nonfinite"
        assert h["last_anomaly"]["leaf"] == first_leaf
        assert h["last_anomaly"]["step"] == 2
        assert any(r.get("nonfinite_leaf") == first_leaf
                   for r in h["ring"])
        assert "dispatch_ledger" in h
    finally:
        rec.stop()
        rec.reset()


# ------------------------------------------------ detector baselines
def _rec(step, loss=1.0, gnorm=1.0, finite=True, leaf=None):
    r = {"step": step, "src": "unit", "loss": loss, "grad_norm": gnorm,
         "max_update_ratio": 0.01, "finite": finite}
    if leaf:
        r["nonfinite_leaf"] = leaf
    return r


def test_detector_loss_spike_and_gradnorm_explosion():
    mon = health.HealthMonitor(["a", "b"], src="unit")
    faults = []
    ff = telemetry.FAULT.subscribe(lambda **kw: faults.append(kw))
    try:
        for i in range(8):                     # fill the baselines
            mon._detect(_rec(i))
        assert health.last_anomaly() is None   # warm-up never flags
        mon._detect(_rec(8, loss=1.2, gnorm=1.1))   # in-band
        assert health.last_anomaly() is None
        mon._detect(_rec(9, loss=10.0))        # > 4x rolling mean
        anom = health.last_anomaly()
        assert anom["kind"] == "loss_spike" and anom["step"] == 9
        mon._detect(_rec(10, gnorm=50.0))      # > 10x rolling mean
        assert health.last_anomaly()["kind"] == "grad_norm_explosion"
        # one FAULT per kind within the debounce window, even though a
        # second spike lands right away
        mon._detect(_rec(11, loss=10.0))
        kinds = [f["kind"] for f in faults if f["event"] == "anomaly"]
        assert kinds == ["loss_spike", "grad_norm_explosion"]
        c = telemetry.registry.get("mxtpu_health_anomalies")
        assert c.sample()["by"]["kind=loss_spike,src=unit"] == 2
    finally:
        telemetry.FAULT.unsubscribe(ff)


def test_detector_nonfinite_skips_baseline_poisoning():
    mon = health.HealthMonitor(["a", "b"], src="unit")
    for i in range(8):
        mon._detect(_rec(i))
    mon._detect(_rec(8, loss=float("nan"), gnorm=float("nan"),
                     finite=False, leaf="b"))
    anom = health.last_anomaly()
    assert anom["kind"] == "nonfinite" and anom["leaf"] == "b"
    # the NaN step must not enter the rolling windows: the next clean
    # step compares against the clean baseline and stays quiet
    health.reset()
    mon._detect(_rec(9))
    assert health.last_anomaly() is None
    assert len(mon._loss_win) == 9             # 8 warm-up + step 9


# ------------------------------------------------------- serving twin
def _gpt(max_length=64, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64,
                   num_layers=2, num_heads=2, max_length=max_length,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


def test_decode_health_rides_decode_into_stats(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    net = _gpt()
    eng = GenerationEngine(net, name="hg", max_slots=2, max_len=64)
    b = ContinuousBatcher(eng, name="hg")
    try:
        out = b.submit([3, 7, 11], max_new_tokens=4)
        assert len(out) == 4
        st = b.stats()
        dh = st["decode_health"]
        assert dh["finite"] and st["nonfinite_generations"] == 0
        assert np.isfinite(dh["logit_max"])
        assert dh["entropy_mean"] >= 0.0
        g = telemetry.registry.get("mxtpu_health_logit_max")
        assert g.sample()["model=hg"] == pytest.approx(dh["logit_max"])
        g = telemetry.registry.get("mxtpu_health_decode_entropy")
        assert g.sample()["model=hg"] >= 0.0
    finally:
        b.close()


def test_plane_off_decode_unchanged(monkeypatch):
    monkeypatch.delenv("MXNET_HEALTH_PLANE", raising=False)
    eng = GenerationEngine(_gpt(), name="hoff", max_slots=2, max_len=64)
    b = ContinuousBatcher(eng, name="hoff")
    try:
        assert len(b.submit([3, 7, 11], max_new_tokens=3)) == 3
        assert eng.last_decode_health() is None
        assert "decode_health" not in b.stats()
    finally:
        b.close()


def test_nonfinite_generation_anomaly_names_requests(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    net = _gpt()
    eng = GenerationEngine(net, name="hnan", max_slots=2, max_len=64)
    b = ContinuousBatcher(eng, name="hnan")
    try:
        b.submit([3, 7, 11], max_new_tokens=2)     # healthy warm-up
        for p in net.collect_params().values():    # then poison live
            bad = p.data().asnumpy().copy()        # (read-only view)
            bad[:] = np.nan
            p.set_data(mx.nd.array(bad))
        b.submit([5, 9], max_new_tokens=2, request_id="nan-rid")
        st = b.stats()
        assert st["nonfinite_generations"] >= 1
        assert not st["decode_health"]["finite"]
        anom = health.last_anomaly()
        assert anom["kind"] == "nonfinite_generation"
        assert anom["src"] == "hnan"
        assert "nan-rid" in anom["request_ids"]
        c = telemetry.registry.get("mxtpu_health_nonfinite_generations")
        assert c.sample()["by"]["model=hnan"] >= 1
        assert health.report()["status"] == "anomalous"
    finally:
        b.close()


# --------------------------- HTTP surface: /health + router federation
def _get(port, path, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = (resp.status, resp.read())
    conn.close()
    return out


def test_http_health_route_and_router_fleet(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_PLANE", "1")
    eng = GenerationEngine(_gpt(), name="hh", max_slots=2, max_len=64)
    srv = ModelServer(port=0)
    srv.add_model("hh", eng)
    srv.start()
    router = Router([f"127.0.0.1:{srv.port}"], port=0,
                    health_interval=0.05, retry_deadline=5.0,
                    federate_seconds=0.05).start()
    try:
        srv._models["hh"].submit([3, 7, 11], max_new_tokens=3)
        s, body = _get(srv.port, "/health")
        rep = json.loads(body)
        assert s == 200
        assert rep["enabled"] and rep["status"] == "ok"
        assert rep["models"]["hh"]["decode_health"]["finite"]
        assert rep["models"]["hh"]["nonfinite_generations"] == 0
        # the router view: per-replica bodies + the fleet roll-up
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not router._eligible():
            time.sleep(0.05)
        rid = router._eligible()[0].id
        s, body = _get(router.port, "/health")
        fleet = json.loads(body)
        assert s == 200
        assert fleet["status"] == "ok"
        assert fleet["fleet_anomaly_total"] == 0
        assert fleet["replicas"][rid]["models"]["hh"][
            "decode_health"]["finite"]
        # inject one anomaly → the roll-up turns anomalous and the
        # worst-replica summary points at it
        health.serving_anomaly("hh", 7, ["rid-1"])
        s, body = _get(router.port, "/health")
        fleet = json.loads(body)
        assert fleet["status"] == "anomalous"
        assert fleet["fleet_anomaly_total"] == 1
        assert fleet["worst"]["replica"] == rid
        assert fleet["worst"]["last_anomaly"]["kind"] \
            == "nonfinite_generation"
    finally:
        router.stop()
        srv.stop()


# ----------------------------------------------------------- the CLI
def test_cli_health_flag_requires_fleet(monkeypatch, capsys):
    import sys

    from incubator_mxnet_tpu import _cli
    monkeypatch.setattr(sys, "argv", ["mxtpu-stats", "--health"])
    with pytest.raises(SystemExit) as ei:
        _cli.stats_main()
    assert ei.value.code == 2
    assert "--fleet" in capsys.readouterr().err
