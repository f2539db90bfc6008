"""Device-plane observability tests (docs/observability.md "Device
plane"): the dispatch ledger's closed-program-set accounting for plain
and speculative engines; the ``mxtpu_dispatches_per_token``
dispatch-economy gauge (exactly 1.0 for plain decode, < 1.0 when a
draft amortizes dispatches over accepted bursts); OOM forensics — an
injected ``RESOURCE_EXHAUSTED`` dispatch failure produces exactly ONE
debounced flight dump carrying the per-owner memory breakdown, the
program inventory, and the implicated request ids; the on-demand
``jax.profiler`` capture (CPU-backend round-trip, single-capture
guard, HTTP route, router fan-out); and federation of the new gauges
through the router's ``/metrics``."""
import glob
import http.client
import json
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import (fault, telemetry, telemetry_device,
                                 telemetry_ring)
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models.gpt import GPTModel
from incubator_mxnet_tpu.serving import (ContinuousBatcher,
                                         GenerationEngine, ModelServer)
from incubator_mxnet_tpu.serving import slo as _slo
from incubator_mxnet_tpu.serving.router import Router


@pytest.fixture(autouse=True)
def _clean_state():
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()
    yield
    fault.clear_plan()
    telemetry.stop()
    telemetry.reset()
    _slo.tracker.reset()


def _gpt(max_length=64, seed=3):
    mx.random.seed(seed)
    net = GPTModel(vocab_size=50, units=32, hidden_size=64,
                   num_layers=2, num_heads=2, max_length=max_length,
                   dropout=0.0)
    net.initialize(init=mx.init.Normal(0.6))
    net(mx.nd.array(np.zeros((1, 2), np.int32)))   # settle shapes
    return net


def _engine(name="g", max_slots=2, max_len=64, **kw):
    return GenerationEngine(_gpt(max_length=max_len), name=name,
                            max_slots=max_slots, max_len=max_len, **kw)


def _get(port, path, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = (resp.status, resp.read())
    conn.close()
    return out


def _post(port, path, body=b"{}", timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path,
                 body=body if isinstance(body, bytes)
                 else json.dumps(body).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


# ------------------------------------------- closed-program-set ledger
def test_closed_program_set(monkeypatch):
    eng = _engine(name="obsd")
    assert eng.warmup() == eng.expected_programs
    inv = eng.program_inventory()
    assert inv["model"] == "obsd"
    assert inv["compiled_programs"] == inv["expected_programs"]
    assert inv["slots"] == []                  # warm-up left none live
    # every warmed program shows up as a ledger site; sites that never
    # dispatched (the verify wrapper on a draftless engine) sit at 0 —
    # that surplus-program visibility IS the inventory's point
    sites = telemetry.dispatch_ledger(prefix="serving:obsd:")
    assert "serving:obsd:prefill_ext" in sites
    decode = sites["serving:obsd:decode"]
    assert decode["dispatches"] >= 1
    assert decode["last_dispatch_age_s"] is not None
    assert "seconds_p50" in decode and "seconds_p99" in decode
    # accounting drift is LOUD: a warmup whose compile count disagrees
    # with the closed-set prediction must raise, not limp along
    monkeypatch.setattr(eng, "compiled_programs", lambda: 999)
    with pytest.raises(MXNetError, match="program accounting drift"):
        eng.warmup()


def test_closed_program_set_spec_and_dispatches_per_token():
    tnet = _gpt()
    tgt = GenerationEngine(tnet, name="obst", max_slots=2, max_len=64)
    drf = GenerationEngine(tnet, name="obsf", max_slots=2, max_len=64)
    tgt.attach_draft(drf, spec_k=4)            # draft IS the target:
    tgt.warmup()                               # accept rate 1
    inv = tgt.program_inventory()
    assert inv["spec_k"] == 4
    assert inv["compiled_programs"] == inv["expected_programs"]
    assert inv["draft"]["model"] == "obsf"
    assert inv["draft"]["compiled_programs"] == \
        inv["draft"]["expected_programs"]
    # the verify program is a distinct ledger site of the closed set
    assert any(s.endswith(":verify")
               for s in telemetry.dispatch_ledger(prefix="serving:obst:"))
    # dispatch economy: with a perfect draft each verify dispatch emits
    # k+1 tokens per slot, so dispatches-per-token sits well below 1
    b = ContinuousBatcher(tgt, name="obst")
    try:
        assert len(b.submit([3, 7, 11], max_new_tokens=10)) == 10
        st = b.stats()
        assert st["dispatches_per_token"] is not None
        assert st["dispatches_per_token"] < 1.0
        assert st["dispatches_per_token"] == pytest.approx(
            1.0 / st["accepted_tokens_per_dispatch"])
        g = telemetry.registry.get("mxtpu_dispatches_per_token")
        assert g.sample()["model=obst"] < 1.0
    finally:
        b.close()


def test_dispatches_per_token_per_step_is_exactly_one():
    # scan_steps=0 disables the burst program: every decode dispatch
    # advances every live slot by exactly one token, so per-slot
    # normalization makes the ratio exactly 1.0
    b = ContinuousBatcher(_engine(name="obsp", scan_steps=0),
                          name="obsp")
    try:
        b.submit([3, 7, 11], max_new_tokens=6)
        b.submit([5, 5], max_new_tokens=4)
        st = b.stats()
        assert st["decode_scan_steps"] == 0
        assert st["decode_burst_dispatches"] == 0
        assert st["dispatches_per_token"] == pytest.approx(1.0)
        g = telemetry.registry.get("mxtpu_dispatches_per_token")
        assert g.sample()["model=obsp"] == pytest.approx(1.0)
    finally:
        b.close()


def test_dispatches_per_token_burst_approaches_one_over_k():
    # default-on burst path: once the lone stream reaches steady state
    # (no joins pending) each dispatch buys up to scan_steps tokens —
    # the cumulative ratio must land at <= 1/k plus the measurement
    # tolerance from the per-step prefix before bursts engage
    b = ContinuousBatcher(_engine(name="obsb", max_len=128,
                                  scan_steps=8), name="obsb")
    try:
        out = b.submit([3, 7, 11], max_new_tokens=100)
        assert len(out) == 100
        st = b.stats()
        assert st["decode_scan_steps"] == 8
        assert st["decode_burst_dispatches"] > 0
        assert st["dispatches_per_token"] <= 0.2
        g = telemetry.registry.get("mxtpu_dispatches_per_token")
        assert g.sample()["model=obsb"] <= 0.2
        h = telemetry.registry.get("mxtpu_decode_burst_tokens")
        assert h.sample()["count"] == st["decode_burst_dispatches"]
    finally:
        b.close()


# ----------------------------------------------------- OOM forensics
def test_oom_forensics_single_debounced_flight_dump(monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv("MXNET_FLIGHT_DUMP_DIR", str(tmp_path))
    rec = telemetry_ring.recorder
    rec.reset()                                # restore dump budget
    rec.start()
    eng = _engine(name="oomg")
    b = ContinuousBatcher(
        eng, name="oomg",
        retry_policy=fault.RetryPolicy(max_retries=0,
                                       base_seconds=0.01,
                                       deadline_seconds=0.5))
    oom0 = telemetry.registry.get("mxtpu_oom_failures").value
    fault.install_plan(
        "serving.infer:ioerror:RESOURCE_EXHAUSTED: injected device "
        "oom@1-99")
    try:
        # two back-to-back RESOURCE_EXHAUSTED failures inside the 1 s
        # debounce window: each increments the counter, but the flight
        # recorder writes exactly ONE dump
        for _ in range(2):
            with pytest.raises(IOError, match="RESOURCE_EXHAUSTED"):
                b.submit([3, 7, 11], max_new_tokens=4,
                         request_id="oom-rid")
        assert telemetry.registry.get("mxtpu_oom_failures").value \
            == oom0 + 2
        deadline = time.monotonic() + 10
        dumps = []
        while time.monotonic() < deadline:
            dumps = glob.glob(
                str(tmp_path / "flight_*_resource_exhausted.json"))
            if dumps:
                break
            time.sleep(0.05)
        assert len(dumps) == 1
        time.sleep(0.3)                        # a second writer would
        dumps = glob.glob(                     # have landed by now
            str(tmp_path / "flight_*_resource_exhausted.json"))
        assert len(dumps) == 1
        with open(dumps[0]) as f:
            payload = json.load(f)
        assert payload["reason"] == "resource_exhausted"
        # per-owner memory attribution rides on the dump
        owners = payload["device_memory"]["owners"]
        assert "kv:oomg" in owners and "params:oomg" in owners
        assert "unattributed_bytes" in payload["device_memory"]
        # ...as does the runtime program inventory
        assert "oomg" in payload["programs"]["engines"]
        assert "sites" in payload["programs"]
        # ...and the ring names the implicated requests
        ooms = [e for e in payload["ring"]
                if e.get("event") == "oom"]
        assert ooms and ooms[0]["site"] == "serving.infer"
        assert "oom-rid" in ooms[0]["request_ids"]
    finally:
        b.close()
        rec.stop()
        rec.reset()


# ------------------------------------------------- profiler capture
def test_profiler_capture_roundtrip_and_guard(tmp_path):
    import os
    cap0 = telemetry.registry.get("mxtpu_profile_captures").value
    path = telemetry_device.capture_profile(0.05,
                                            out_dir=str(tmp_path))
    assert os.path.isdir(path) and path.startswith(str(tmp_path))
    assert telemetry.registry.get("mxtpu_profile_captures").value \
        == cap0 + 1
    # single-capture guard: a second capture during the window is
    # refused (jax.profiler holds one trace per process)
    started = threading.Event()
    done = threading.Event()

    def long_capture():
        started.set()
        telemetry_device.capture_profile(0.5, out_dir=str(tmp_path))
        done.set()

    t = threading.Thread(target=long_capture, daemon=True)
    t.start()
    started.wait(5)
    deadline = time.monotonic() + 2
    while not telemetry_device.capture_active() \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert telemetry_device.capture_active()
    with pytest.raises(telemetry_device.CaptureBusy):
        telemetry_device.capture_profile(0.05, out_dir=str(tmp_path))
    t.join(60)      # the profiler's stop took over 10 s on a loaded host
    assert done.is_set() and not telemetry_device.capture_active()


def test_profiler_capture_writes_the_xplane_alone(tmp_path):
    """The capture's device trace is one ``.xplane.pb`` that
    ``ProfileData`` reads, where jax's export puts it, and no
    ``trace.json.gz``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = telemetry_device.capture_profile(0.05, out_dir=str(tmp_path))
    files = [os.path.relpath(f, path) for f in glob.glob(
        os.path.join(path, "**", "*"), recursive=True) if os.path.isfile(f)]
    xplane = [f for f in files if f.endswith(".xplane.pb")]
    assert len(xplane) == 1
    assert xplane[0].split(os.sep)[:2] == ["plugins", "profile"]
    assert sorted(files) == sorted(xplane + ["spans.json"])
    assert [p.name for p in
            ProfileData.from_file(os.path.join(path, xplane[0])).planes]
    # the first capture left no session behind: a second one starts
    import jax._src.profiler as jp
    assert jp._profile_state.profile_session is None
    telemetry_device.capture_profile(0.05, out_dir=str(tmp_path))


# ------------- HTTP surface: server routes + router federation/fan-out
def test_http_device_routes_and_router_federation(monkeypatch,
                                                  tmp_path):
    monkeypatch.setenv("MXNET_PROFILE_DIR", str(tmp_path))
    eng = _engine(name="g")
    srv = ModelServer(port=0)
    srv.add_model("g", eng)
    srv.start()
    router = Router([f"127.0.0.1:{srv.port}"], port=0,
                    health_interval=0.05, retry_deadline=5.0,
                    federate_seconds=0.05).start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not router._eligible():
            time.sleep(0.05)
        assert router._eligible()
        s, out = _post(router.port, "/v1/models/g:generate",
                       {"tokens": [3, 7, 11], "max_new_tokens": 4})
        assert s == 200 and len(out["tokens"]) == 4
        # -- replica-side routes ----------------------------------------
        s, body = _get(srv.port, "/programs")
        rep = json.loads(body)
        assert s == 200
        assert rep["engines"]["g"]["compiled_programs"] >= 1
        assert rep["engines"]["g"]["expected_programs"] \
            == eng.expected_programs
        assert any(site.startswith("serving:g:")
                   for site in rep["sites"])
        s, body = _get(srv.port, "/memory")   # refreshes owner gauges
        mem = json.loads(body)
        assert s == 200
        assert "kv:g" in mem["owners"]
        assert mem["owned_bytes"] >= mem["owners"]["kv:g"] > 0
        # the inventory is merged into /v1/models per model
        s, body = _get(srv.port, "/v1/models")
        models = json.loads(body)["models"]
        assert models["g"]["programs"]["expected_programs"] \
            == eng.expected_programs
        # on-demand capture round-trips over HTTP on the CPU backend
        import os
        s, out = _post(srv.port, "/debug/profile?seconds=0.05")
        assert s == 200 and os.path.isdir(out["profile"])
        s, out = _post(srv.port, "/debug/profile?seconds=nope")
        assert s == 400
        # -- router federation ------------------------------------------
        router._federate_maybe(force=True)
        s, body = _get(router.port, "/metrics")
        text = body.decode()
        assert s == 200
        # the new device-plane series federate through the router
        assert "mxtpu_dispatches_per_token" in text
        assert "mxtpu_device_owned_bytes" in text
        assert "mxtpu_dispatches_total" in text
        # fan-out views: one answer PER replica, keyed by replica id
        rid = router._eligible()[0].id
        s, body = _get(router.port, "/programs")
        rep = json.loads(body)["replicas"]
        assert s == 200 and rep[rid]["engines"]["g"][
            "expected_programs"] == eng.expected_programs
        s, body = _get(router.port, "/memory")
        rep = json.loads(body)["replicas"]
        assert s == 200 and "kv:g" in rep[rid]["owners"]
        # profiler fan-out: one artifact per replica
        s, out = _post(router.port, "/debug/profile?seconds=0.05")
        assert s == 200
        assert os.path.isdir(out["replicas"][rid]["profile"])
    finally:
        router.stop()
        srv.stop()


# ----------------------------------------------------------- the CLI
def test_cli_device_flags_require_fleet(monkeypatch, capsys):
    import sys

    from incubator_mxnet_tpu import _cli
    for argv in (["mxtpu-stats", "--memory"],
                 ["mxtpu-stats", "--programs"],
                 ["mxtpu-stats", "--profile", "1"]):
        monkeypatch.setattr(sys, "argv", argv)
        with pytest.raises(SystemExit) as ei:
            _cli.stats_main()
        assert ei.value.code == 2
        assert "--fleet" in capsys.readouterr().err
