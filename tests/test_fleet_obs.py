"""Fleet observability tests (router front tier): cross-process trace
propagation (``X-Trace-Id`` stamping, remote-parent span attrs, router
``/trace`` stitching with synthetic ``unreachable`` legs), metrics
federation (deterministic histogram reservoir union, fleet sums that
equal the arithmetic sum of replica counters, ``mxtpu_router_*``
double-count exclusion, snapshot staleness age-out), fleet SLO merging
by summed windows, and correlated incident bundles (atomic directory,
cross-keyed request ids, per-(reason, replica) debounce).

Same scaffolding as test_router.py: the real :class:`Router` over
scripted stdlib fake replicas, so failure timing is exact.
"""
import http.client
import json
import os
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from incubator_mxnet_tpu import fault, telemetry
from incubator_mxnet_tpu.http_util import parse_trace_id
from incubator_mxnet_tpu.serving import Router
from incubator_mxnet_tpu.serving import slo as _slo
from incubator_mxnet_tpu.telemetry import Histogram


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


# ------------------------------------------------------------ fake fleet
class ObsReplica:
    """A scripted replica for the observability endpoints: answers
    ``/readyz``, ``/slo``, ``/metrics.json``, ``/flight`` and
    ``/trace`` like ``mxtpu-serve``, records the ``X-Trace-Id`` each
    ``:predict`` arrives with, and serves back spans whose
    ``remote_parent`` names the recorded hop sid — the replica half of
    the stitched timeline, with exact timing."""

    def __init__(self):
        self.ready = True
        self.predict_plan = []          # ("ok"|"503", retry_after)
        self.metrics_state = {"counters": {}, "gauges": {},
                              "histograms": {}}
        self.slo_snapshot = {"objectives": {}, "models": {}}
        self.flight = {"ring": [], "fake": True}
        self.trace_headers = []         # raw X-Trace-Id per :predict
        self.spans_by_rid = {}          # rid -> [span dict]
        self._srv = None
        self.port = None

    @property
    def id(self):
        return f"127.0.0.1:{self.port}"

    def start(self, port=0):
        rep = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, code, obj, headers=None):
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path == "/readyz":
                    code = 200 if rep.ready else 503
                    self._json(code, {"status": "ready" if rep.ready
                                      else "unready", "draining": False})
                elif path == "/slo":
                    self._json(200, rep.slo_snapshot)
                elif path == "/metrics.json":
                    self._json(200, rep.metrics_state)
                elif path == "/flight":
                    self._json(200, rep.flight)
                elif path == "/trace":
                    rid = None
                    for part in query.split("&"):
                        if part.startswith("request_id="):
                            rid = urllib.parse.unquote(
                                part.split("=", 1)[1])
                    self._json(200, {"request_id": rid,
                                     "spans": rep.spans_by_rid.get(
                                         rid, [])})
                else:
                    self._json(404, {"error": "?"})

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(n)
                rid = self.headers.get("X-Request-Id", "")
                if self.path.endswith(":predict"):
                    raw = self.headers.get("X-Trace-Id")
                    rep.trace_headers.append(raw)
                    parsed = parse_trace_id(raw)
                    kind, arg = rep.predict_plan.pop(0) \
                        if rep.predict_plan else ("ok", None)
                    if kind == "ok":
                        if parsed is not None:
                            # what a real replica records: a root span
                            # carrying the propagated parentage attrs
                            rep.spans_by_rid.setdefault(
                                parsed[0], []).append(
                                {"name": "serve.request", "cat": "serve",
                                 "attrs": {"request_id": rid,
                                           "trace_id": parsed[0],
                                           "remote_parent": parsed[1],
                                           "replica": rep.id}})
                        self._json(200, {"ok": True, "replica": rep.id,
                                         "request_id": rid})
                    else:
                        self._json(503, {"error": "shedding"},
                                   headers={"Retry-After": arg or 1})
                    return
                self._json(404, {"error": "?"})

        self._srv = ThreadingHTTPServer(("127.0.0.1", port), H)
        self._srv.daemon_threads = True
        self.port = self._srv.server_address[1]
        threading.Thread(target=self._srv.serve_forever,
                         daemon=True).start()
        return self

    def stop(self):
        if self._srv is not None:
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None


def _router(reps, **kw):
    kw.setdefault("port", 0)
    kw.setdefault("health_interval", 30)    # tests drive polls manually
    kw.setdefault("retry_deadline", 5.0)
    specs = [r if isinstance(r, str) else r.id for r in reps]
    return Router(specs, **kw).start()


def _predict(port, rid, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/models/g:predict",
                 body=json.dumps({"inputs": [[1]]}).encode(),
                 headers={"Content-Type": "application/json",
                          "X-Request-Id": rid})
    resp = conn.getresponse()
    out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


def _get(port, path, timeout=10):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    out = (resp.status, body)
    conn.close()
    return out


def _counter_state(name, value, labels="model=g"):
    return {name: {"help": "h", "values": {labels: float(value)}}}


# ------------------------------------------- histogram reservoir union
def test_histogram_merge_exact_when_under_cap():
    a = {"count": 3, "sum": 6.0, "max": 3.0, "samples": [3.0, 1.0, 2.0]}
    b = {"count": 2, "sum": 9.0, "max": 5.0, "samples": [5.0, 4.0]}
    m = Histogram.merge([a, b])
    assert m["count"] == 5 and m["sum"] == 15.0 and m["max"] == 5.0
    assert m["samples"] == [1.0, 2.0, 3.0, 4.0, 5.0]
    st = Histogram.stats_of(m)
    assert st["p50"] == 3.0 and st["max"] == 5.0


def test_histogram_merge_known_distribution_quantiles():
    # one replica holds a tight distribution, the other the slow tail:
    # a merged-reservoir p99 must see the tail, while the p99 of either
    # replica alone (or an average of per-replica p99s) would not
    fast = {"count": 3000, "sum": float(sum(i / 1000 for i in
                                            range(3000))),
            "max": 2.999,
            "samples": [i / 1000 for i in range(3000)]}
    slow = {"count": 3000,
            "sum": float(sum(10 + i / 1000 for i in range(3000))),
            "max": 12.999,
            "samples": [10 + i / 1000 for i in range(3000)]}
    m = Histogram.merge([fast, slow])
    assert m["count"] == 6000 and len(m["samples"]) == 4096
    st = Histogram.stats_of(m)
    # true combined p99 sits deep in the slow pool (~12.9); the fast
    # pool alone tops out below 3
    assert st["p99"] > 12.0
    assert st["max"] == 12.999
    # the union keeps the pools proportionally: roughly half the kept
    # samples come from each side
    kept_slow = sum(1 for s in m["samples"] if s >= 10)
    assert 1900 < kept_slow < 2200
    # deterministic: same inputs, same reservoir (no RNG)
    assert Histogram.merge([fast, slow]) == m


def test_merge_states_sums_and_renders():
    s1 = {"counters": _counter_state("mxtpu_serve_requests", 10),
          "gauges": _counter_state("mxtpu_serve_queue_depth", 3),
          "histograms": {}}
    s2 = {"counters": _counter_state("mxtpu_serve_requests", 32),
          "gauges": _counter_state("mxtpu_serve_queue_depth", 1),
          "histograms": {}}
    fleet = telemetry.merge_states([s1, s2])
    assert fleet["counters"]["mxtpu_serve_requests"]["values"][
        "model=g"] == 42.0
    assert fleet["gauges"]["mxtpu_serve_queue_depth"]["values"][
        "model=g"] == 4.0
    text = telemetry.render_prometheus_state(
        fleet, extra_labels={"cluster": "a"})
    assert 'mxtpu_serve_requests{model="g",cluster="a"} 42' in text


# --------------------------------------------------- trace propagation
def test_parse_trace_id_edge_cases():
    assert parse_trace_id("req-1-00af") == ("req-1", "00af")
    assert parse_trace_id("r-" + "a" * 16) == ("r", "a" * 16)
    # malformed: no separator, non-hex sid, uppercase hex, empty parts
    assert parse_trace_id("plainjunk") is None
    assert parse_trace_id("rid-xyz!") is None
    assert parse_trace_id("rid-00AF") is None
    assert parse_trace_id("-00af") is None
    assert parse_trace_id("rid-") is None
    # oversized header and oversized sid are ignored, not truncated
    assert parse_trace_id("r" * 90 + "-00af") is None
    assert parse_trace_id("rid-" + "a" * 17) is None
    assert parse_trace_id(None) is None
    assert parse_trace_id(12) is None


def test_tracer_remote_parent_attrs():
    telemetry.start()
    with telemetry.tracer.remote("req-9", "beef01"):
        with telemetry.tracer.span("serve.request", cat="serve") as sp:
            pass
    assert sp.attrs["trace_id"] == "req-9"
    assert sp.attrs["remote_parent"] == "beef01"
    # only roots inherit the remote parent: a child span keeps its
    # real in-process parent edge
    with telemetry.tracer.remote("req-10", "beef02"):
        with telemetry.tracer.span("outer") as outer:
            with telemetry.tracer.span("inner") as inner:
                pass
    assert outer.attrs["remote_parent"] == "beef02"
    assert not (inner.attrs or {}).get("remote_parent")
    # outside the context nothing leaks
    with telemetry.tracer.span("later") as later:
        pass
    assert not (later.attrs or {}).get("remote_parent")


def test_stitched_trace_across_failover_legs(tmp_path):
    rep1, rep2 = ObsReplica().start(), ObsReplica().start()
    rep1.predict_plan = [("503", "0")]  # first leg sheds -> failover
    router = _router([rep1, rep2], incident_dir=str(tmp_path))
    try:
        with router._lock:
            router._rr = 1          # pin round-robin: rep1 first
        status, body = _predict(router.port, "trace-req-1")
        assert status == 200 and body["replica"] == rep2.id

        headers = rep1.trace_headers + rep2.trace_headers
        assert len(headers) == 2
        parsed = [parse_trace_id(h) for h in headers]
        assert all(p is not None for p in parsed)
        # same trace root (the request id), DISTINCT hop span ids
        assert {p[0] for p in parsed} == {"trace-req-1"}
        assert len({p[1] for p in parsed}) == 2

        status, raw = _get(router.port, "/trace?request_id=trace-req-1")
        assert status == 200
        stitched = json.loads(raw)
        assert stitched["stitched"] and \
            stitched["request_id"] == "trace-req-1"
        hops = stitched["hops"]
        assert [h["replica"] for h in hops] == [rep1.id, rep2.id]
        assert hops[0]["outcome"] == "shed" and \
            hops[1]["outcome"] == "ok"
        # parentage intact: the ok leg's remote span hangs under the
        # hop whose sid it names; the shed leg produced no replica span
        kids = hops[1]["children"]
        assert kids[0]["attrs"]["remote_parent"] == hops[1]["id"]
        assert kids[0]["attrs"]["trace_id"] == "trace-req-1"
        assert "children" not in hops[0]

        # unknown request id -> 404, missing param -> 400
        assert _get(router.port, "/trace?request_id=nope")[0] == 404
        assert _get(router.port, "/trace")[0] == 400
    finally:
        router.stop()
        rep1.stop()
        rep2.stop()


def test_stitch_trace_unreachable_replica_synthetic_span(tmp_path):
    rep = ObsReplica().start()
    router = _router([rep], incident_dir=str(tmp_path))
    try:
        status, _ = _predict(router.port, "gone-req")
        assert status == 200
        rep.stop()                      # replica dies after serving
        stitched = router.stitch_trace("gone-req")
        kids = stitched["hops"][0]["children"]
        assert kids[0]["name"] == "unreachable"
        assert kids[0]["synthetic"] and kids[0]["replica"] == rep.id
    finally:
        router.stop()


# --------------------------------------------------- metrics federation
def test_fleet_counters_sum_and_no_router_double_count(tmp_path):
    rep1, rep2 = ObsReplica().start(), ObsReplica().start()
    rep1.metrics_state = {
        "counters": {**_counter_state("mxtpu_serve_requests", 10),
                     # a replica must never inflate the router's own
                     # series (shared-registry in-process topologies)
                     **_counter_state("mxtpu_router_requests", 99,
                                      labels="")},
        "gauges": {}, "histograms": {
            "mxtpu_serve_latency_seconds":
                {"help": "h", "count": 2, "sum": 0.3, "max": 0.2,
                 "samples": [0.1, 0.2]}}}
    rep2.metrics_state = {
        "counters": _counter_state("mxtpu_serve_requests", 32),
        "gauges": {}, "histograms": {
            "mxtpu_serve_latency_seconds":
                {"help": "h", "count": 1, "sum": 0.9, "max": 0.9,
                 "samples": [0.9]}}}
    router = _router([rep1, rep2], incident_dir=str(tmp_path))
    try:
        router._federate_maybe(force=True)
        fleet = router.fleet_metrics_state()
        vals = fleet["counters"]["mxtpu_serve_requests"]["values"]
        # fleet sum is the arithmetic sum of the replica counters…
        assert vals["model=g"] == 42.0
        # …with per-replica labeled series alongside
        assert vals[f"replica={rep1.id}"] == 10.0
        assert vals[f"replica={rep2.id}"] == 32.0
        assert "mxtpu_router_requests" not in fleet["counters"]
        merged = fleet["histograms"]["mxtpu_serve_latency_seconds"]
        assert merged["count"] == 3 and merged["samples"] == \
            [0.1, 0.2, 0.9]

        status, raw = _get(router.port, "/metrics")
        text = raw.decode()
        assert status == 200
        assert 'mxtpu_serve_requests{model="g"} 42' in text
        assert f'mxtpu_serve_requests{{replica="{rep1.id}"}} 10' in text
        # the router's own series appear exactly once (local registry)
        assert text.count("# TYPE mxtpu_router_requests counter") == 1
    finally:
        router.stop()
        rep1.stop()
        rep2.stop()


def test_federation_staleness_ages_out_of_fleet_sums(tmp_path):
    rep1, rep2 = ObsReplica().start(), ObsReplica().start()
    rep1.metrics_state = {"counters": _counter_state(
        "mxtpu_serve_requests", 10), "gauges": {}, "histograms": {}}
    rep2.metrics_state = {"counters": _counter_state(
        "mxtpu_serve_requests", 32), "gauges": {}, "histograms": {}}
    router = _router([rep1, rep2], incident_dir=str(tmp_path))
    try:
        router._federate_maybe(force=True)
        # freeze rep1's snapshot in the past, beyond the horizon
        with router._lock:
            router._federation[rep1.id]["time"] -= \
                router._stale_horizon() + 100
        fleet = router.fleet_metrics_state()
        vals = fleet["counters"]["mxtpu_serve_requests"]["values"]
        # the frozen snapshot no longer freezes fleet totals…
        assert vals["model=g"] == 32.0
        # …but its last-known series stays visible, labeled stale
        assert vals[f"replica={rep1.id},stale=true"] == 10.0
        assert vals[f"replica={rep2.id}"] == 32.0
        from incubator_mxnet_tpu.serving import metrics as _m
        assert _m.ROUTER_FEDERATION_STALE.value == 1
    finally:
        router.stop()
        rep1.stop()
        rep2.stop()


def _slo_snapshot(window, bad, slow, p99):
    return {"objectives": {"availability": 0.99,
                           "p99_seconds": 0.5},
            "models": {"g": {"model": "g", "window": window, "bad": bad,
                             "slow": slow, "availability":
                                 1 - bad / window,
                             "availability_objective": 0.99,
                             "p99_seconds": p99,
                             "burn_rate": (bad / window) / 0.01}}}


def test_merge_snapshots_fleet_burn_from_summed_windows():
    merged = _slo.merge_snapshots({
        "a": _slo_snapshot(900, 0, 0, 0.1),
        "b": _slo_snapshot(100, 10, 5, 0.7)})
    g = merged["models"]["g"]
    assert merged["fleet"] and merged["replicas"] == ["a", "b"]
    assert g["window"] == 1000 and g["bad"] == 10
    # burn from summed counts: (10/1000)/0.01 = 1.0 — NOT the average
    # of per-replica burns ((0 + 10)/2 = 5)
    assert g["burn_rate"] == pytest.approx(1.0)
    assert g["p99_seconds_worst_replica"] == 0.7
    assert g["per_replica"]["b"]["bad"] == 10


def test_fleet_slo_endpoint_merges_replicas(tmp_path):
    rep1, rep2 = ObsReplica().start(), ObsReplica().start()
    rep1.slo_snapshot = _slo_snapshot(900, 0, 0, 0.1)
    rep2.slo_snapshot = _slo_snapshot(100, 10, 5, 0.7)
    router = _router([rep1, rep2], incident_dir=str(tmp_path))
    try:
        status, raw = _get(router.port, "/slo")
        body = json.loads(raw)
        assert status == 200 and body["fleet"]
        assert body["models"]["g"]["window"] == 1000
        assert body["models"]["g"]["burn_rate"] == pytest.approx(1.0)
    finally:
        router.stop()
        rep1.stop()
        rep2.stop()


# ------------------------------------------------------ incident bundles
def test_incident_bundle_on_ejection(tmp_path):
    rep = ObsReplica().start()
    inc_dir = str(tmp_path / "incidents")
    router = _router([rep], incident_dir=inc_dir, retry_deadline=0.5,
                     eject_threshold=2)
    try:
        assert _predict(router.port, "ok-req")[0] == 200
        rep.stop()                      # transport failures from now on
        status, _ = _predict(router.port, "doomed-req")
        assert status >= 500

        deadline = time.monotonic() + 10
        bundles = []
        while time.monotonic() < deadline:
            if os.path.isdir(inc_dir):
                bundles = sorted(x for x in os.listdir(inc_dir)
                                 if not x.startswith("."))
            if len(bundles) >= 2:
                break
            time.sleep(0.05)
        # the connect-error storm ejects the replica (one bundle) and
        # the request exhausts failover (one bundle) — exactly once
        # each, debounce collapsing the repeats
        assert len(bundles) == 2, bundles
        reasons = {b.split("_", 3)[3] for b in bundles}
        assert reasons == {"ejected", "failover_exhausted"}, bundles

        ejected = [b for b in bundles
                   if b.split("_", 3)[3] == "ejected"][0]
        bdir = os.path.join(inc_dir, ejected)
        manifest = json.load(open(os.path.join(bdir, "incident.json")))
        assert manifest["reason"] == "ejected"
        assert manifest["replica"] == rep.id
        assert "doomed-req" in manifest["request_ids"]
        for fname in manifest["files"]:
            assert os.path.exists(os.path.join(bdir, fname))
        flight = json.load(open(os.path.join(bdir,
                                             "router_flight.json")))
        assert flight["reason"] == "incident:ejected"
        # the router provider's fleet view rode along in the dump
        assert "recent_hops" in flight.get("router", {})
        assert any(h["request_id"] == "doomed-req"
                   for h in flight["router"]["recent_hops"])
        stitched = json.load(open(os.path.join(
            bdir, "stitched_traces.json")))
        assert "doomed-req" in stitched
        legs = stitched["doomed-req"]["hops"]
        assert legs and all(h["replica"] == rep.id for h in legs)
        assert all(h["outcome"] == "connect_error" for h in legs)
        delta = json.load(open(os.path.join(bdir,
                                            "metrics_delta.json")))
        assert "counters_delta" in delta

        # debounce: a repeat of the same (reason, replica) within the
        # window writes nothing new
        before = len(os.listdir(inc_dir))
        router._incident("ejected", rep.id, ["doomed-req"])
        time.sleep(0.3)
        assert len(os.listdir(inc_dir)) == before
    finally:
        router.stop()


# --------------------------------------------- incremental run journals
def test_pytest_jsonl_journal_roundtrip(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "pytest_jsonl", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "pytest_jsonl.py"))
    pj = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pj)

    path = str(tmp_path / "tier.jsonl")
    lines = [
        {"nodeid": "t.py::a", "outcome": "failed", "when": "call"},
        {"nodeid": "t.py::b", "outcome": "passed", "when": "call"},
        {"nodeid": "t.py::a", "outcome": "passed", "when": "call"},
        {"nodeid": "t.py::c", "outcome": "skipped", "when": "setup"},
    ]
    with open(path, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
        f.write('{"nodeid": "t.py::d", "outco')   # torn tail line
    passed, records = pj.load_journal(path)
    # last verdict wins: the re-run pass of ::a supersedes its failure
    assert passed == {"t.py::a", "t.py::b"}
    assert len(records) == 4
    assert pj.load_journal(str(tmp_path / "missing.jsonl")) == (set(), [])
