"""Telemetry-plane unit tests: event-bus thread safety, metric edge
cases, the span tracer, XLA cost / MFU accounting, and the HTTP
exporter.  Integration with the profiler dump lives in
test_profiler.py; the end-to-end check is ci/run_tests.sh trace_smoke."""
import json
import math
import threading
import time
from urllib.error import HTTPError
from urllib.request import urlopen

import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import telemetry


@pytest.fixture(autouse=True)
def _clean_observability_state():
    mx.profiler.set_state("stop")
    telemetry.stop()
    telemetry.reset()
    telemetry.tracer._enable_count = 0
    yield
    mx.profiler.set_state("stop")
    telemetry.stop()
    telemetry.reset()
    telemetry.tracer._enable_count = 0


# ------------------------------------------------------ event bus safety
def test_subscribe_unsubscribe_race_with_publish():
    """Churning subscribe/unsubscribe from other threads must neither
    drop a delivery to a stable subscriber nor corrupt the topic."""
    t = telemetry.Topic("race")
    got = []
    t.subscribe(got.append)
    stop = threading.Event()

    def churn():
        def fn(_):
            pass
        while not stop.is_set():
            t.subscribe(fn)
            t.unsubscribe(fn)

    workers = [threading.Thread(target=churn) for _ in range(4)]
    for w in workers:
        w.start()
    n = 2000
    try:
        for i in range(n):
            t.publish(i)
    finally:
        stop.set()
        for w in workers:
            w.join()
    assert got == list(range(n))        # no drops, no double delivery
    assert t.subscribers == [got.append]
    assert t.forcing == 1               # churners' bookkeeping unwound
    assert t.errors == 0


def test_unsubscribe_during_publish_does_not_skip_others():
    t = telemetry.Topic("selfremove")
    seen = []

    def a(v):
        seen.append(("a", v))
        t.unsubscribe(a)

    def b(v):
        seen.append(("b", v))

    t.subscribe(a)
    t.subscribe(b)
    t.publish(1)
    assert seen == [("a", 1), ("b", 1)]  # b still saw the in-flight event
    t.publish(2)
    assert seen == [("a", 1), ("b", 1), ("b", 2)]
    assert t.errors == 0 and t.forcing == 1


class _Obj:
    def __init__(self):
        self.n = 0

    def meth(self, *a, **k):
        self.n += 1


def test_bound_method_unsubscribe():
    """obj.meth is a FRESH object per attribute access: unsubscribe must
    match it by equality and keep the forcing count balanced."""
    t = telemetry.Topic("bound")
    o = _Obj()
    t.subscribe(o.meth)
    assert t.forcing == 1
    t.publish()
    assert o.n == 1
    t.unsubscribe(o.meth)               # a different-but-equal object
    assert t.subscribers == [] and t.forcing == 0
    t.publish()
    assert o.n == 1


def test_passive_bound_method_unsubscribe_keeps_forcing_balanced():
    t = telemetry.Topic("passivebound")
    o = _Obj()
    t.subscribe(o.meth, passive=True)
    assert t.forcing == 0
    t.unsubscribe(o.meth)
    assert t.forcing == 0 and t.subscribers == []
    t.unsubscribe(o.meth)               # unknown fn: no-op, no underflow
    assert t.forcing == 0


# -------------------------------------------------- histogram edge cases
def test_histogram_empty():
    h = telemetry.Histogram("h_empty")
    assert h.percentile(0.5) is None
    assert h.stats() == {"count": 0, "sum": 0.0, "p50": None, "p95": None,
                         "p99": None, "max": None}


def test_histogram_single_sample():
    h = telemetry.Histogram("h_one")
    h.observe(3.5)
    assert h.stats() == {"count": 1, "sum": 3.5, "p50": 3.5, "p95": 3.5,
                         "p99": 3.5, "max": 3.5}
    assert h.percentile(0.0) == h.percentile(1.0) == 3.5


def test_histogram_reservoir_overflow():
    h = telemetry.Histogram("h_res", max_samples=8)
    for v in range(100):
        h.observe(float(v))
    s = h.stats()
    # count/sum/max are exact over the FULL stream...
    assert s["count"] == 100
    assert s["sum"] == float(sum(range(100)))
    assert s["max"] == 99.0
    # ...while percentiles come from the last max_samples window (92..99)
    assert h.percentile(0.0) == 92.0
    assert h.percentile(1.0) == 99.0
    assert 92.0 <= s["p50"] <= 99.0


def test_histogram_p99_known_distribution():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("h_p99_seconds")
    for v in range(1000):                   # 0..999 fits the 4096 reservoir
        h.observe(float(v))
    s = h.stats()
    assert s["count"] == 1000 and s["max"] == 999.0
    # nearest-rank on the sorted reservoir: index = round(q * (n - 1))
    assert s["p50"] == 500.0
    assert s["p95"] == 949.0
    assert s["p99"] == 989.0
    prom = reg.render_prometheus()
    assert 'h_p99_seconds{quantile="0.99"} 989.0' in prom
    assert 'h_p99_seconds{quantile="0.5"} 500.0' in prom


# ------------------------------------------------------------ span tracer
def test_trace_span_noop_when_inactive():
    with telemetry.trace_span("x") as sp:
        assert sp is None
    assert telemetry.current_span() is None


def test_span_nesting_and_root_publish():
    telemetry.tracer.enable()
    roots = []
    telemetry.SPAN.subscribe(roots.append)
    try:
        with telemetry.trace_span("outer", cat="test", k=1) as outer:
            assert telemetry.current_span() is outer
            with telemetry.trace_span("inner") as inner:
                assert telemetry.current_span() is inner
                assert inner.parent is outer
        assert telemetry.current_span() is None
    finally:
        telemetry.SPAN.unsubscribe(roots.append)
        telemetry.tracer.disable()
    assert roots == [outer]             # only the ROOT is published
    assert [c.name for c in outer.children] == ["inner"]
    assert outer.attrs == {"k": 1}
    assert outer.seconds >= inner.seconds >= 0


def test_span_cross_thread_attach():
    telemetry.tracer.enable()
    try:
        with telemetry.trace_span("root") as root:
            def worker():
                with telemetry.tracer.attach(root):
                    with telemetry.trace_span("child"):
                        pass
                assert telemetry.current_span() is None
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert [c.name for c in root.children] == ["child"]
        assert root.children[0].tid != root.tid
    finally:
        telemetry.tracer.disable()


def test_traced_decorator():
    @telemetry.traced
    def plain():
        return 1

    @telemetry.traced("named", cat="custom")
    def named():
        return 2

    assert plain() == 1 and named() == 2    # inactive: pure pass-through
    telemetry.tracer.enable()
    try:
        with telemetry.trace_span("root") as root:
            assert plain() == 1 and named() == 2
    finally:
        telemetry.tracer.disable()
    # @traced takes the function's qualname; @traced("name") is explicit
    assert [c.name for c in root.children] == \
        ["test_traced_decorator.<locals>.plain", "named"]
    assert root.children[1].cat == "custom"


def test_chrome_events_nest_on_main_thread_tid_zero():
    telemetry.tracer.enable()
    t0 = time.perf_counter()
    try:
        with telemetry.trace_span("outer"):
            with telemetry.trace_span("inner"):
                time.sleep(0.001)
    finally:
        telemetry.tracer.disable()
    evs = {e["name"]: e for e in telemetry.tracer.chrome_events(t0)}
    assert {"outer", "inner"} <= set(evs)
    o, i = evs["outer"], evs["inner"]
    assert o["ph"] == i["ph"] == "X"
    assert o["tid"] == i["tid"] == 0        # main thread maps to tid 0
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6


def test_tracer_tree_live_and_finished():
    telemetry.tracer.enable()
    try:
        with telemetry.trace_span("done"):
            pass
        ctx = telemetry.trace_span("open")
        ctx.__enter__()
        try:
            tree = telemetry.tracer.tree()
        finally:
            ctx.__exit__(None, None, None)
    finally:
        telemetry.tracer.disable()
    assert any(s["name"] == "done" and "duration_s" in s
               for s in tree["finished"])
    assert any(s["name"] == "open" and s.get("open") for s in tree["live"])


# ------------------------------------------------ cost / MFU accounting
def test_mfu_accounting_from_synthetic_events():
    telemetry.start()
    try:
        telemetry.TRAINER.publish(phase="step", seconds=0.0)  # open window
        telemetry.XLA_COST.publish(where="test", flops=1e9, nbytes=8.0)
        time.sleep(0.005)
        telemetry.TRAINER.publish(phase="step", seconds=0.0)  # close it
        snap = telemetry.snapshot(include_memory=False)
    finally:
        telemetry.stop()
    mfu = snap["gauges"]["mxtpu_mfu"]
    assert mfu is not None and math.isfinite(mfu) and mfu > 0
    assert snap["histograms"]["mxtpu_step_seconds"]["count"] == 1
    assert snap["gauges"]["mxtpu_step_flops"] == 1e9
    assert snap["gauges"]["mxtpu_device_peak_flops"] > 0
    assert snap["counters"]["mx_xla_flops_total"]["total"] == 1e9
    assert snap["counters"]["mx_xla_bytes_total"]["total"] == 8.0


def test_peak_flops_detection():
    assert telemetry.tpu_peak_flops("TPU v4") == 275e12
    # longest-key match: 'v5 lite' must not lose to a shorter key
    assert telemetry.tpu_peak_flops("TPU v5 lite") == 197e12
    assert telemetry.tpu_peak_flops("TPU v5p") == 459e12
    # a kind the table does not list is an error, never the v5e default
    with pytest.raises(mx.MXNetError, match="never-heard-of-it"):
        telemetry.tpu_peak_flops("never-heard-of-it")
    assert telemetry.cpu_peak_flops() > 0
    assert (telemetry.device_peak_flops() or 0) > 0   # CPU host estimate


def test_instrument_jit_publishes_cost_per_call():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    events = []

    def on_cost(**kw):
        events.append(kw)

    telemetry.XLA_COST.subscribe(on_cost)
    try:
        f = telemetry.instrument_jit(
            "costsite", jax.jit(lambda x: (x @ x).sum()))
        x = jnp.ones((16, 16), jnp.float32)
        f(x)
        f(x)
    finally:
        telemetry.XLA_COST.unsubscribe(on_cost)
    assert len(events) == 2
    assert events[0]["where"] == "costsite"
    assert events[0]["flops"] > 0
    assert events[0] == events[1]       # second call reuses the cached cost


# --------------------------------------------------------- HTTP exporter
def test_http_exporter_endpoints():
    from incubator_mxnet_tpu import telemetry_http

    telemetry.start()
    srv = telemetry_http.start_server(0, host="127.0.0.1")
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        metrics = urlopen(base + "/metrics", timeout=10).read().decode()
        assert "mxtpu_mfu" in metrics
        assert "mx_op_dispatch_total" in metrics

        health = json.loads(urlopen(base + "/healthz", timeout=10).read())
        assert health["status"] == "ok"
        assert health["collecting"] is True
        assert health["tracing"] is True

        with telemetry.trace_span("served"):
            tree = json.loads(urlopen(base + "/trace", timeout=10).read())
        assert any(s["name"] == "served" for s in tree["live"])

        with pytest.raises(HTTPError) as exc:
            urlopen(base + "/nope", timeout=10)
        assert exc.value.code == 404
    finally:
        telemetry_http.stop_server()
        telemetry.stop()
    assert telemetry_http.server() is None


# ------------------------------------------------------ monitor bus mode
def test_monitor_bus_mode():
    from incubator_mxnet_tpu.monitor import Monitor

    base_forcing = telemetry.OP_TIMED.forcing
    mon = Monitor(interval=1, pattern="dot")
    mon.install()                       # no executor: op-stream mode
    try:
        assert telemetry.OP_TIMED.forcing == base_forcing + 1
        mon.tic()
        telemetry.OP_TIMED.publish("dot", 0.5)
        telemetry.OP_TIMED.publish("add", 0.1)    # filtered by pattern
        res = mon.toc()
    finally:
        mon.uninstall()
    assert res == [(1, "op:dot", 0.5)]
    assert telemetry.OP_TIMED.forcing == base_forcing
    telemetry.OP_TIMED.publish("dot", 0.5)        # detached: not recorded
    assert mon.queue == []
