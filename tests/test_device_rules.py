"""The device rules of the chip round, on the CPU with jax's device lists
monkeypatched: the default context follows the default backend, a context
whose device does not exist raises (nothing is substituted), a peak is
never guessed, the compile cache is placed from outside or at one fixed
in-checkout path, and a process that never placed anything stays off the
device backend."""
import os
import subprocess
import sys

import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import compile_cache, context


class _Dev:
    def __init__(self, platform, id=0):
        self.platform, self.id = platform, id


# ------------------------------------------------------ default context
def test_default_context_follows_the_default_backend(monkeypatch):
    import jax
    assert mx.current_context() == mx.cpu(0)      # JAX_PLATFORMS=cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mx.current_context() == mx.tpu(0)
    # an explicit scope still wins over the backend
    with mx.cpu(0):
        assert mx.current_context() == mx.cpu(0)
    assert mx.current_context() == mx.tpu(0)


def test_default_context_places_arrays_on_the_accelerator(monkeypatch):
    """What `mxtpu-serve` relies on: parameters and inputs created with no
    ctx= resolve to the first accelerator when one is the default."""
    import jax
    chip = jax.local_devices()[3]                  # stand-in "tpu:0"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(context, "_accelerators", lambda: [chip])
    arr = mx.nd.zeros((2, 2))
    assert arr.context == mx.tpu(0)
    assert arr._data.devices() == {chip}


# ------------------------------------------- no substitute for a device
def test_tpu_context_without_accelerator_raises():
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.tpu(0).jax_device()
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.nd.ones((2, 3), ctx=mx.gpu(0))


def test_cpu_context_without_cpu_backend_raises(monkeypatch):
    import jax

    def local_devices(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return [_Dev("tpu")]
    monkeypatch.setattr(jax, "local_devices", local_devices)
    with pytest.raises(mx.MXNetError, match="no CPU backend"):
        mx.cpu(0).jax_device()


def test_accelerator_index_out_of_range_raises(monkeypatch):
    monkeypatch.setattr(context, "_accelerators", lambda: [_Dev("tpu")])
    with pytest.raises(mx.MXNetError, match="only 1 accelerator"):
        mx.tpu(1).jax_device()


# --------------------------------------------------------- compile cache
class _ConfigSpy:
    """Stands in for ``jax.config`` inside the resolver: records updates
    instead of applying them, so the session's real config never moves."""

    def __init__(self, cache_dir=None):
        self.jax_compilation_cache_dir = cache_dir
        self.updates = {}

    def update(self, key, value):
        self.updates[key] = value


def _resolve_with(monkeypatch, spy):
    import jax
    monkeypatch.setattr(jax, "config", spy)
    return compile_cache.ensure_compile_cache()


def test_compile_cache_env_set_means_code_sets_no_directory(monkeypatch,
                                                            tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    spy = _ConfigSpy(cache_dir=str(tmp_path))      # jax read the env itself
    assert _resolve_with(monkeypatch, spy) == str(tmp_path)
    assert "jax_compilation_cache_dir" not in spy.updates
    # the zero floors apply either way: serving is many small programs
    assert spy.updates == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1}


def test_compile_cache_unset_uses_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    spy = _ConfigSpy()
    _resolve_with(monkeypatch, spy)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(mx.__file__)))
    assert spy.updates["jax_compilation_cache_dir"] \
        == os.path.join(repo, ".jax_cache") == compile_cache.DEFAULT_DIR
    assert not compile_cache.DEFAULT_DIR.startswith("/tmp")


def test_supervise_compile_cache_exports_the_jax_variable(monkeypatch):
    """``mxtpu-supervise --compile-cache DIR`` places the replicas' cache
    from outside: it reaches them as JAX_COMPILATION_CACHE_DIR."""
    from incubator_mxnet_tpu import _cli, serving
    seen = {}

    class FakeSupervisor:
        def __init__(self, command, **kw):
            seen.update(kw)

        def start(self):
            raise SystemExit(0)
    monkeypatch.setattr(serving, "Supervisor", FakeSupervisor)
    monkeypatch.setattr(sys, "argv", [
        "mxtpu-supervise", "--compile-cache", "/srv/cc",
        "--command", "server --port {port}"])
    with pytest.raises(SystemExit):
        _cli.supervise_main()
    assert seen["child_env"] == {"JAX_COMPILATION_CACHE_DIR": "/srv/cc"}


# ------------------------------------------------- one process per chip
_OFF_CHIP = r"""
import json, sys, urllib.request
import jax
from jax._src import xla_bridge
from incubator_mxnet_tpu import context, telemetry, telemetry_ring
from incubator_mxnet_tpu.serving import Router, Supervisor

telemetry.start()
router = Router(["127.0.0.1:9"], port=0, host="127.0.0.1")
router.start()
sup = Supervisor(["true", "{port}"], replicas=1, router=router)
try:
    # what an ejection / quarantine does: dump the flight recorder with
    # the router's and the supervisor's providers registered
    telemetry_ring.recorder.register_provider("supervisor", sup.state)
    payload = telemetry_ring.recorder.payload("incident:test")
    urllib.request.urlopen(
        f"http://127.0.0.1:{router.port}/metrics", timeout=10).read()
    telemetry.render_prometheus()
    telemetry.snapshot()
finally:
    router.stop()
print(json.dumps({"providers": sorted(payload),
                  "backend_in_use": context.backend_in_use(),
                  "backends_initialized":
                      xla_bridge.backends_are_initialized()}))
"""


def test_router_and_supervisor_never_initialize_a_backend():
    """The router/supervisor process imports the package, dumps through
    the shared flight recorder and serves /metrics — none of which may
    bring a jax backend up: on the chip host that would take the chip the
    replica needs."""
    import json
    out = subprocess.run([sys.executable, "-c", _OFF_CHIP],
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "MXNET_TELEMETRY": "1"})
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["backends_initialized"] is False
    assert rec["backend_in_use"] is False
    assert "router" in rec["providers"]
    assert "device_memory" not in rec["providers"]


def test_device_memory_providers_ride_with_the_first_owner():
    """A process that DOES hold a device still gets the forensics: the
    providers register with the first owner/inventory."""
    from incubator_mxnet_tpu import telemetry_device, telemetry_ring
    telemetry_device.register_owner("params:test-owner", lambda: 7.0)
    try:
        payload = telemetry_ring.recorder.payload("test")
        assert payload["device_memory"]["owners"]["params:test-owner"] == 7.0
        assert "programs" in payload
    finally:
        telemetry_device.unregister_owner("params:test-owner")
