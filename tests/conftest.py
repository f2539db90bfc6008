"""Test config: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's trick of exercising distributed paths without a
cluster (reference: tests/nightly/dist_sync_kvstore.py via the dmlc 'local'
tracker) — here multi-device SPMD tests run on 8 virtual CPU devices.  The
chip checks (chip_smoke.py, tests_tpu/) do NOT import this.

Tier-1 is run with ``JAX_PLATFORMS=cpu`` in the environment; the update
below pins the same thing for a bare ``pytest tests/``."""
import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Tier-1 compiles COLD, in this process and in every child it spawns.
# Engines and trainers place a persistent compile cache by themselves
# (compile_cache.ensure_compile_cache: JAX_COMPILATION_CACHE_DIR, else the
# in-checkout .jax_cache), and on cache hits a handful of bit-identity
# tests (checkpoint resume, zero1 interop, fused-vs-unfused optimizer)
# observe different executable numerics — so the cache is switched off
# here unless asked for.  MXNET_TEST_COMPILE_CACHE=1 opts in for local
# iteration (probe: test_model_zoo 36s -> 13s warm).  Test-only knob,
# deliberately not in docs/env_var.md (the registry lint scopes that file
# to package code).
if os.environ.get("MXNET_TEST_COMPILE_CACHE", "0") != "1":
    jax.config.update("jax_enable_compilation_cache", False)
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as _np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded():
    """Reproducible seeding per test (reference:
    tests/python/unittest/common.py @with_seed)."""
    import incubator_mxnet_tpu as mx
    mx.random.seed(42)
    _np.random.seed(42)
    yield


@pytest.fixture(autouse=True)
def _amp_isolation():
    """amp.init() patches op namespaces; never let that leak across
    tests."""
    yield
    from incubator_mxnet_tpu.contrib import amp
    if amp._state["initialized"] or amp._patched:
        amp._reset()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight test excluded from the tier-1 CPU run "
        "(-m 'not slow'); the full suite still runs them")
