"""TPU-tier test config (reference pattern:
tests/python/gpu/test_operator_gpu.py — re-run the CPU suite on the
accelerator + cross-device consistency).

Unlike tests/conftest.py this does NOT pin jax to CPU: the suite runs on
the chip, in ONE process (a chip belongs to one process at a time), and
errors — never skips — when jax's default backend is not a TPU.

Run (on the chip, through the chip tool):  python -m pytest tests_tpu -q
"""
import os
import sys

import numpy as _np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))


def pytest_sessionstart(session):
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU: jax's default backend is "
            f"{platform!r} ({jax.devices()[0].device_kind}). This tier "
            "has no CPU mode — tier-1 is `pytest tests/`.")


@pytest.fixture
def highest_matmul_precision():
    """The chip's default f32 matmul is ONE bf16 pass (operands rounded to
    8 mantissa bits).  The op sweep and the CPU-vs-TPU tier check the ops'
    math — finite differences through a rounded matmul measure rounding,
    not gradients — so they run every MXU op (Pallas kernels included:
    the precision is baked in at trace time) at ``highest``.  Opt-in per
    module (``pytestmark``); test_kernels_tpu.py runs as served."""
    import jax
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _tpu_default_ctx():
    """Every test in this tier runs with default context tpu(0)
    (reference: test_operator_gpu.py sets default_context = mx.gpu(0))."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import test_utils as tu
    mx.random.seed(42)
    _np.random.seed(42)
    ctx = mx.tpu(0)
    tu.set_default_context(ctx)
    with ctx:
        yield
    tu.set_default_context(None)
