"""TPU tier: the op-corpus gradient sweep re-run on the real chip
(reference: tests/python/gpu/test_operator_gpu.py does
``from test_operator import *`` then sets default_context = mx.gpu(0) —
the import re-collects every test in this directory's context, where the
autouse fixture pins default context to tpu(0))."""
import pytest

from test_op_gradients import *          # noqa: F401,F403

pytestmark = pytest.mark.usefixtures("highest_matmul_precision")
