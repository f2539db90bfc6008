"""The reduction from a trace to numbers, on a small recorded trace worked by
hand, and the loader on a trace recorded here on the CPU."""
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def planes():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)["planes"]
    return {p: {l: [tuple(e) for e in evs] for l, evs in lines.items()}
            for p, lines in raw.items()}


def test_reduction_by_hand():
    out = trace_reduce.reduce_planes(planes(), {"host_spans": ["bench."]})
    # window: the device's first to last event, 1.0 .. 2.4 ms
    assert out["window_s"] == pytest.approx(1.4e-3)
    # busy: [1.0, 1.4] + [1.6, 1.9] + [2.0, 2.1] + [2.11, 2.4] ms = 1.09 ms
    assert out["busy_s"] == pytest.approx(1.09e-3)
    assert out["idle_pct"] == pytest.approx(100 * (1 - 1.09 / 1.4))
    progs = out["programs"]
    assert progs["jit__decode_paged_pure"] == {"count": 2,
                                               "seconds": pytest.approx(8e-4)}
    assert progs["jit__prefill_paged_pure"]["count"] == 1
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["%gather.2 gather f32[36,8]"] == pytest.approx(5.4e-4)
    assert ops["fusion.9"] == pytest.approx(3.0e-4)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 1.4 .. 1.6 ms: its midpoint lies in the host's bench.data span
    assert gaps["bench.data"] == pytest.approx(2.0e-4)
    assert gaps["after jit__prefill_paged_pure before "
                "jit__decode_paged_pure"] == pytest.approx(1.0e-4)
    assert gaps["short gaps between device operations"] \
        == pytest.approx(1.0e-5)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(1.4e-3)


def test_no_device_plane_is_said():
    p = planes()
    del p["/device:TPU:0"]
    assert "missing" in trace_reduce.reduce_planes(p)


def test_program_name_drops_the_run_id():
    assert trace_reduce.program_name("jit_pure_step(8731)") == "jit_pure_step"
    assert trace_reduce.program_name("fusion.3") == "fusion.3"


def test_loader_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.sync"):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace_reduce.load_planes(trace_reduce.find_xplane(str(tmp_path)))
    names = {name for lines in loaded.values() for evs in lines.values()
             for name, _, _ in evs}
    assert "bench.sync" in names
    assert trace_reduce.summarize(loaded)
