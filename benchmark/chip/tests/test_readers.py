"""The stock readers on hand-made snapshots."""
import pytest

import readers

CFG = {"deployment": {"model_name": "m"}}


def snap(decode, burst, prefill_s, decode_s):
    def row(n, s):
        return {"dispatches": n, "seconds_sum": s}
    return {"programs": {"engines": {"m": {"programs": {
                "serving:m:decode": row(decode, decode_s),
                "serving:m:decode_burst": row(burst, 0.0),
                "serving:m:prefill": row(1, prefill_s)}}}}}


def test_programs_share_is_a_window_delta():
    ctx = {"config": CFG, "snap0": snap(10, 0, 1.0, 1.0),
           "snap1": snap(16, 18, 2.0, 4.0)}
    share = readers.programs({"field": "dispatches",
                              "numerator": ["decode_burst"],
                              "denominator": ["decode_burst", "decode"]}, ctx)
    assert share == pytest.approx(100 * 18 / 24)
    wall = readers.programs({"field": "seconds_sum", "numerator": ["prefill"],
                             "denominator": "*"}, ctx)
    assert wall == pytest.approx(100 * 1.0 / 4.0)
    assert readers.programs({"field": "dispatches", "numerator": ["decode"],
                             "denominator": ["decode"]},
                            {"config": CFG, "snap1": None}) is None


def test_models_stats_and_clock_and_counter():
    samples = [{"models": {"m": {"slots_in_use": n, "max_slots": 4}}}
               for n in (1, 2, 4)]
    ctx = {"config": CFG, "samples": samples,
           "series": {"x": [1.0, 2.0, 3.0, 4.0, 5.0]},
           "counters": {"c": 0}, "trace": {"idle_pct": 12.5}}
    spec = {"numerator": "slots_in_use", "denominator": "max_slots"}
    assert readers.models_stats({**spec, "reduce": "max"}, ctx) == 100.0
    assert readers.models_stats({**spec, "reduce": "mean"}, ctx) \
        == pytest.approx(100 * 7 / 12)
    assert readers.bench_clock({"series": "x", "reduce": "p50",
                                "scale": 1000.0}, ctx) == 3000.0
    assert readers.bench_clock({"series": "none", "reduce": "p50"}, ctx) \
        is None
    # a rate is taken over all the work and all the time of the window
    assert readers.bench_clock({"series": "x", "reduce": "per_second"},
                               {**ctx, "window_s": 5.0}) == 3.0
    assert readers.counter({"counter": "c"}, ctx) == 0
    assert readers.trace_idle({}, ctx) == 12.5
