"""The readers of the program's own counters and histograms
(`window.py` and the seven metrics on it) on hand-made snapshots."""
import pytest

import readers
import window

CFG = {"deployment": {"model_name": "m"}}
METRICS = ["queue_wait_mean_ms", "queue_wait_p95_ms", "loop_host_share_pct",
           "loop_emit_share_pct", "loop_operands_share_pct",
           "loop_prefill_share_pct", "burst_token_share_pct"]


def snap(loop=None, tokens=None, waits=None, other_model=False):
    counters, histograms = {}, {}
    if loop is not None:
        values = {f"model=m,phase={p}": v for p, v in loop.items()}
        if other_model:
            values.update({f"model=draft,phase={p}": 100.0 for p in loop})
        counters["mxtpu_serve_loop_seconds"] = {"help": "", "values": values}
    if tokens is not None:
        counters["mxtpu_generate_tokens"] = {"help": "", "values": tokens}
    if waits is not None:
        histograms["mxtpu_serve_queue_wait_seconds"] = {
            "count": len(waits), "sum": sum(waits), "max": max(waits or [0]),
            "samples": list(waits)[-8:], "help": ""}
    return {"metrics": {"counters": counters, "gauges": {},
                        "histograms": histograms}}


def read(name, ctx):
    entry = {"name": name, "unit": "x"}
    got = readers.read_all([entry], ctx)
    return got[name][0] if name in got else None


LOOP0 = {"wait": 5.0, "admit": 1.0, "prefill_host": 1.0, "prefill_wait": 1.0,
         "operands": 1.0, "decode_wait": 1.0, "emit": 1.0}
LOOP1 = {"wait": 9.0, "admit": 1.5, "prefill_host": 2.0, "prefill_wait": 4.0,
         "operands": 1.5, "decode_wait": 81.0, "emit": 6.0}
# deltas: admit .5, prefill_host 1, prefill_wait 3, operands .5,
# decode_wait 80, emit 5 -> busy 90


def test_loop_shares_are_window_deltas_over_all_but_wait():
    ctx = {"config": CFG, "snap0": snap(LOOP0), "snap1": snap(LOOP1, other_model=True)}
    assert read("loop_host_share_pct", ctx) == pytest.approx(100 * 7 / 90)
    assert read("loop_emit_share_pct", ctx) == pytest.approx(100 * 5.5 / 90)
    assert read("loop_operands_share_pct", ctx) \
        == pytest.approx(100 * 0.5 / 90)
    assert read("loop_prefill_share_pct", ctx) == pytest.approx(100 * 4 / 90)
    host = read("loop_host_share_pct", ctx)
    assert host >= read("loop_emit_share_pct", ctx) \
        + read("loop_operands_share_pct", ctx)


def test_burst_token_share_counts_tokens_by_path():
    before = {"model=m,path=prefill": 10.0, "model=m,path=burst": 100.0}
    after = {"model=m,path=prefill": 12.0, "model=m,path=burst": 180.0,
             "model=m,path=step": 18.0, "model=other,path=step": 1000.0}
    ctx = {"config": CFG, "snap0": snap(tokens=before),
           "snap1": snap(tokens=after)}
    assert read("burst_token_share_pct", ctx) == pytest.approx(80.0)
    assert window.counter_by(ctx, "mxtpu_generate_tokens", "path") == {
        "prefill": 2.0, "burst": 80.0, "step": 18.0}


def test_queue_wait_reads_the_windows_samples():
    old = [9.0, 9.0, 9.0]
    new = [0.1, 0.2, 0.3, 0.4]
    ctx = {"config": CFG, "snap0": snap(waits=old),
           "snap1": snap(waits=old + new)}
    assert read("queue_wait_mean_ms", ctx) == pytest.approx(250.0)
    assert read("queue_wait_p95_ms", ctx) == pytest.approx(385.0)
    count, total, samples = window.histogram_window(
        ctx, "mxtpu_serve_queue_wait_seconds")
    assert (count, samples) == (4, new) and total == pytest.approx(1.0)


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_gives_none(name):
    """A program older than the counters (the parent of the PR that added
    them), an untraced run, and an empty window: no value, no error."""
    older = {"mxtpu_generate_tokens": {"help": "", "values": {"model=m": 7.0}},
             }
    old_snap = {"metrics": {"counters": older, "gauges": {}, "histograms": {
        "mxtpu_serve_queue_wait_seconds": {"count": 0, "sum": 0.0,
                                           "max": None, "samples": []}}}}
    for ctx in ({"config": CFG},
                {"config": CFG, "snap0": None, "snap1": None},
                {"config": CFG, "snap0": old_snap, "snap1": old_snap},
                {"config": CFG, "snap0": snap(LOOP1, {}, [1.0]),
                 "snap1": snap(LOOP1, {}, [1.0])}):
        assert read(name, ctx) is None


def test_a_window_larger_than_the_reservoir_gives_none():
    many = [0.5] * 20          # the fake reservoir keeps the last 8
    ctx = {"config": CFG, "snap0": snap(waits=[1.0]),
           "snap1": snap(waits=[1.0] + many)}
    assert read("queue_wait_p95_ms", ctx) is None
    assert read("queue_wait_mean_ms", ctx) is None
