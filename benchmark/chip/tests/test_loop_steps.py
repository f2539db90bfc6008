"""The seven readers of the worker loop's step clock (`loop_steps.py`): on
hand-made snapshots for the arithmetic, on a pair recorded from a tiny served
GPT for the program's own label format, and on a program without the counters
(the parent of the PR that added them)."""
import json
import os

import pytest

import common
import readers
import window

CFG = {"deployment": {"model_name": "m"}}
METRICS = ["join_hash_ms_mean", "join_alloc_ms_mean", "join_edit_ms_mean",
           "join_enqueue_ms_mean", "dispatch_enqueue_ms_mean",
           "emit_fanout_ms_mean", "loop_host_offcpu_pct"]


def snap(steps=None, wall=None, cpu=None, dispatches=None, model="m"):
    counters = {}
    for name, label, values in (
            ("mxtpu_serve_loop_step_seconds", None, steps),
            ("mxtpu_serve_loop_seconds", "phase", wall),
            ("mxtpu_serve_loop_cpu_seconds", "phase", cpu)):
        if values is None:
            continue
        if label is None:
            keyed = {f"model={model},phase={p},step={s}": v
                     for (p, s), v in values.items()}
            keyed.update({f"model=draft,phase={p},step={s}": 100.0
                          for p, s in values})
        else:
            keyed = {f"model={model},{label}={k}": v
                     for k, v in values.items()}
        counters[name] = {"help": "", "values": keyed}
    rows = {f"serving:{model}:{p}": {"dispatches": n}
            for p, n in (dispatches or {}).items()}
    return {"metrics": {"counters": counters, "gauges": {},
                        "histograms": {}},
            "programs": {"engines": {model: {"programs": rows}}}}


def read(name, ctx):
    got = readers.read_all([{"name": name, "unit": "x"}], ctx)
    return got[name][0] if name in got else None


STEPS0 = {("admit", "hash"): 1.0, ("prefill_host", "hash"): 1.0,
          ("admit", "alloc"): 0.5, ("prefill_host", "alloc"): 0.5,
          ("emit", "alloc"): 0.5, ("prefill_host", "edit"): 2.0,
          ("prefill_host", "sampling"): 0.1, ("operands", "edit"): 3.0,
          ("prefill_host", "params"): 0.2, ("prefill_host", "enqueue"): 1.0,
          ("operands", "params"): 0.3, ("operands", "enqueue"): 4.0,
          ("emit", "fanout"): 2.0, ("admit", "lock"): 0.1}
GROWTH = {("admit", "hash"): 0.030, ("prefill_host", "hash"): 0.030,
          ("admit", "alloc"): 0.004, ("prefill_host", "alloc"): 0.006,
          ("emit", "alloc"): 0.050, ("prefill_host", "edit"): 0.018,
          ("prefill_host", "sampling"): 0.002, ("operands", "edit"): 0.5,
          ("prefill_host", "params"): 0.001, ("prefill_host", "enqueue"): 0.039,
          ("operands", "params"): 0.01, ("operands", "enqueue"): 0.17,
          ("emit", "fanout"): 0.33, ("admit", "lock"): 0.7}
STEPS1 = {k: v + GROWTH[k] for k, v in STEPS0.items()}
# 10 joins (7 misses + 3 hits), 100 dispatches (90 bursts + 10 steps)
CALLS0 = {"prefill": 5, "prefill_ext": 1, "decode": 20, "decode_burst": 200,
          "slot_edit": 7}
CALLS1 = {"prefill": 12, "prefill_ext": 4, "decode": 30, "decode_burst": 290,
          "slot_edit": 70}
WALL0 = {"wait": 5.0, "admit": 1.0, "prefill_host": 1.0, "prefill_wait": 1.0,
         "operands": 1.0, "decode_wait": 1.0, "emit": 1.0}
WALL1 = {"wait": 9.0, "admit": 2.0, "prefill_host": 3.0, "prefill_wait": 4.0,
         "operands": 2.0, "decode_wait": 81.0, "emit": 5.0}   # host: 8
CPU0 = {p: 0.5 * v for p, v in WALL0.items()}
CPU1 = {"wait": 2.6, "admit": 1.5, "prefill_host": 2.0, "prefill_wait": 0.6,
        "operands": 1.5, "decode_wait": 2.0, "emit": 1.0}     # host: 4


def ctx_of(**over):
    ctx = {"config": CFG,
           "snap0": snap(STEPS0, WALL0, CPU0, CALLS0),
           "snap1": snap(STEPS1, WALL1, CPU1, CALLS1)}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name,want", [
    ("join_hash_ms_mean", 6.0),             # both chains: 60 ms / 10 joins
    ("join_alloc_ms_mean", 1.0),            # admit + prefill_host, not emit
    ("join_edit_ms_mean", 2.0),             # edit + sampling, prefill_host
    ("join_enqueue_ms_mean", 4.0),          # params + enqueue, prefill_host
    ("dispatch_enqueue_ms_mean", 1.8),      # params + enqueue, operands
    ("emit_fanout_ms_mean", 3.0),           # 330 ms / (100 + 10)
    ("loop_host_offcpu_pct", 50.0)])        # 1 - 4 / 8 over the host phases
def test_readers_take_window_deltas_of_the_served_model(name, want):
    assert read(name, ctx_of()) == pytest.approx(want)


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_gives_none(name):
    """A program older than the counters (the parent), an untraced run, and
    a window without a join or a dispatch: no value, no error."""
    parent = {"metrics": {"counters": {"mxtpu_serve_loop_seconds": snap(
        wall=WALL1)["metrics"]["counters"]["mxtpu_serve_loop_seconds"]},
        "gauges": {}, "histograms": {}},
        "programs": snap(dispatches=CALLS1)["programs"]}
    still = snap(STEPS1, WALL1, CPU1, CALLS1)
    for ctx in ({"config": CFG},
                {"config": CFG, "snap0": None, "snap1": None},
                {"config": CFG, "snap0": parent, "snap1": parent},
                {"config": CFG, "snap0": snap(dispatches=CALLS0),
                 "snap1": parent},
                {"config": CFG, "snap0": still, "snap1": still}):
        assert read(name, ctx) is None


def test_recorded_snapshots_of_the_program():
    """What a served program really exports: the keys parse, every reader
    finds its steps, and the join's four steps stay inside `prefill_host`."""
    with open(os.path.join(common.HERE, "tests", "data",
                           "loop_steps_snapshots.json")) as f:
        rec = json.load(f)
    ctx = {"config": {"deployment": {"model_name": "tiny"}},
           "snap0": rec["snap0"], "snap1": rec["snap1"]}
    got = {name: read(name, ctx) for name in METRICS}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    assert all(got[n] > 0.0 for n in METRICS if n.endswith("_ms_mean"))
    assert 0.0 <= got["loop_host_offcpu_pct"] < 100.0
    import loop_steps
    joins = loop_steps.calls(ctx, loop_steps.JOINS)
    assert joins == 6 and loop_steps.calls(ctx, loop_steps.DISPATCHES) > 6
    host = window.counter_by(ctx, "mxtpu_serve_loop_seconds",
                             "phase")["prefill_host"]
    in_prefill_host = sum(
        loop_steps.step_seconds(ctx, [s], ["prefill_host"])
        for s in ("hash", "alloc", "edit", "sampling", "params", "enqueue"))
    assert 0.5 * host < in_prefill_host <= host + 1e-5
    # the readers' four sums count `hash` and `alloc` in `admit` too
    four = sum(got[n] for n in METRICS[:4]) * joins / 1e3
    assert four >= in_prefill_host - 1e-5
