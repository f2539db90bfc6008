#!/usr/bin/env python3
"""Readings that a benchmark PR needs once, on the chip:

    python3 benchmark/chip/tools/calibrate.py --workload <cell> \\
        [--seeds 101,102,... --seconds 10]   program and control, a whole run a seed
        [--rates 4,6,8,10,12 --sweep-seconds 20]   the rate sweep (open loop, one process)

For each seed it prints the numbers `correct` compares, for the program and for
the control (the reference at the next precision below), from which the limits
in the configuration's ``check`` are set.  Results also go to
``chiprun_out/calibrate_<cell>.jsonl``.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

import common                    # noqa: E402
import loadgen                   # noqa: E402
from common import log           # noqa: E402


def dump(name, obj):
    """Raw readings, for working out limits and spreads afterwards."""
    path = os.path.join(ROOT, "chiprun_out", name)
    with open(path, "w") as f:
        json.dump(obj, f)


def emit(out, record):
    log("RECORD " + json.dumps(record))
    out.write(json.dumps(record) + "\n")
    out.flush()


def serve(cell, ns, out):
    from runners import serve as runner
    # whole runs, a process a seed, as run.py makes them, with the control
    # read beside the program: these count as runs of a set
    for seed in ns.seeds:
        pieces = runner.run(cell, seed, ns.seconds, False, control=True)
        extra = pieces[6]
        series = extra["series"]
        dump(f"series_{cell['name']}_{seed}_{int(time.time())}.json",
             {"seed": seed, "seconds": ns.seconds,
              "ttft_s": series["ttft_s"],
              "req_latency_s": series["req_latency_s"],
              "gap_pcts": {str(q): common.percentile(series["gap_s"], q)
                           for q in (50, 75, 90, 95, 99)}})
        emit(out, {"kind": "run", "seed": seed, "correct": pieces[0],
                   "attempted": pieces[1], "failed": pieces[2],
                   "program": extra["program"],
                   "control": extra["control"], "also": extra["also"],
                   "metrics": {k: v[0] for k, v in pieces[3].items()},
                   "memory_peak_bytes": pieces[4]["memory_peak_bytes"]})
    if not ns.rates:
        return
    # the rate sweep: one process, one set-up, a window a rate
    child, port, cfg_path = runner.start(cell, 1)
    try:
        ready = runner.wait_ready(cell, child, "tpu")
        vocab = cell["config"]["vocab_size"]
        for rate in ns.rates:
            reqs = loadgen.build_requests(cell["traffic"], 1, vocab,
                                          ns.sweep_seconds, rate)
            pieces = runner.drive(cell, child, ready, port, reqs, 1,
                                  ns.sweep_seconds, False, "tpu",
                                  time.monotonic(), check=False, free=False)
            series = pieces[6]["series"]
            by_due = series["ttft_s"]
            if not by_due:
                continue
            half = len(by_due) // 2
            emit(out, {"kind": "sweep", "rate": rate,
                       "attempted": pieces[1], "failed": pieces[2],
                       "waiting_at_close": pieces[6]["waiting_at_close"],
                       "ttft_p50_ms": 1e3 * common.median(by_due),
                       "ttft_p95_ms": 1e3 * common.percentile(by_due, 95),
                       "ttft_first_half_p50_ms":
                           1e3 * common.median(by_due[:half] or [0]),
                       "ttft_second_half_p50_ms":
                           1e3 * common.median(by_due[half:] or [0]),
                       "gap_p95_ms":
                           1e3 * (common.percentile(series["gap_s"], 95) or 0),
                       "tokens_per_s":
                           series["out_tokens"][0] / ns.sweep_seconds})
            time.sleep(3)
        emit(out, {"kind": "status", **child.ask(cmd="status")})
    finally:
        child.stop()
        os.remove(cfg_path)


def train(cell, ns, out):
    from runners import train as runner
    for seed in ns.seeds:
        pieces = runner.run(cell, seed, ns.seconds, False, control=True)
        extra = pieces[6]
        dump(f"raw_{cell['name']}_{seed}_{int(time.time())}.json",
             {"seed": seed, **extra["raw"]})
        emit(out, {"kind": "run", "seed": seed, "correct": pieces[0],
                   "program": extra["program"], "control": extra["control"],
                   "metrics": {k: v[0] for k, v in pieces[3].items()},
                   "memory_peak_bytes": pieces[4]["memory_peak_bytes"]})


def main():
    ints = lambda s: [int(x) for x in s.split(",") if x]         # noqa: E731
    floats = lambda s: [float(x) for x in s.split(",") if x]     # noqa: E731
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--rates", type=floats, default=[])
    ap.add_argument("--sweep-seconds", type=float, default=20)
    ns = ap.parse_args()
    cell = common.resolve_cell(ns.workload)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate_{ns.workload}.jsonl"),
              "a") as out:
        {"serve": serve, "train": train}[cell["config"]["kind"]](cell, ns, out)


if __name__ == "__main__":
    main()
