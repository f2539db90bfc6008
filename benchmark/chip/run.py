#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, warms up, measures for --seconds, checks what the
timed path produced against the plain reference outside the window, prints one
result line (the last line of stdout) and exits.  It has no CPU mode: unless
jax finds a TPU with the chips the cell asks for, it exits non-zero and prints
no result.  Everything that belongs to one configuration, one traffic mix or
one metric is a file found by name from BENCHMARK.json; nothing here names one.
"""
import time
T_START = time.monotonic()       # as near to process start as Python allows

import argparse                  # noqa: E402
import importlib                 # noqa: E402
import os                        # noqa: E402
import sys                       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
# the child of a serve cell imports the program from the checkout too
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

import common                    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args()
    cell = common.resolve_cell(ns.workload)
    runner = importlib.import_module("runners." + cell["config"]["kind"])
    correct, attempted, failed, metrics, device, breakdown, _ = runner.run(
        cell, ns.seed, ns.seconds, bool(ns.trace), platform="tpu",
        t_start=T_START)
    for name, (value, unit) in metrics.items():
        common.log(f"metric {name} = {value!r} {unit}")
    if not ns.trace:
        missing = [m["name"] for m in cell["end_to_end"]
                   if m["name"] not in metrics]
        if missing:
            raise SystemExit(f"bench: no samples for {missing}: no result")
    common.print_result(correct, attempted, failed, metrics, device,
                        breakdown)


if __name__ == "__main__":
    main()
