"""The process that holds the chip in a serve cell: builds the configuration's
program from the seed, serves it over HTTP, and afterwards runs the plain
reference over what was served.  The parent (`runners/serve.py`) never imports
jax; it talks to this process over HTTP (the program's own endpoints) and over
stdin/stdout (one JSON command a line in, one ``REPLY {...}`` line out):

    {"cmd": "status"}                      compile counts, memory peak
    {"cmd": "check", "samples": [...], "control": bool, "free": bool}
    {"cmd": "trace", "dir": path, "spec": {...}}     reduce a captured profile
    {"cmd": "exit"}
"""
import argparse
import gc
import importlib
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

import common  # noqa: E402


def reply(obj):
    print("REPLY " + json.dumps(obj), flush=True)


class Child:
    def __init__(self, ns):
        with open(ns.config) as f:
            self.cfg = json.load(f)
        self.counter = common.CompileCounter()
        self.device = common.device_record(ns.platform)
        self.ref = importlib.import_module("reference." + self.cfg["reference"])
        self.prog = importlib.import_module("programs." + self.cfg["program"])
        self.seed = ns.seed
        t0 = time.time()
        self.net = self.prog.build_net(self.cfg)
        t1 = time.time()
        self.prog.load_weights(self.net,
                               self.ref.init_params(self.cfg, self.seed))
        t2 = time.time()
        self.srv, self.engine = self.prog.build_server(self.cfg, self.net,
                                                       ns.port)
        common.log(f"child set-up: net {t1 - t0:.1f} s, weights from the seed "
                   f"{t2 - t1:.1f} s, engine + warm-up + listen "
                   f"{time.time() - t2:.1f} s")
        self.served = self.prog.served_state(self.net, self.engine)
        self._gap_fns = {}

    def status(self):
        return {"compile_requests": self.counter.requests,
                "cache_hits": self.counter.hits,
                "compiled": self.counter.compiled,
                "cache_dir": self.counter.cache_dir,
                "memory_peak_bytes": common.memory_peak_bytes()}

    def free(self):
        """Stop serving and let go of the program's state, so the reference
        runs beside nothing."""
        if self.srv is not None:
            self.prog.stop_server(self.srv)
        self.srv = self.engine = self.net = None
        gc.collect()

    def check(self, samples, control, free):
        """The reference's full forward over each prompt with its served
        tokens (`refcheck.serve_numbers`).  With ``control``, also the same
        reading for the token the next precision below would have served at
        each position."""
        import refcheck
        out = {"memory_peak_bytes": common.memory_peak_bytes()}
        t0 = time.time()
        if free:
            self.free()
        low = self.cfg["check"]["control_precision"]
        also = list(self.cfg["check"].get("report_precisions", []))
        numbers = refcheck.serve_numbers(
            self.ref, self.cfg, self.seed, samples,
            ["float32"] + ([low] + also if control else []), self._gap_fns)
        out["program"] = numbers["float32"]
        if control:
            out["control"] = numbers[low]
            out["also"] = {p: numbers[p] for p in also}
        out["check_seconds"] = round(time.time() - t0, 3)
        return out

    def trace(self, directory, spec):
        import trace_reduce
        return trace_reduce.reduce_dir(directory, spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--platform", default="tpu")
    ns = ap.parse_args()
    t0 = time.time()
    child = Child(ns)
    print("READY " + json.dumps({
        "device": child.device, "build_seconds": round(time.time() - t0, 3),
        "served": child.served, **child.status()}), flush=True)
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        kind = cmd["cmd"]
        if kind == "exit":
            break
        if kind == "status":
            reply(child.status())
        elif kind == "check":
            reply(child.check(cmd["samples"], cmd.get("control", False),
                              cmd.get("free", True)))
        elif kind == "trace":
            reply(child.trace(cmd["dir"], cmd.get("spec", {})))
        else:
            reply({"error": f"no such command: {kind!r}"})
    child.free()


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    os._exit(0)      # daemon threads of the program must not hold the exit
