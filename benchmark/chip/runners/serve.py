"""The runner of a served configuration.  This process never imports jax: it
starts `serve_child.py`, which holds the chip, and is the HTTP/SSE client of
the program that child serves.

A run: build the requests from the mix and the seed while the child compiles
or loads; send through a ramp (set-up) and then the measured window; read the
program's own endpoints at the window's ends (traced run only); afterwards
hand a seeded sample of finished requests to the child, which runs the plain
reference over them with the program's state freed.
"""
import http.client
import json
import os
import queue
import random
import shutil
import socket
import subprocess
import sys
import threading
import time

import checks
import common
import loadgen
import readers
from common import log

CHILD = os.path.join(common.HERE, "runners", "serve_child.py")
READY_SECONDS = 1150          # a first run compiles
REPLY_SECONDS = 300


class ChildProcess:
    """The child and its two pipes.  One helper thread moves its stdout lines
    into a queue; anything that is not READY/REPLY is echoed."""

    def __init__(self, cfg_path, seed, port, platform, env=None):
        full_env = dict(os.environ)
        full_env.update(env or {})
        self.proc = subprocess.Popen(
            [sys.executable, CHILD, "--config", cfg_path, "--seed",
             str(seed), "--port", str(port), "--platform", platform],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=full_env, cwd=common.ROOT)
        self.lines = queue.Queue()
        self.reader = threading.Thread(target=self._pump, daemon=True)
        self.reader.start()

    def _pump(self):
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            if line.startswith(("READY ", "REPLY ")):
                self.lines.put(line)
            else:
                print(line, flush=True)
        self.lines.put(None)

    def wait_line(self, prefix, timeout):
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=max(0.1, deadline
                                                  - time.monotonic()))
            except queue.Empty:
                raise SystemExit(f"bench: the child sent no {prefix.strip()} "
                                 f"in {timeout} s")
            if line is None:
                raise SystemExit("bench: the child ended (exit "
                                 f"{self.proc.wait()}) before {prefix.strip()}")
            if line.startswith(prefix):
                return json.loads(line[len(prefix):])

    def ask(self, timeout=REPLY_SECONDS, **cmd):
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()
        out = self.wait_line("REPLY ", timeout)
        if "error" in out:
            raise SystemExit(f"bench: the child refused {cmd['cmd']}: {out}")
        return out

    def stop(self):
        """End the child and wait for it; kill it if it will not go."""
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "exit"}\n')
                self.proc.stdin.flush()
                self.proc.stdin.close()
            except (OSError, ValueError):
                pass
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self.reader.join(5)


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise RuntimeError(f"GET {path} -> {resp.status}: {body[:200]!r}")
        return json.loads(body)
    finally:
        conn.close()


def snapshot(port):
    return {"programs": get_json(port, "/programs"),
            "metrics": get_json(port, "/metrics.json"),
            "models": get_json(port, "/v1/models")}


def capture_profile(port, seconds, out):
    """POST /debug/profile; blocks for the capture (run in a thread)."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=seconds + 120)
    try:
        conn.request("POST", f"/debug/profile?seconds={seconds}")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        out["status"], out["body"] = resp.status, body
    except (OSError, ValueError) as e:
        out["status"], out["body"] = 0, {"error": str(e)}
    finally:
        conn.close()


def window_series(streams, w0, w1, loop):
    """The benchmark-clock samples of the window, from the streams, read
    after the drain.  Open loop: every request due in the window is attempted
    and owes its whole answer.  Closed loop: every request in flight during
    the window is attempted; one still running when the window closed owes
    nothing yet."""
    series = {"ttft_s": [], "gap_s": [], "gen_late_s": [],
              "req_latency_s": []}
    tokens_in = attempted = failed = 0
    for st in streams:
        if loop == "open":
            counted = w0 <= st.due < w1
            bad = st.failed or not st.done
        else:
            counted = (st.sent is not None and st.sent < w1
                       and (not st.times or st.times[-1] >= w0
                            or not st.done))
            bad = st.failed
        if counted:
            attempted += 1
            failed += bool(bad)
        if loop == "open" and counted:
            series["gen_late_s"].append(st.sent - st.due)
            if st.times:
                series["ttft_s"].append(st.times[0] - st.due)
            if st.done:
                series["req_latency_s"].append(st.times[-1] - st.due)
        prev = None
        for t in st.times:
            if w0 <= t < w1:
                tokens_in += 1
                if prev is not None:
                    series["gap_s"].append(t - prev)
            prev = t
    series["out_tokens"] = [tokens_in]
    return series, attempted, failed


def pick_sample(streams, w1, seed, n):
    """A seeded sample of the requests finished in the window, the longest
    among them always in it."""
    done = [st for st in streams if st.done and st.times
            and st.times[-1] < w1]
    if not done:
        done = [st for st in streams if st.done and st.times]
    if not done:
        return []
    done.sort(key=lambda st: (len(st.req["tokens"]) + len(st.tokens)))
    longest = done.pop()
    random.Random(int(seed)).shuffle(done)
    return [longest] + done[:max(0, n - 1)]


def start(cell, seed, platform="tpu"):
    """Start the child that builds and serves the cell's configuration.
    Returns ``(child, port, cfg_path)``; the caller stops the child."""
    os.makedirs(common.WORK, exist_ok=True)
    cfg_path = os.path.join(common.WORK, f"config_{os.getpid()}.json")
    with open(cfg_path, "w") as f:
        json.dump(cell["config"], f)
    profile_dir = os.path.join(common.WORK, "profile")
    shutil.rmtree(profile_dir, ignore_errors=True)
    port = free_port()
    env = {"MXNET_PROFILE_DIR": profile_dir}
    return ChildProcess(cfg_path, seed, port, platform, env), port, cfg_path


def wait_ready(cell, child, platform):
    ready = child.wait_line("READY ", READY_SECONDS)
    device = ready["device"]
    log(f"child ready: device {device}, built in "
        f"{ready['build_seconds']} s, compile requests "
        f"{ready['compile_requests']}, cache hits {ready['cache_hits']}, "
        f"compiled {ready['compiled']}, cache at {ready['cache_dir']}")
    if device["count"] < cell["chips"]:
        raise SystemExit(f"bench: the cell asks for {cell['chips']} "
                         f"chip(s), jax sees {device['count']}")
    return ready


def run(cell, seed, seconds, trace, platform="tpu", t_start=None,
        control=False):
    """One run of a serve cell.  Returns the pieces of the result line:
    ``(correct, attempted, failed, metrics, device, breakdown, extra)``."""
    t_start = time.monotonic() if t_start is None else t_start
    child, port, cfg_path = start(cell, seed, platform)
    try:
        requests = loadgen.build_requests(
            cell["traffic"], seed, cell["config"]["vocab_size"], seconds)
        ready = wait_ready(cell, child, platform)
        return drive(cell, child, ready, port, requests, seed, seconds, trace,
                     platform, t_start, control, free=True)
    finally:
        child.stop()
        try:
            os.remove(cfg_path)
        except OSError:
            pass


def drive(cell, child, ready, port, requests, seed, seconds, trace, platform,
          t_start, control=False, free=True, check=True):
    """Ramp, window, drain and check against a ready child.  ``free=False``
    (tools only) leaves the server up for another drive."""
    cfg, traffic = cell["config"], cell["traffic"]
    dep = cfg["deployment"]
    device = ready["device"]
    client = None
    try:
        peaks = common.peaks_for(device["kind"]) if platform == "tpu" else {}

        client = loadgen.LoadClient(port, dep["model_name"])
        ramp = float(traffic.get("ramp_seconds", 0))
        t0 = time.monotonic()
        w0, w1 = t0 + ramp, t0 + ramp + seconds
        state = {"snap0": None, "snap1": None, "samples": [],
                 "next_sample": w0, "profile": None, "prof_out": {},
                 "status0": None}
        prof_seconds = max(1.0, min(3.0, seconds / 3.0))

        def on_tick(now):
            if now >= w0 and state["status0"] is None:
                state["status0"] = child.ask(cmd="status")
                if trace:
                    state["snap0"] = snapshot(port)
            if trace and w0 <= now < w1:
                if now >= state["next_sample"]:
                    state["samples"].append(get_json(port, "/v1/models"))
                    state["next_sample"] += 1.0
                if state["profile"] is None and now >= w0 + min(
                        1.0, seconds / 4.0):
                    state["profile"] = threading.Thread(
                        target=capture_profile,
                        args=(port, prof_seconds, state["prof_out"]))
                    state["profile"].start()

        if traffic["loop"] == "open":
            loadgen.run_open(client, requests, t0, on_tick)
            while time.monotonic() < w1:
                client.poll(0.05)
                on_tick(time.monotonic())
        else:
            loadgen.run_closed(client, requests, w1, on_tick)
        status1 = child.ask(cmd="status")
        if trace:
            state["snap1"] = snapshot(port)
        # after the window: follow every request that was due in it to its
        # end, so each has a first-token time and a whole-answer time
        owed = [st for st in client.streams if w0 <= st.due < w1] \
            if traffic["loop"] == "open" else []
        t_drain = time.monotonic() + float(traffic.get("drain_seconds", 0))
        while time.monotonic() < t_drain and any(
                not st.done and not st.failed for st in owed):
            client.poll(0.05)
        client.abandon()
        if state["profile"] is not None:
            state["profile"].join(prof_seconds + 150)

        series, attempted, failed = window_series(
            client.streams, w0, w1, traffic["loop"])
        series["setup_s"] = [w0 - t_start]
        compiled_in_window = status1["compile_requests"] \
            - (state["status0"] or ready)["compile_requests"]
        log(f"window {seconds} s: attempted {attempted}, failed {failed}, "
            f"tokens received {series['out_tokens'][0]}; samples: ttft "
            f"{len(series['ttft_s'])}, whole answers "
            f"{len(series['req_latency_s'])}, gaps {len(series['gap_s'])}; "
            f"compile requests inside the window {compiled_in_window}")
        for st in client.streams:
            if st.failed:
                log(f"failed request: {st.error}")
                break
        log("ttft_p50_ms {}, ttft_p95_ms {}, gap_p50_ms {}".format(
            *(None if x is None else 1e3 * x for x in (
                common.median(series["ttft_s"]),
                common.percentile(series["ttft_s"], 95),
                common.median(series["gap_s"])))))

        # correctness, outside the window, with the program's state freed:
        # what the child read of the program it built against what the
        # configuration states, then the served tokens against the reference
        served = ready["served"]
        as_stated = checks.judge_stated(served, dep, "serve")
        n_sample = int(cfg["check"]["sample_requests"])
        sample = pick_sample(client.streams, w1, seed, n_sample)
        if sample and check:
            result = child.ask(cmd="check", control=control, free=free,
                               samples=[{"tokens": st.req["tokens"],
                                         "served": st.tokens}
                                        for st in sample])
        else:
            result = {"program": {"gap_max": None, "gap_mean": None},
                      "memory_peak_bytes": status1["memory_peak_bytes"]}
        log(f"check: {len(sample)} requests, {result['program']}, reference "
            f"took {result.get('check_seconds')} s")
        numbers = {k: result["program"][k] for k in ("gap_max", "gap_mean")}
        correct = checks.judge(numbers, cfg["check"]["limits"], "serve") \
            and as_stated and bool(sample) and compiled_in_window == 0
        if control and "control" in result:
            log(f"control ({cfg['check']['control_precision']}): "
                f"{result['control']}")
        device = dict(device,
                      memory_peak_bytes=result["memory_peak_bytes"])

        ctx = {"series": series, "window_s": float(seconds), "config": cfg,
               "peaks": peaks, "served": served,
               "counters": {"programs_compiled": status1["compiled"]}}
        breakdown = None
        if trace:
            breakdown = _traced(cfg, state, child, ctx, device)
        metrics = readers.read_all(
            cell["per_layer" if trace else "end_to_end"], ctx)
        for note in ctx.get("notes", []):
            log(note)
        waiting = sum(1 for st in client.streams
                      if st.due < w1 and not st.failed
                      and (not st.times or st.times[0] >= w1))
        extra = {"control": result.get("control"),
                 "also": result.get("also"),
                 "program": result.get("program"), "series": series,
                 "waiting_at_close": waiting}
        return correct, attempted, failed, metrics, device, breakdown, extra
    finally:
        if client is not None:
            client.close()


def _traced(cfg, state, child, ctx, device):
    """Adds what a traced run collected to the readers' context and the
    trace's busy time to the device record; returns the breakdown."""
    prof = state["prof_out"]
    reduction = None
    if prof.get("status") == 200:
        reduction = child.ask(cmd="trace", dir=prof["body"]["profile"],
                              spec=cfg.get("trace", {}))
        for line in reduction.pop("summary", []):
            log("trace: " + line)
        if "missing" in reduction:
            log(f"trace: {reduction}")
            reduction = None
    else:
        log(f"no profile: {prof}")
    ctx.update(snap0=state["snap0"], snap1=state["snap1"],
               samples=state["samples"], trace=reduction)
    if not reduction:
        return None
    device["busy_s"] = reduction["busy_s"]
    device["window_s"] = reduction["window_s"]
    for name, row in sorted(reduction["programs"].items()):
        log(f"trace program {name}: {row['count']:.0f} x, "
            f"{row['seconds']:.4f} s")
    return reduction["breakdown"]
