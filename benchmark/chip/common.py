"""What every part of the harness shares: where things are, the manifest,
percentiles, the compile counter and the result line.  Imports no jax at
module level: the serve runner's parent must never touch the chip."""
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: scratch of a run (profiles, the child's files); git-ignored, inside the checkout
WORK = os.path.join(HERE, "_work")


def log(msg):
    print(f"bench: {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve_cell(name):
    """A workload's name -> its cell: the manifest entry, the configuration's
    file, the traffic file, and the metrics it reports, each found by name."""
    man = manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", cell["traffic"] + ".json")

    def reported(metric):
        return name in metric.get("workloads", cells)

    return {
        "name": name, "chips": cell["chips"], "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in man["end_to_end"] if reported(m)],
        "per_layer": [m for m in man["per_layer"] if reported(m)],
    }


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation; None if empty."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return statistics.median(values) if values else None


def peaks_for(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} in "
                         "peaks.json; a device that is not listed is an error")
    return table[device_kind]


class CompileCounter:
    """Compile requests against persistent-cache hits of this process, from
    jax's own monitoring events (the pattern of `chip_smoke.py`).  Applies the
    repo's compile-cache rule first: `JAX_COMPILATION_CACHE_DIR` if it is
    set, else the checkout's fixed `.jax_cache`."""

    def __init__(self):
        import jax.monitoring
        from incubator_mxnet_tpu.compile_cache import ensure_compile_cache
        self.cache_dir = ensure_compile_cache()
        # no size cap on the cache: the chip machine sets
        # JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB, BERT-large's three step
        # programs alone are 180 MB, and an LRU cache that is too small for
        # one cell evicts everything on every run, so no run is ever warm
        # (PERF.md, PR 23).  The directory stays where it was put.
        import jax
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self):
        return self.requests - self.hits


def device_record(require_platform):
    """What jax finds, as the result line reports it.  Any platform but the
    required one ends the process non-zero with no result."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != require_platform:
        raise SystemExit(
            f"bench: jax found platform {dev.platform!r} ({dev.device_kind}), "
            f"not {require_platform!r}: no accelerator, no result")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    """Peak bytes in use on the fullest device (0 where the backend does not
    report it, as the CPU of the rehearsal)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def print_result(correct, attempted, failed, metrics, device, breakdown=None):
    """The last line of stdout: one JSON object with the contract's keys."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v[0], "unit": v[1]}
                        for k, v in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
