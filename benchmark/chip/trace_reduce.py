"""From a ``jax.profiler`` trace to numbers: device busy and idle time, time per
compiled program, the costliest device operations and the longest idle gaps.

``load_planes`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
nothing else; ``reduce_planes`` is plain arithmetic on
``{plane: {line: [(name, start_ns, duration_ns), ...]}}`` and is what the
tests check on a small recorded trace.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_SUFFIX = re.compile(r"\(\d+\)$")
#: gaps shorter than this are summed under one label instead of each named
SHORT_GAP_NS = 20_000


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load_planes(path):
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events)
    return planes


def summarize(planes, top=6):
    """What is in a trace, for a human: every plane and line with its event
    count and its most frequent names."""
    out = []
    for pname, lines in planes.items():
        for lname, events in lines.items():
            counts = {}
            for name, _, _ in events:
                counts[name] = counts.get(name, 0) + 1
            common = sorted(counts.items(), key=lambda kv: -kv[1])[:top]
            out.append(f"{pname} | {lname}: {len(events)} events; " + ", ".join(
                f"{n[:60]} x{c}" for n, c in common))
    return out


def program_name(event_name):
    """``jit__decode_paged_pure(1234)`` -> ``jit__decode_paged_pure``."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name):
    """An XLA Ops event is named by its whole HLO line; keep the
    instruction's name, its kind and its output shape:
    ``%fusion.28 = f32[2304,16,16,64]{...} fusion(...)`` ->
    ``%fusion.28 fusion f32[2304,16,16,64]``."""
    lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return event_name[:100]
    if rhs.startswith("("):             # a tuple of shapes
        shape, rest = "tuple", rhs.partition(") ")[2]
    else:
        shape, _, rest = rhs.partition(" ")
        shape = shape.split("{", 1)[0]
    return f"{lhs} {rest.split('(', 1)[0]} {shape}"[:100]


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _label_at(spans, t):
    """The innermost of the host spans that cover time ``t``."""
    best = None
    for name, start, dur in spans:
        if start <= t < start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else None


def reduce_planes(planes, spec=None):
    """``spec``: ``{"device_plane": regex, "host_spans": [name prefixes]}``,
    both optional.  Device planes default to ``/device:TPU:<n>``."""
    spec = spec or {}
    dev_re = re.compile(spec["device_plane"]) if spec.get("device_plane") \
        else DEVICE_PLANE
    devices = {p: l for p, l in planes.items() if dev_re.match(p)}
    if not devices:
        return {"missing": "no device plane in the trace",
                "planes": sorted(planes)}
    # the traced window: from the first to the last event of the device
    # planes.  The host's planes also hold the profiler's own start and stop,
    # which can take a second in which the device records nothing.
    lo = min(s for lines in devices.values() for evs in lines.values()
             for _, s, _ in evs)
    hi = max(s + d for lines in devices.values() for evs in lines.values()
             for _, s, d in evs)
    prefixes = tuple(spec.get("host_spans", ()))
    host_spans = [ev for p, lines in planes.items() if p not in devices
                  for evs in lines.values() for ev in evs
                  if prefixes and ev[0].startswith(prefixes)]

    busy, op_time, programs, gaps = [], {}, {}, {}
    for lines in devices.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        mod_starts = [m[1] for m in modules]
        merged = _union((s, s + d) for _, s, d in ops)
        busy.append(sum(e - s for s, e in merged))
        for name, _, d in ops:
            name = op_name(name)
            op_time[name] = op_time.get(name, 0) + d
        for name, _, d in modules:
            row = programs.setdefault(program_name(name), [0, 0])
            row[0] += 1
            row[1] += d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for k in range(0, len(edges), 2):
            start, end = edges[k], edges[k + 1]
            if end <= start:
                continue
            if end - start < SHORT_GAP_NS:
                label = "short gaps between device operations"
            else:
                label = _label_at(host_spans, (start + end) // 2)
            if label is None:
                # no host span on the profiler's clock: name the device
                # programs on either side of the gap
                i = bisect.bisect_right(mod_starts, start)
                j = bisect.bisect_left(mod_starts, end - 1)
                if i and modules[i - 1][1] + modules[i - 1][2] >= end:
                    label = "inside " + program_name(modules[i - 1][0])
                else:
                    label = "after {} before {}".format(
                        program_name(modules[i - 1][0]) if i
                        else "trace start",
                        program_name(modules[j][0]) if j < len(modules)
                        else "trace end")
            gaps[label] = gaps.get(label, 0) + (end - start)

    n = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / n / 1e9

    def top(table):
        return [[k, v / n / 1e9] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": window_s, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s) if window_s else None,
        "programs": {k: {"count": v[0] / n, "seconds": v[1] / n / 1e9}
                     for k, v in programs.items()},
        "breakdown": {"device_ops": top(op_time), "idle_gaps": top(gaps)},
    }


def reduce_dir(directory, spec=None):
    planes = load_planes(find_xplane(directory))
    out = reduce_planes(planes, spec)
    out["summary"] = summarize(planes)
    return out
