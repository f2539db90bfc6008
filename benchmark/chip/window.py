"""Window deltas of the program's own counters and histograms, for the
metrics that read them: ``ctx["snap0"|"snap1"]["metrics"]`` is the served
program's ``/metrics.json`` (``registry.export_state()``) at the window's
ends.  A counter's ``values`` are keyed by label strings
(``"model=m,phase=emit"``), which are parsed here, not spelled out by the
readers.  Everything returns None where the program has no such series (a
program older than the counter) or the window holds nothing."""

#: phases of the serving worker's loop in which it is not idle
#: (`mxtpu_serve_loop_seconds{phase}`; `wait` is the idle one)
HOST_PHASES = ("admit", "prefill_host", "operands", "emit")


def _labels(key):
    return dict(part.split("=", 1) for part in key.split(",") if "=" in part)


def _model(ctx):
    return ctx["config"]["deployment"]["model_name"]


def _state(ctx, snap, kind, name):
    return ((ctx.get(snap) or {}).get("metrics") or {}).get(kind, {}) \
        .get(name)


def counter_by(ctx, name, label):
    """``{value of label: delta over the window}`` over the counter's label
    sets of the served model that carry ``label``; None if there is none."""
    after = _state(ctx, "snap1", "counters", name)
    if after is None:
        return None
    before = (_state(ctx, "snap0", "counters", name) or {}).get("values", {})
    out = {}
    for key, value in after.get("values", {}).items():
        labels = _labels(key)
        if labels.get("model") != _model(ctx) or label not in labels:
            continue
        out[labels[label]] = out.get(labels[label], 0.0) \
            + value - before.get(key, 0.0)
    return out or None


def loop_share(ctx, phases):
    """Share (%) of the worker loop's busy time, all phases but ``wait``,
    that the window spent in ``phases``."""
    seconds = counter_by(ctx, "mxtpu_serve_loop_seconds", "phase")
    if not seconds:
        return None
    busy = sum(v for phase, v in seconds.items() if phase != "wait")
    if busy <= 0:
        return None
    return 100.0 * sum(seconds.get(p, 0.0) for p in phases) / busy


def histogram_window(ctx, name):
    """``(count, sum, samples)`` of the observations made inside the window.
    The reservoir is a deque in arrival order, so the window's samples are
    the last ``count`` of the later snapshot; a window with more
    observations than the reservoir holds gives None."""
    after = _state(ctx, "snap1", "histograms", name)
    if after is None:
        return None
    before = _state(ctx, "snap0", "histograms", name) or {}
    count = int(after.get("count", 0)) - int(before.get("count", 0))
    samples = after.get("samples") or []
    if count <= 0 or count > len(samples):
        return None
    total = float(after.get("sum", 0.0)) - float(before.get("sum", 0.0))
    return count, total, samples[-count:]
