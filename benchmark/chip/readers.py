"""The stock reductions from what a run collected to one metric, end-to-end
or per-layer alike.

A metric is ``metrics/<name>.json``: ``{"source_kind": ..., ...}``; the reader
named by ``source_kind`` gets that file's dict and the run's ``context`` and
returns a number, or None where it finds nothing to read (the metric is then
left out of the line).  A metric whose reduction is none of these brings
``metrics/<name>.py`` with ``read(spec, context)``.

``context``: ``series`` (benchmark-clock samples by name), ``window_s`` (the
measured window's length, for a rate), ``served`` (what the child read of the
program it built), ``snap0``/``snap1`` (the program's endpoints at the
window's ends: ``programs``, ``metrics``, ``models``), ``samples``
(``/v1/models`` each second), ``trace`` (the reduction of ``trace_reduce``),
``counters``, ``config``, ``peaks``.
"""
import importlib.util
import os

from common import HERE, load_json, median, percentile


def _reduce(values, how, window_s=None):
    if not values:
        return None
    if how == "per_second":       # a rate over all the work of the window
        return sum(values) / window_s
    if how in ("p90", "p95", "p99"):
        return percentile(values, int(how[1:]))
    if how == "p50":
        return median(values)
    if how == "max":
        return max(values)
    if how == "mean":
        return sum(values) / len(values)
    raise ValueError(f"no such reduction: {how!r}")


def bench_clock(spec, ctx):
    values = ctx.get("series", {}).get(spec["series"])
    out = _reduce(values, spec["reduce"], ctx.get("window_s"))
    return None if out is None else out * spec.get("scale", 1.0)


def _program_rows(snap, model):
    eng = (snap or {}).get("programs", {}).get("engines", {}).get(model, {})
    return {site.rsplit(":", 1)[1]: row
            for site, row in eng.get("programs", {}).items()}


def programs(spec, ctx):
    """A share over the dispatch ledger's window delta: ``field`` summed over
    the ``numerator`` programs / over the ``denominator`` programs ("*": all
    of the model's)."""
    model = ctx["config"]["deployment"]["model_name"]
    r0, r1 = (_program_rows(ctx.get(k), model) for k in ("snap0", "snap1"))
    if not r1:
        return None

    def total(names):
        names = list(r1) if names == "*" else names
        return sum(r1.get(n, {}).get(spec["field"], 0)
                   - r0.get(n, {}).get(spec["field"], 0) for n in names)
    den = total(spec["denominator"])
    if den <= 0:
        return None
    return 100.0 * total(spec["numerator"]) / den


def models_stats(spec, ctx):
    model = ctx["config"]["deployment"]["model_name"]
    values = []
    for s in ctx.get("samples", []):
        row = s.get("models", {}).get(model, {})
        if spec["numerator"] in row and row.get(spec["denominator"]):
            values.append(100.0 * row[spec["numerator"]]
                          / row[spec["denominator"]])
    return _reduce(values, spec["reduce"])


def trace_idle(spec, ctx):
    return (ctx.get("trace") or {}).get("idle_pct")


def counter(spec, ctx):
    return ctx.get("counters", {}).get(spec["counter"])


def _custom(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


STOCK = {"bench_clock": bench_clock, "programs": programs, "models_stats": models_stats,
         "trace_idle": trace_idle, "counter": counter}


def read_all(metric_entries, ctx):
    """``{name: (value, unit)}`` for every metric of the list whose reader
    found something."""
    out = {}
    for entry in metric_entries:
        name = entry["name"]
        spec = load_json("metrics", name + ".json")
        read = STOCK.get(spec["source_kind"]) or _custom(name)
        value = read(spec, ctx)
        if value is not None:
            out[name] = (float(value), entry["unit"])
    return out
