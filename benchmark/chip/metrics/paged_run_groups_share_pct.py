"""Share (%) of the grouped paged kernel's 128-key groups whose blocks the
table names in a row — fetched in one copy, not eight.  None where the
program has no such counter (a program older than it, a model whose paged
calls take another kernel) or the window held no decode step."""
import decode_counters
import window


def read(spec, ctx):
    groups = decode_counters._delta(ctx, "mxtpu_paged_groups_total")
    by_fetch = window.counter_by(ctx, "mxtpu_paged_groups_total", "fetch")
    if not groups or not by_fetch:
        return None
    return 100.0 * by_fetch.get("run", 0.0) / groups
