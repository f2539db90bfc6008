"""Share (%) of the state rows a decode step could have visited that the
one-token step's work list of live rows left out: a slot that was not live
(free, or ended inside the burst) a step of a dispatch.  None where the
program has no such counter (a program older than the list, a model without
state-space layers) or the window held no decode step."""
import decode_counters


def read(spec, ctx):
    skipped = decode_counters._delta(ctx, "mxtpu_ssm_step_rows_skipped_total")
    updated = decode_counters._delta(ctx, "mxtpu_ssm_step_rows_total")
    if skipped is None or updated is None or skipped + updated <= 0:
        return None
    return 100.0 * skipped / (skipped + updated)
