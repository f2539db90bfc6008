"""Of the cached index keys the decode programs scored in the window, the
share (%) chosen and attended over.  None where the program has no such
counters (a program older than them, a model without an indexer)."""
import decode_counters


def read(spec, ctx):
    scored = decode_counters._delta(ctx, "mxtpu_index_keys_scored")
    chosen = decode_counters._delta(ctx, "mxtpu_index_keys_selected")
    if not scored or chosen is None:
        return None
    return 100.0 * chosen / scored
