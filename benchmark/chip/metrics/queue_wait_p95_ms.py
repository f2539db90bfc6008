"""95th percentile of the wait in the batcher's queue, over the window."""
import window
from common import percentile


def read(spec, ctx):
    got = window.histogram_window(ctx, "mxtpu_serve_queue_wait_seconds")
    return None if got is None else 1e3 * percentile(got[2], 95)
