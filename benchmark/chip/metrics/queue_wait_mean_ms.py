"""Mean wait in the batcher's queue of the requests admitted in the window."""
import window


def read(spec, ctx):
    got = window.histogram_window(ctx, "mxtpu_serve_queue_wait_seconds")
    return None if got is None else 1e3 * got[1] / got[0]
