"""`runners/serve_child.py` with the recurrent state broken underneath: a
prefix hit starts from the null row's garbage instead of its snapshot (what a
lost or stale snapshot would be).  The tokens still come out of the timed
path, so only the comparison with the reference can tell.  Only
`test_qwen3next_cell.py` starts this, in the child's place."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "runners"))

import serve_child  # noqa: E402


class BrokenStateChild(serve_child.Child):
    def __init__(self, ns):
        super().__init__(ns)
        eng = self.engine
        prefill_at = eng._prefill_at

        def wrong_row(plan, bucket, n_valid, slot, ctx=None):
            at = prefill_at(plan, bucket, n_valid, slot, ctx)
            if ctx is not None:     # a hit: [n_valid, slot, ctx, row, ...]
                at[3] = eng._null_row
            return at

        eng._prefill_at = wrong_row


if __name__ == "__main__":
    serve_child.Child = BrokenStateChild
    serve_child.main()
    sys.stdout.flush()
    os._exit(0)
