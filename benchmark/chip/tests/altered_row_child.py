"""`runners/serve_child.py` with a state row altered underneath: after every
join the slot's row of every state leaf is turned round and scaled (what a
row written to the wrong place, or a snapshot restored over a live slot,
would be).  The
tokens still come out of the timed path, so only the comparison with the
reference can tell.  Only `test_granite4_cell.py` starts this, in the child's
place."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "runners"))

import serve_child  # noqa: E402


class AlteredRowChild(serve_child.Child):
    def __init__(self, ns):
        super().__init__(ns)
        eng = self.engine
        prefill = eng.prefill

        def join(tokens, slot, *a, **k):
            first = prefill(tokens, slot, *a, **k)
            eng._recur = tuple(leaf.at[int(slot)].multiply(-4.0)
                               for leaf in eng._recur)
            return first

        eng.prefill = join


if __name__ == "__main__":
    serve_child.Child = AlteredRowChild
    serve_child.main()
    sys.stdout.flush()
    os._exit(0)
