"""`runners/serve_child.py` with the timed path broken underneath: the first
token of every request is altered where the engine produces it.  Only
`test_rehearsal.py` starts this, in the child's place."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "runners"))

import serve_child  # noqa: E402


class AlteredChild(serve_child.Child):
    def __init__(self, ns):
        super().__init__(ns)
        prefill, vocab = self.engine.prefill, self.cfg["vocab_size"]
        self.engine.prefill = lambda *a, **k: (prefill(*a, **k) + 1) % vocab


if __name__ == "__main__":
    serve_child.Child = AlteredChild
    serve_child.main()
    sys.stdout.flush()
    os._exit(0)
