"""mxtpu-lint tests: each checker proven on a fixture true-positive AND
a clean negative, the two suppression planes (inline pragma, committed
baseline) round-tripped, a zero-unsuppressed run over the real package,
and the serving regressions the linter caught in the wild (engine reset
under ``_cv``)."""
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from incubator_mxnet_tpu.analysis import (Baseline, run_checks)
from incubator_mxnet_tpu.analysis.core import line_text_lookup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, source, checks, name="mod.py", extra=None):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    for rel, text in (extra or {}).items():
        q = tmp_path / rel
        q.parent.mkdir(parents=True, exist_ok=True)
        q.write_text(textwrap.dedent(text))
    return run_checks([str(tmp_path)], checks=checks,
                      root=str(tmp_path))


# -- host-sync-in-hot-path --------------------------------------------------

def test_host_sync_flags_marked_roots_and_callees(tmp_path):
    found = _lint(tmp_path, """
        def _helper(x):
            return x.item()

        # mxtpu-lint: hot-path
        def hot(x):
            y = x.block_until_ready()
            return _helper(y)

        def cold(x):
            return x.item()          # fine: not reachable from a root
    """, ["host-sync-in-hot-path"])
    lines = sorted(f.line for f in found)
    assert lines == [3, 7]           # _helper's .item() and the block
    assert all(f.check == "host-sync-in-hot-path" for f in found)


def test_host_sync_flagged_inside_a_loop_step_block(tmp_path):
    """A ``with loop_step(...)`` block of the worker loop's clock hides
    nothing from the rule: its body is the hot path's body."""
    src = """
        import jax.numpy as jnp
        from incubator_mxnet_tpu.serving import metrics as _m

        # mxtpu-lint: hot-path
        def emit(toks, live):
            with _m.loop_step("fanout", "serve.emit.fanout"):
                for s in live:
                    tok = int(toks[s])
            with _m.loop_step("carry", "serve.carry"):
                n = len(live)
            return tok, n
    """
    found = _lint(tmp_path, src, ["host-sync-in-hot-path"])
    assert [f.line for f in found] == [9]


def test_host_sync_clean_negative(tmp_path):
    assert _lint(tmp_path, """
        # mxtpu-lint: hot-path
        def hot(x, cfg):
            n = int(cfg.batch)       # attribute arg: host config, fine
            return x + n
    """, ["host-sync-in-hot-path"]) == []


# -- donation-hazard --------------------------------------------------------

def test_donation_use_after_donate(tmp_path):
    found = _lint(tmp_path, """
        import jax

        _f = jax.jit(lambda c, x: (c, x), donate_argnums=(0,))

        def bad(c, x):
            y = _f(c, x)
            return c                 # c is dead: donated at position 0
    """, ["donation-hazard"])
    assert len(found) == 1
    assert "`c` used after being donated" in found[0].message


def test_donation_rebind_is_clean(tmp_path):
    assert _lint(tmp_path, """
        import jax

        _f = jax.jit(lambda c, x: (c, x), donate_argnums=(0,))

        def good(c, x):
            c, y = _f(c, x)          # sanctioned rebind
            return c, y
    """, ["donation-hazard"]) == []


# -- closed-program-set -----------------------------------------------------

def test_closed_program_raw_jit_flagged(tmp_path):
    found = _lint(tmp_path, """
        import jax

        def build(fn):
            return jax.jit(fn)       # unregistered program
    """, ["closed-program-set"])
    assert len(found) == 1
    assert "instrument_jit" in found[0].message


def test_closed_program_wrapped_and_build_then_wrap_clean(tmp_path):
    assert _lint(tmp_path, """
        import jax
        from incubator_mxnet_tpu import telemetry

        direct = telemetry.instrument_jit("site:a", jax.jit(abs))

        _raw = jax.jit(abs)
        wrapped = telemetry.instrument_jit("site:b", _raw)
    """, ["closed-program-set"]) == []


def test_closed_program_traced_branching(tmp_path):
    found = _lint(tmp_path, """
        import jax
        from incubator_mxnet_tpu import telemetry

        def body(x):
            if x > 0:                # traced-value Python branch
                return x
            return -x

        def shaped(x):
            if x.shape[0] > 2:       # static under trace: fine
                return x
            return -x

        a = telemetry.instrument_jit("s", jax.jit(body))
        b = telemetry.instrument_jit("t", jax.jit(shaped))
    """, ["closed-program-set"])
    assert len(found) == 1
    assert found[0].line == 6
    assert "lax.cond" in found[0].message


# -- lock-discipline --------------------------------------------------------

def test_lock_discipline_blocking_under_lock(tmp_path):
    found = _lint(tmp_path, """
        import threading, queue

        class A:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def bad(self):
                with self._lock:
                    return self._q.get()     # untimed queue read

            def good(self):
                with self._lock:
                    n = 1
                return self._q.get()         # outside: fine

            def bounded(self):
                with self._lock:
                    return self._q.get(timeout=0.1)
    """, ["lock-discipline"])
    assert len(found) == 1
    assert found[0].line == 11
    assert "holding `_lock`" in found[0].message


def test_lock_discipline_cv_wait_is_fine(tmp_path):
    assert _lint(tmp_path, """
        import threading

        class A:
            def __init__(self):
                self._cv = threading.Condition()

            def waiter(self):
                with self._cv:
                    self._cv.wait()          # releases the lock
    """, ["lock-discipline"]) == []


def test_lock_discipline_order_conflict(tmp_path):
    found = _lint(tmp_path, """
        import threading

        a_lock = threading.Lock()
        b_lock = threading.Lock()

        def one():
            with a_lock:
                with b_lock:
                    pass

        def two():
            with b_lock:
                with a_lock:
                    pass
    """, ["lock-discipline"])
    assert len(found) == 1
    assert "can deadlock" in found[0].message


# -- registry-drift ---------------------------------------------------------

_DRIFT_DOCS = {
    "docs/env_var.md": """
        | Variable | Effect |
        |---|---|
        | `MXNET_DOCUMENTED` | documented and read |
        | `MXNET_STALE_ROW` | documented but never read |
    """,
    "docs/observability.md": """
        | Metric | Type | Meaning |
        |---|---|---|
        | `mxtpu_known{site}` | counter | registered and documented |
        | `mxtpu_ghost` | counter | documented but never registered |
    """,
    "docs/robustness.md": """
        | Site | Plane | Where |
        |---|---|---|
        | `known.site` | inject | documented |
        | `ghost.site` | inject | documented but never instrumented |
    """,
}


def test_registry_drift_both_directions(tmp_path):
    found = _lint(tmp_path, """
        from . import base, fault, telemetry

        base.getenv("MXNET_DOCUMENTED")
        base.getenv("MXNET_UNDOCUMENTED")
        telemetry.registry.counter("mxtpu_known", "d")
        telemetry.registry.counter("mxtpu_secret", "d")
        fault.inject("known.site")
        fault.inject("hidden.site")
    """, ["registry-drift"], extra=_DRIFT_DOCS)
    msgs = "\n".join(f.render() for f in found)
    assert "MXNET_UNDOCUMENTED" in msgs and "MXNET_STALE_ROW" in msgs
    assert "mxtpu_secret" in msgs and "mxtpu_ghost" in msgs
    assert "hidden.site" in msgs and "ghost.site" in msgs
    # the matched pairs are NOT findings
    assert "MXNET_DOCUMENTED" not in msgs
    assert "`mxtpu_known`" not in msgs
    assert "`known.site`" not in msgs
    assert len(found) == 6


def test_registry_drift_silent_without_docs(tmp_path):
    assert _lint(tmp_path, """
        from . import base
        base.getenv("MXNET_WHATEVER")
    """, ["registry-drift"]) == []


# -- suppression planes -----------------------------------------------------

def test_inline_pragma_suppresses(tmp_path):
    found = _lint(tmp_path, """
        # mxtpu-lint: hot-path
        def hot(x):
            a = x.item()  # mxtpu-lint: disable=host-sync-in-hot-path
            # mxtpu-lint: disable=all
            b = x.item()
            c = x.item()
            return a + b + c
    """, ["host-sync-in-hot-path"])
    assert [f.line for f in found] == [7]    # only the unpragma'd one


def test_baseline_round_trip(tmp_path):
    src = """
        # mxtpu-lint: hot-path
        def hot(x):
            a = x.item()
            b = x.item()
            a = x.item()
            return a + b
    """
    found = _lint(tmp_path, src, ["host-sync-in-hot-path"])
    assert len(found) == 3
    lookup = line_text_lookup(str(tmp_path))
    bl = Baseline.from_findings(found, lookup, reason="fixture")
    path = tmp_path / ".mxtpu-lint-baseline.json"
    bl.save(str(path))
    reloaded = Baseline.load(str(path))
    keep, suppressed = reloaded.filter(found, lookup)
    assert keep == [] and len(suppressed) == 3
    # occurrence fingerprints: dropping ONE of the two identical
    # `a = x.item()` entries un-suppresses exactly one finding
    thinned = Baseline([e for e in reloaded.entries
                        if not (e["text"] == "a = x.item()"
                                and e["occ"] == 1)])
    keep, suppressed = thinned.filter(found, lookup)
    assert len(keep) == 1 and len(suppressed) == 2
    assert keep[0].line == 6


# -- the real package -------------------------------------------------------

def _run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtpu_lint.py")]
        + args, capture_output=True, text=True, cwd=cwd)


def test_package_is_clean():
    """The tentpole gate: zero unsuppressed findings over the package
    (pragmas + the committed baseline account for every intentional
    sync/boundary)."""
    res = _run_cli(["incubator_mxnet_tpu"])
    assert res.returncode == 0, res.stdout + res.stderr


def test_injected_violation_fails(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent("""
        import jax
        j = jax.jit(abs)
    """))
    res = _run_cli(["--no-baseline", str(p)])
    assert res.returncode == 1
    assert "closed-program-set" in res.stdout


def test_cli_json_and_unknown_check(tmp_path):
    p = tmp_path / "ok.py"
    p.write_text("x = 1\n")
    res = _run_cli(["--format", "json", str(p)])
    assert res.returncode == 0
    assert json.loads(res.stdout)["findings"] == []
    res = _run_cli(["--checks", "nonsense", str(p)])
    assert res.returncode == 2
    assert "unknown check" in res.stderr


# -- regressions the linter caught in the wild ------------------------------

class _StubEngine:
    name = "stub"
    max_slots = 2
    max_len = 8
    max_batch_size = 0

    def reset(self):
        pass


def test_decode_failed_resets_outside_cv():
    """lock-discipline regression: a wedged ``engine.reset()`` inside
    ``_decode_failed`` must not hold ``_cv`` — the watchdog (and every
    introspection call) needs the lock to even diagnose the wedge."""
    from incubator_mxnet_tpu.serving import ContinuousBatcher

    eng = _StubEngine()
    b = ContinuousBatcher(eng, name="stub")
    try:
        entered, release = threading.Event(), threading.Event()

        def wedged_reset():
            entered.set()
            release.wait(10)

        eng.reset = wedged_reset
        t = threading.Thread(
            target=b._decode_failed,
            args=(0, [], RuntimeError("boom")), daemon=True)
        t.start()
        assert entered.wait(5), "reset was never reached"
        # reset is wedged RIGHT NOW; _cv must still be acquirable
        got = []
        probe = threading.Thread(
            target=lambda: got.append(b.slots_in_use()), daemon=True)
        probe.start()
        probe.join(5)
        assert got == [0], "slots_in_use blocked while reset was wedged"
        release.set()
        t.join(5)
    finally:
        eng.reset = lambda: None
        b.close(drain=False, timeout=5)


def test_superseded_worker_skips_reset():
    """The generation check still gates the reset: a superseded
    worker's _decode_failed must NOT reset the new worker's cache."""
    from incubator_mxnet_tpu.serving import ContinuousBatcher

    eng = _StubEngine()
    b = ContinuousBatcher(eng, name="stub2")
    try:
        calls = []
        eng.reset = lambda: calls.append(1)
        stale_gen = b._worker_gen - 1     # pretend we were replaced
        b._decode_failed(stale_gen, [], RuntimeError("boom"))
        assert calls == []
        b._decode_failed(b._worker_gen, [], RuntimeError("boom"))
        assert calls == [1]
    finally:
        eng.reset = lambda: None
        b.close(drain=False, timeout=5)


def test_batcher_module_has_no_lock_findings():
    """Keep serving/batcher.py lock-clean: the fixed reset-under-_cv
    must not come back."""
    found = run_checks(
        [os.path.join(REPO, "incubator_mxnet_tpu", "serving",
                      "batcher.py")],
        checks=["lock-discipline"], root=REPO)
    assert found == []
