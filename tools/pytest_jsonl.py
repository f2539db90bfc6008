"""Pytest plugin: append one JSON line per finished test to the file
named by ``MXNET_TEST_JSONL`` — incremental persistence for long tiers
(e.g. ``tests_tpu`` on the chip, writing under ``chiprun_out/``), so a
run stopped at its time limit keeps every verdict it produced.

Loaded explicitly (``-p pytest_jsonl`` with tools/ on PYTHONPATH); does
nothing when the env var is unset.  Each line::

    {"nodeid": "...", "outcome": "passed|failed|skipped",
     "duration_s": 0.12, "when": "call", "time_unix": ...}

One line per test: the ``call`` phase normally, but setup/teardown
errors and skips surface through their own phase, so non-``passed``
setup/teardown outcomes are recorded too (a setup error IS the test's
verdict).  Appends are flushed per line — the journal is valid JSONL
at every instant.
"""
import json
import os
import time


def _path():
    return os.environ.get("MXNET_TEST_JSONL") or None


def pytest_runtest_logreport(report):
    path = _path()
    if not path:
        return
    # the call phase carries the real verdict; setup/teardown only
    # matter when they didn't pass (error or skip decided the test)
    if report.when != "call" and report.outcome == "passed":
        return
    rec = {"nodeid": report.nodeid,
           "outcome": report.outcome,
           "when": report.when,
           "duration_s": round(getattr(report, "duration", 0.0), 4),
           "time_unix": round(time.time(), 3)}
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
    except OSError:
        pass  # a broken journal must never fail the tier itself


def load_journal(path):
    """Parse a journal written by this plugin: ``(passed_ids, records)``
    where ``passed_ids`` is the set of node ids whose LAST ``call``
    verdict was ``passed`` (re-runs supersede — a flaky pass after a
    fail counts as passed).  Tolerates truncated trailing lines."""
    last = {}
    records = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line from a killed run
                if not isinstance(rec, dict) or "nodeid" not in rec:
                    continue
                records.append(rec)
                last[rec["nodeid"]] = rec
    except OSError:
        return set(), []
    passed = {nid for nid, rec in last.items()
              if rec.get("outcome") == "passed"}
    return passed, records
