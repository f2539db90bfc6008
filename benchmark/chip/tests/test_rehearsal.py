"""The end-to-end rehearsal on the CPU: both runners' functions at a tiny size,
the result object's keys, and `correct` coming out false when the timed path is
broken underneath.  `run.py` itself has no CPU mode; these tests skip its look
for a chip by calling the runners with ``platform="cpu"``.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests -q
"""
import contextlib
import io
import json
import os

import pytest

import common
from runners import serve, train

HERE = os.path.dirname(os.path.abspath(__file__))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny_cell(real_cell, config, traffic):
    cell = common.resolve_cell(real_cell)
    with open(os.path.join(HERE, config)) as f:
        cell["config"] = json.load(f)
    if traffic:
        with open(os.path.join(HERE, traffic)) as f:
            cell["traffic"] = json.load(f)
    return cell


def result_line(pieces):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        common.print_result(*pieces[:6])
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("real,traffic", [
    ("gpt2m-chat-open", "tiny_open.json"),
    ("gpt2m-decode-closed", "tiny_closed.json")])
@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal(real, traffic, trace):
    cell = tiny_cell(real, "tiny_gpt.json", traffic)
    pieces = serve.run(cell, seed=2**31 + 11, seconds=4, trace=trace,
                       platform="cpu")
    line = result_line(pieces)
    assert KEYS <= set(line)
    assert line["correct"] is True, pieces[6]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:                     # no device trace on the CPU: the rest is read
        assert "programs_compiled" in line["metrics"]
        assert "burst_dispatch_share_pct" in line["metrics"]


def test_serve_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: `correct` must come out false."""
    cell = tiny_cell("gpt2m-decode-closed", "tiny_gpt.json",
                     "tiny_closed.json")
    monkeypatch.setattr(serve, "CHILD",
                        os.path.join(HERE, "altered_child.py"))
    pieces = serve.run(cell, seed=5, seconds=3, trace=False, platform="cpu")
    assert pieces[0] is False


@pytest.mark.parametrize("key,stated", [("param_dtype", "bfloat16"),
                                        ("kv_dtype", "bfloat16"),
                                        ("matmul_precision", "highest")])
def test_serve_other_storage_than_stated_is_not_correct(key, stated):
    """The program serves float32 parameters and KV at jax's default matmul
    precision; a configuration that states otherwise is not what ran."""
    cell = tiny_cell("gpt2m-decode-closed", "tiny_gpt.json",
                     "tiny_closed.json")
    cell["config"]["deployment"][key] = stated
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


@pytest.mark.parametrize("trace", [False, True])
def test_train_rehearsal(trace):
    cell = tiny_cell("bertl-mlm-s128", "tiny_bert.json", None)
    pieces = train.run(cell, seed=2**31 + 3, seconds=2, trace=trace,
                       platform="cpu")
    line = result_line(pieces)
    assert KEYS <= set(line)
    assert line["correct"] is True, pieces[6]
    if not trace:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:
        assert {"step_ms_p50", "data_wait_ms_p50",
                "programs_compiled"} <= set(line["metrics"])


def test_train_unchanged_state_is_not_correct():
    cell = tiny_cell("bertl-mlm-s128", "tiny_bert.json", None)
    pieces = train.run(cell, seed=7, seconds=1, trace=False, platform="cpu",
                       break_step=True)
    assert pieces[0] is False
