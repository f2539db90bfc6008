"""The AFMoE configuration's own pieces on the CPU: the whole run of its cell
at a tiny size (`tiny_afmoe.json`, `tiny_agent.json`), its control, the three
metrics that read its counters on hand-made snapshots, and the operation and
byte count against ISSUE 26's arithmetic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_afmoe_cell.py -q
"""
import json
import os
import random

import pytest

import checks
import common
import opcount_afmoe
import readers
import refcheck
from reference import afmoe
from runners import serve
from test_rehearsal import KEYS, result_line, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "trinity-ep8-agent-closed"


def _real_config():
    with open(os.path.join(common.HERE, "configs",
                           "trinity-large-serve-ep8.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal_afmoe(trace):
    cell = tiny_cell(CELL, "tiny_afmoe.json", "tiny_agent.json")
    pieces = serve.run(cell, seed=2**31 + 26, seconds=4, trace=trace,
                       platform="cpu")
    line = result_line(pieces)
    assert KEYS <= set(line)
    assert line["correct"] is True, pieces[6]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == {"gap_p95_ms", "serve_out_tok_s",
                                        "setup_s"}
    else:       # no device trace on the CPU: the counters' metrics are read
        got = line["metrics"]
        assert 0 < got["moe_experts_touched_mean"]["value"] <= 4
        assert 30 < got["decode_context_tokens_mean"]["value"] < 128
        assert "afmoe_decode_roofline_pct" not in got
        assert "burst_token_share_pct" in got


def test_serve_other_storage_than_stated_is_not_correct():
    """The tiny program serves float32; a file that states bfloat16 KV is
    not what ran."""
    cell = tiny_cell(CELL, "tiny_afmoe.json", "tiny_agent.json")
    cell["config"]["deployment"]["kv_dtype"] = "bfloat16"
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


def test_control_fails_and_reference_passes():
    """As `test_control.py` for GPT-2: greedy tokens of the float32
    reference pass the tiny limits, the float8 control's fail them."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(HERE, "tiny_afmoe.json")) as f:
        cfg = json.load(f)
    rng = random.Random(5)
    fwd = afmoe.make_forward(cfg, "float32")
    params = afmoe.init_params(cfg, 31)
    samples = []
    for _ in range(4):
        seq = [rng.randrange(cfg["vocab_size"]) for _ in range(40)]
        for _ in range(24):
            pad = np.zeros((1, 128), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        samples.append({"tokens": seq[:40], "served": seq[40:]})
    out = refcheck.serve_numbers(afmoe, cfg, 31, samples,
                                 ["float32", "float8"])
    limits = cfg["check"]["limits"]
    assert checks.judge({k: out["float32"][k] for k in limits}, limits,
                        "sound") is True
    assert checks.judge({k: out["float8"][k] for k in limits}, limits,
                        "control") is False


def test_counts_are_the_issues_arithmetic():
    """ISSUE 26: attention 7.86 M a layer, an expert 28.31 M, 0.69 GB of
    weights every token reads, 4.05 B parameters = 8.09 GB on the chip,
    2,560 B of KV a token."""
    cfg, oc = _real_config(), opcount_afmoe
    assert oc._attention_params(cfg) == 7_864_320
    assert oc.expert_params(cfg) == 28_311_552
    always = oc.always_read_params(cfg)
    assert 0.68e9 < 2 * always < 0.70e9
    held = 4 * 32 * oc.expert_params(cfg)
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    assert 8.08e9 < 2 * (always + held + embed) < 8.10e9
    shapes = [afmoe.layer_shapes(cfg, i) for i in range(5)]
    total = sum(int(__import__("numpy").prod(s)) for sh in shapes
                for s in sh.values()) + 2 * embed + cfg["hidden_size"]
    assert total == always + held + embed
    # 64 streams at 5,000 written positions: sliding layers read 4,096
    assert oc.window_bounded_context(cfg, 5000) == 4 * 4096 + 5000
    flops, nbytes = oc.afmoe_decode_step(cfg, 64, 80.0, 5000, 2, 2)
    kv = 2 * 128 * 2 * (64 * (4 * 4096 + 5000) + 5 * 64)
    assert nbytes == (always + 80 * oc.expert_params(cfg)) * 2 + kv
    assert 5.5e9 < nbytes < 6.5e9 and flops / 197e12 < 0.1 * nbytes / 819e9


def _ctx(touched, context, tokens, decode, burst):
    def snap(scale):
        return {"metrics": {"counters": {
            "mxtpu_moe_experts_touched":
                {"values": {"model=m": scale * touched}},
            "mxtpu_decode_context_tokens":
                {"values": {"model=m": scale * context,
                            "model=draft": 7.0}},
            "mxtpu_generate_tokens":
                {"values": {"model=m,path=burst": scale * tokens,
                            "model=m,path=prefill": scale * 5.0}}}},
            "programs": {"engines": {"m": {"programs": {
                "serving:m:decode": {"dispatches": scale * decode},
                "serving:m:decode_burst": {"dispatches": scale * burst},
            }}}}}
    return {"config": {"deployment": {"model_name": "m", "scan_steps": 8},
                       "num_hidden_layers": 5, "num_dense_layers": 1},
            "snap0": snap(1), "snap1": snap(3)}


def test_counter_metrics_are_window_deltas():
    # window: 2 x (10 decode + 20 bursts x 8) = 340 steps, 4 expert layers
    ctx = _ctx(touched=13600.0, context=2.0e6, tokens=1000.0, decode=10,
               burst=20)
    got = readers.read_all(
        [{"name": "moe_experts_touched_mean", "unit": "count"},
         {"name": "decode_context_tokens_mean", "unit": "tokens"}], ctx)
    assert got["moe_experts_touched_mean"][0] == pytest.approx(
        2 * 13600.0 / 340 / 4)
    assert got["decode_context_tokens_mean"][0] == pytest.approx(
        4.0e6 / 2000.0)


def test_counter_metrics_find_nothing_in_an_older_program():
    """A program without the counters (the parent commit): each reader
    returns None and the line leaves the metric out."""
    ctx = {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
           "snap0": {"metrics": {"counters": {}}, "programs": {}},
           "snap1": {"metrics": {"counters": {}}, "programs": {}},
           "trace": {"programs": {"jit__decode_paged_pure": {
               "count": 3, "seconds": 0.1}}},
           "served": {"param_bytes": 2, "kv_bytes": 2}, "peaks": {}}
    entries = [{"name": n, "unit": "x"} for n in (
        "moe_experts_touched_mean", "decode_context_tokens_mean",
        "afmoe_decode_roofline_pct")]
    assert readers.read_all(entries, ctx) == {}


def test_roofline_reads_the_trace_and_the_counters():
    cfg = _real_config()
    ctx = _ctx(touched=13600.0, context=1.0e6, tokens=21760.0 / 2, decode=10,
               burst=20)         # a step: 64 live slots, 80 experts touched
    ctx["config"] = dict(cfg, deployment=dict(cfg["deployment"],
                                              model_name="m"))
    ctx.update(served={"param_bytes": 2, "kv_bytes": 2},
               peaks=common.peaks_for("TPU v5 lite"),
               trace={"programs": {
                   "jit__decode_burst_paged_pure": {"count": 10,
                                                    "seconds": 2.0}}})
    got = readers.read_all([{"name": "afmoe_decode_roofline_pct",
                             "unit": "%"}], ctx)
    _, nbytes = opcount_afmoe.afmoe_decode_step(cfg, 64.0, 80.0, 2.0e6 / 21760.0, 2, 2)
    assert got["afmoe_decode_roofline_pct"][0] == pytest.approx(
        100.0 * (nbytes / 819e9) * 80 / 2.0)
    assert 0 < got["afmoe_decode_roofline_pct"][0] < 100
