"""The SmallThinker configuration's own pieces on the CPU: the whole run of
its cell at a tiny size (`tiny_smallthinker.json`, `tiny_docqa.json`), its
control, the two metrics that read its counters on hand-made snapshots, and
the operation and byte count against ISSUE 31's arithmetic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_smallthinker_cell.py -q
"""
import json
import os
import random

import pytest

import checks
import common
import opcount_smallthinker as oc
import readers
import refcheck
from reference import smallthinker
from runners import serve
from test_rehearsal import KEYS, result_line, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "smallthinker-l8-docqa-closed"


def _real_config():
    with open(os.path.join(common.HERE, "configs",
                           "smallthinker-21b-serve-l8.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal_smallthinker(trace):
    cell = tiny_cell(CELL, "tiny_smallthinker.json", "tiny_docqa.json")
    pieces = serve.run(cell, seed=2**31 + 31, seconds=4, trace=trace,
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
        assert 0 < got["moe_experts_touched_mean"]["value"] <= 8
        context = got["decode_context_tokens_mean"]["value"]
        window = got["decode_window_tokens_mean"]["value"]
        assert 12 < context < 128
        # slot by slot, so below min(mean context, window) when contexts
        # lie on both sides of the window of 24
        assert 12 < window < min(context, 24)
        assert "smallthinker_decode_roofline_pct" not in got
        assert "burst_token_share_pct" in got


def test_serve_other_storage_than_stated_is_not_correct():
    cell = tiny_cell(CELL, "tiny_smallthinker.json", "tiny_docqa.json")
    cell["config"]["deployment"]["kv_dtype"] = "bfloat16"
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


def test_control_fails_and_reference_passes():
    """Greedy tokens of the float32 reference pass the tiny limits, the
    float8 control's fail them."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(HERE, "tiny_smallthinker.json")) as f:
        cfg = json.load(f)
    rng = random.Random(5)
    fwd = smallthinker.make_forward(cfg, "float32")
    params = smallthinker.init_params(cfg, 31)
    samples = []
    for _ in range(4):
        seq = [rng.randrange(cfg["vocab_size"]) for _ in range(40)]
        for _ in range(24):
            pad = np.zeros((1, 128), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        samples.append({"tokens": seq[:40], "served": seq[40:]})
    out = refcheck.serve_numbers(smallthinker, cfg, 31, samples,
                                 ["float32", "float8"])
    limits = cfg["check"]["limits"]
    assert checks.judge({k: out["float32"][k] for k in limits}, limits,
                        "sound") is True
    assert checks.judge({k: out["float8"][k] for k in limits}, limits,
                        "control") is False


def test_the_file_keeps_every_published_number():
    """The catalog row's numbers, unchanged but for depth and the two
    layouts cut with it (the kept layers' own entries)."""
    cfg = _real_config()
    want = {"head_dim": 128, "hidden_size": 2560,
            "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
            "moe_num_active_primary_experts": 6,
            "moe_num_primary_experts": 64, "num_attention_heads": 28,
            "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
            "rope_theta": 1500000, "sliding_window_size": 4096,
            "vocab_size": 151936}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == ["num_hidden_layers", "rope_layout",
                              "sliding_window_layout"]
    assert cfg["num_hidden_layers"] == 8 \
        and cfg["published"]["num_hidden_layers"] == 52
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 2
    assert all(v is not None for v in cfg["check"]["limits"].values())


def test_counts_are_the_issues_arithmetic():
    """ISSUE 31: attention 20,971,520 a layer, router 163,840, norms 5,120,
    an expert 5,898,240, a layer 398.6 M, 3.967 B parameters = 7.93 GB on
    the chip, 16 KB of KV a token."""
    import numpy as np
    cfg = _real_config()
    assert oc._attention_params(cfg) == 20_971_520
    assert oc.expert_params(cfg) == 5_898_240
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], 8
    always = oc.always_read_params(cfg)
    assert always == L * (20_971_520 + 163_840 + 5_120) + d + d * V
    shapes = [smallthinker.layer_shapes(cfg, i) for i in range(L)]
    total = sum(int(np.prod(s)) for sh in shapes for s in sh.values()) \
        + 2 * d * V + d
    assert total == always + L * 64 * oc.expert_params(cfg) + d * V
    assert 3.96e9 < total < 3.97e9 and 7.92e9 < 2 * total < 7.94e9
    # 32 streams, 5,000 written positions each: the 6 windowed layers read
    # 4,096 (the counter's mean is slot by slot; here every slot is alike)
    assert oc.keys_read(cfg, 5000, 4096) == 2 * 5000 + 6 * 4096
    flops, nbytes = oc.decode_step(cfg, 32, 400.0, 5000, 4096, 2, 2)
    kv = 2 * 4 * 128 * 2 * (32 * (2 * 5000 + 6 * 4096) + 8 * 32)
    assert nbytes == (always + 400 * oc.expert_params(cfg)) * 2 + kv
    assert 16 * 1024 == 8 * 2 * 4 * 128 * 2              # KV bytes a token
    assert 7.0e9 < nbytes < 8.5e9 and flops / 197e12 < 0.1 * nbytes / 819e9
    # the kernels' own counts
    f, b = oc.paged_gqa_call(cfg, 32, 4096, 2)
    assert b == 2 * 4 * 128 * 2 * 4096 * 32 + 2 * 28 * 128 * 4 * 32
    assert f == 4.0 * 28 * 128 * 4096 * 32
    f, b = oc.experts_call(cfg, 192, 50, 2)
    assert f == 2.0 * 5_898_240 * 192
    assert b == 50 * 5_898_240 * 2 + 192 * 2560 * 6


def _ctx(touched, context, window, tokens, decode, burst):
    def snap(scale):
        return {"metrics": {"counters": {
            "mxtpu_moe_experts_touched":
                {"values": {"model=m": scale * touched}},
            "mxtpu_decode_context_tokens":
                {"values": {"model=m": scale * context}},
            "mxtpu_decode_window_tokens":
                {"values": {"model=m": scale * window,
                            "model=draft": 7.0}},
            "mxtpu_generate_tokens":
                {"values": {"model=m,path=burst": scale * tokens,
                            "model=m,path=prefill": scale * 5.0}}}},
            "programs": {"engines": {"m": {"programs": {
                "serving:m:decode": {"dispatches": scale * decode},
                "serving:m:decode_burst": {"dispatches": scale * burst},
            }}}}}
    return {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
            "snap0": snap(1), "snap1": snap(3)}


def test_window_tokens_metric_is_a_window_delta():
    ctx = _ctx(touched=1.0, context=2.0e6, window=1.5e6, tokens=1000.0,
               decode=10, burst=20)
    got = readers.read_all(
        [{"name": "decode_window_tokens_mean", "unit": "tokens"}], ctx)
    assert got["decode_window_tokens_mean"][0] == pytest.approx(
        3.0e6 / 2000.0)


def test_new_metrics_find_nothing_in_an_older_program():
    """A program without the counters (the parent commit): each reader
    returns None and the line leaves the metric out."""
    ctx = {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
           "snap0": {"metrics": {"counters": {}}, "programs": {}},
           "snap1": {"metrics": {"counters": {}}, "programs": {}},
           "trace": {"programs": {"jit__decode_paged_pure": {
               "count": 3, "seconds": 0.1}}},
           "served": {"param_bytes": 2, "kv_bytes": 2}, "peaks": {}}
    entries = [{"name": n, "unit": "x"} for n in (
        "decode_window_tokens_mean", "smallthinker_decode_roofline_pct")]
    assert readers.read_all(entries, ctx) == {}


def test_roofline_reads_the_trace_and_the_counters():
    cfg = _real_config()
    # a step: 32 live slots, 400 experts touched over the 8 layers
    ctx = _ctx(touched=68000.0, context=2.0e7, window=1.6e7,
               tokens=5440.0, decode=10, burst=20)
    ctx["config"] = dict(cfg, deployment=dict(cfg["deployment"],
                                              model_name="m"))
    ctx.update(served={"param_bytes": 2, "kv_bytes": 2},
               peaks=common.peaks_for("TPU v5 lite"),
               trace={"programs": {
                   "jit__decode_burst_paged_pure": {"count": 10,
                                                    "seconds": 2.0}}})
    got = readers.read_all([{"name": "smallthinker_decode_roofline_pct",
                             "unit": "%"}], ctx)
    _, nbytes = oc.decode_step(cfg, 32.0, 400.0, 4.0e7 / 10880.0,
                               3.2e7 / 10880.0, 2, 2)
    assert got["smallthinker_decode_roofline_pct"][0] == pytest.approx(
        100.0 * (nbytes / 819e9) * 80 / 2.0)
    assert 0 < got["smallthinker_decode_roofline_pct"][0] < 100
