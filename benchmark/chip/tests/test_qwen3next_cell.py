"""The Qwen3-Next configuration's own pieces on the CPU: the whole run of its
cell at a tiny size (`tiny_qwen3next.json`, `tiny_corpusqa.json`), `correct`
turning false on a broken state and on a state kept in another type, its
control, the readers of its four metrics on hand-made snapshots, and the
operation and byte count against ISSUE 35's arithmetic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_qwen3next_cell.py -q
"""
import json
import os
import random

import pytest

import checks
import common
import opcount_qwen3next as oc
import readers
import refcheck
from reference import qwen3next
from runners import serve
from test_rehearsal import KEYS, result_line, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "qwen3next-tp2-corpusqa-closed"


def _real_config():
    with open(os.path.join(common.HERE, "configs",
                           "qwen3next-80b-serve-tp2-l4.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal_qwen3next(trace):
    cell = tiny_cell(CELL, "tiny_qwen3next.json", "tiny_corpusqa.json")
    pieces = serve.run(cell, seed=2**31 + 35, seconds=4, trace=trace,
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
        # every join after the second hits the 128 shared tokens of 150-190
        assert 50 < got["prefix_hit_tokens_share_pct"]["value"] < 86
        assert 0 < got["state_rows_peak_pct"]["value"] <= 100
        assert 0 < got["moe_experts_touched_mean"]["value"] <= 8
        assert 128 < got["decode_context_tokens_mean"]["value"] < 256
        assert "qwen3next_decode_roofline_pct" not in got
        assert "qwen3next_prefill_roofline_pct" not in got
        assert "burst_token_share_pct" in got


def test_a_broken_state_is_not_correct(monkeypatch):
    """A hit that starts from another row than its snapshot: the tokens
    come, `correct` must come out false."""
    cell = tiny_cell(CELL, "tiny_qwen3next.json", "tiny_corpusqa.json")
    monkeypatch.setattr(serve, "CHILD",
                        os.path.join(HERE, "broken_state_child.py"))
    pieces = serve.run(cell, seed=35, seconds=3, trace=False, platform="cpu")
    assert pieces[0] is False
    assert pieces[2] == 0                   # no request failed


@pytest.mark.parametrize("key", ["state_dtype", "kv_dtype"])
def test_serve_other_storage_than_stated_is_not_correct(key):
    cell = tiny_cell(CELL, "tiny_qwen3next.json", "tiny_corpusqa.json")
    cell["config"]["deployment"][key] = "bfloat16"
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


def test_control_fails_and_reference_passes():
    """Greedy tokens of the float32 reference pass the tiny limits, the
    float8 control's fail them."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(HERE, "tiny_qwen3next.json")) as f:
        cfg = json.load(f)
    rng = random.Random(5)
    fwd = qwen3next.make_forward(cfg, "float32")
    params = qwen3next.init_params(cfg, 35)
    samples = []
    for _ in range(3):
        seq = [rng.randrange(cfg["vocab_size"]) for _ in range(70)]
        for _ in range(16):
            pad = np.zeros((1, 256), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        samples.append({"tokens": seq[:70], "served": seq[70:]})
    out = refcheck.serve_numbers(qwen3next, cfg, 35, samples,
                                 ["float32", "float8"])
    limits = cfg["check"]["limits"]
    assert checks.judge({k: out["float32"][k] for k in limits}, limits,
                        "sound") is True
    assert checks.judge({k: out["float8"][k] for k in limits}, limits,
                        "control") is False


def test_the_file_keeps_every_published_number():
    """The catalog row's numbers, unchanged but for the keys in `reduced`,
    each with its published value beside it."""
    cfg = _real_config()
    want = {"decoder_sparse_step": 1, "full_attention_interval": 4,
            "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
            "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
            "linear_value_head_dim": 128, "max_position_embeddings": 262144,
            "moe_intermediate_size": 512, "num_experts_per_tok": 10,
            "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
            "rope_theta": 10000000, "shared_expert_intermediate_size": 512}
    assert {k: cfg[k] for k in want} == want
    cut = {"num_hidden_layers": (4, 48), "num_experts": (256, 512),
           "num_attention_heads": (8, 16), "num_key_value_heads": (1, 2),
           "linear_num_key_heads": (8, 16),
           "linear_num_value_heads": (16, 32), "vocab_size": (75968, 151936)}
    assert cfg["reduced"] == list(cut)
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published
        assert key in cfg["reduced_why"]
    assert cfg["num_experts_published"] == 512
    dep = cfg["deployment"]
    assert dep["num_blocks"] == 1 + 64 * 1088 and dep["max_len"] == 17408
    assert all(b % 64 == 0 for b in dep["prefill_buckets"])
    assert all(v is not None for v in cfg["check"]["limits"].values())


def test_counts_are_the_issues_arithmetic():
    """ISSUE 35: DeltaNet 16.9 M, attention 13.6 M, router 1.05 M, shared
    expert 3.15 M, an expert 3,145,728, 826 M / 823 M a layer, 3.61 B
    parameters = 7.23 GB; 1 KB of KV a token."""
    import numpy as np
    cfg = _real_config()
    d, V = 2048, 75968
    assert oc.linear_params(cfg) == d * 6144 + d * 32 + 4096 * 4 + d * d \
        + 2 * 16 + 128
    assert oc.attention_params(cfg) == d * 4096 + 2 * d * 256 + d * d + 512
    assert oc.expert_params(cfg) == 3_145_728
    assert oc.layer_common_params(cfg) == d * 512 + 3 * d * 512 + d + 2 * d
    assert oc.layer_kinds(cfg) == (3, 1)
    shapes = [qwen3next.layer_shapes(cfg, i) for i in range(4)]
    total = sum(int(np.prod(s)) for sh in shapes for s in sh.values()) \
        + 2 * d * V + d
    assert total == oc.body_params(cfg) + oc.head_params(cfg) + d * V \
        + 4 * 256 * oc.expert_params(cfg)
    assert 3.60e9 < total < 3.62e9 and 7.2e9 < 2 * total < 7.25e9
    assert oc.state_elements(cfg) == 16 * 128 * 128 + 3 * 4096
    assert oc.held_pairs_a_token(cfg) == 5.0
    # 64 streams, 13,000 written positions each, 600 experts touched
    flops, nbytes = oc.decode_step(cfg, 64, 600.0, 13000, 2, 2, 4)
    always = oc.body_params(cfg) + oc.head_params(cfg)
    state = 3 * 2 * 64 * oc.state_elements(cfg) * 4
    kv = 2 * 1 * 256 * 2 * (64 * 13000 + 64) + 2 * 8 * 256 * 4 * 64
    assert nbytes == (always + 600 * 3_145_728) * 2 + state + kv
    assert 1024 == 2 * 1 * 256 * 2                       # KV bytes a token
    assert 5.0e9 < nbytes < 6.5e9 and flops / 197e12 < 0.2 * nbytes / 819e9
    # the kernels' own counts
    f, b = oc.gated_delta_step_call(cfg, 64)
    assert f == 6.0 * 16 * 128 * 128 * 64
    assert b == 2 * (16 * 128 * 128 + 3 * 4096) * 4 * 64
    f, b = oc.gated_delta_prefill_call(cfg, 5000, 1, 2)
    assert f == 6.0 * 16 * 128 * 128 * 5000
    assert b == 5000 * (4096 * 2 + 32 * 4 + 2048 * 4) \
        + 2 * oc.state_elements(cfg) * 4
    f, b = oc.paged_gqa_call(cfg, 64, 13000, 2)
    assert f == 4.0 * 8 * 256 * 13000 * 64
    f, b = oc.experts_call(cfg, 320, 150, 2)
    assert f == 2.0 * 3_145_728 * 320
    assert b == 150 * 3_145_728 * 2 + 320 * 2048 * 6
    # a hit of 5,000 positions after 8,192: compute and bandwidth near par
    f, b = oc.prefill_call(cfg, 5000, 8192, 1024, 2, 2, 4)
    assert 0.5 < (f / 197e12) / (b / 819e9) < 2.5


def _ctx(hit, miss_tokens, hit_tokens, prefills):
    def snap(scale):
        return {"metrics": {"counters": {
            "mxtpu_prefix_hit_tokens": {"values": {"model=m": scale * hit,
                                                   "model=other": 5.0}},
            "mxtpu_prefill_tokens": {"values": {
                "model=m,path=miss": scale * miss_tokens,
                "model=m,path=hit": scale * hit_tokens}}}},
            "programs": {"engines": {"m": {"programs": {
                "serving:m:prefill_ext": {"dispatches": scale * prefills},
            }}}}}
    return {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
            "snap0": snap(1), "snap1": snap(3)}


def test_hit_share_is_a_window_delta():
    ctx = _ctx(hit=8192.0, miss_tokens=100.0, hit_tokens=5020.0, prefills=1)
    got = readers.read_all(
        [{"name": "prefix_hit_tokens_share_pct", "unit": "%"}], ctx)
    assert got["prefix_hit_tokens_share_pct"][0] == pytest.approx(
        100.0 * 8192 / (8192 + 5120))


def test_state_rows_peak_reads_the_samples():
    ctx = {"config": {"deployment": {"model_name": "m"}},
           "samples": [{"models": {"m": {"state_rows_in_use": n,
                                         "state_rows_total": 320}}}
                       for n in (64, 96, 80)]}
    got = readers.read_all([{"name": "state_rows_peak_pct", "unit": "%"}],
                           ctx)
    assert got["state_rows_peak_pct"][0] == pytest.approx(30.0)


def test_new_metrics_find_nothing_in_an_older_program():
    """A program without the counters or the state (the parent commit):
    each reader returns None and the line leaves the metric out."""
    ctx = {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
           "snap0": {"metrics": {"counters": {}}, "programs": {}},
           "snap1": {"metrics": {"counters": {}}, "programs": {}},
           "samples": [{"models": {"m": {"kv_blocks_in_use": 1}}}],
           "trace": {"programs": {"jit__decode_paged_pure": {
               "count": 3, "seconds": 0.1}}},
           "served": {"param_bytes": 2, "kv_bytes": 2}, "peaks": {}}
    entries = [{"name": n, "unit": "%"} for n in (
        "qwen3next_decode_roofline_pct", "qwen3next_prefill_roofline_pct",
        "prefix_hit_tokens_share_pct", "state_rows_peak_pct")]
    assert readers.read_all(entries, ctx) == {}


def test_prefill_roofline_reads_the_trace_and_the_counters():
    cfg = _real_config()
    ctx = _ctx(hit=8192.0 * 10, miss_tokens=0.0, hit_tokens=5000.0 * 10,
               prefills=10)
    ctx["config"] = dict(cfg, deployment=dict(cfg["deployment"],
                                              model_name="m"))
    ctx.update(served={"param_bytes": 2, "kv_bytes": 2, "state_bytes": 4},
               peaks=common.peaks_for("TPU v5 lite"),
               trace={"programs": {
                   "jit__prefill_ext_pure": {"count": 4, "seconds": 0.4}}})
    got = readers.read_all([{"name": "qwen3next_prefill_roofline_pct",
                             "unit": "%"}], ctx)
    f, b = oc.prefill_call(cfg, 5000.0, 8192.0, 1024, 2, 2, 4)
    least = max(f / 197e12, b / 819e9)
    assert got["qwen3next_prefill_roofline_pct"][0] == pytest.approx(
        100.0 * least * 4 / 0.4)
    assert 0 < got["qwen3next_prefill_roofline_pct"][0] < 100
