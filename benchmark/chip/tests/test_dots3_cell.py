"""The dots3-note configuration's own pieces on the CPU: the whole run of its
cell at a tiny size (`tiny_dots3.json`, `tiny_repoagent.json`), `correct`
turning false on a broken timed path and on a cache kept in another type, its
control, the manifest's entries for it, the readers of its two metrics on
hand-made snapshots, and the operation and byte count against ISSUE 39's
arithmetic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_dots3_cell.py -q
"""
import json
import os
import random

import pytest

import checks
import common
import opcount_dots3 as oc
import readers
import refcheck
from reference import dots3
from runners import serve
from test_rehearsal import KEYS, result_line, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "dots3-tp8-repoagent-closed"
NEW = ("dots3_decode_roofline_pct", "index_selected_share_pct")


def _real_config():
    with open(os.path.join(common.HERE, "configs",
                           "dots3-note-serve-tp8-l5.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal_dots3(trace):
    cell = tiny_cell(CELL, "tiny_dots3.json", "tiny_repoagent.json")
    pieces = serve.run(cell, seed=2**31 + 39, seconds=4, trace=trace,
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
        # every join after the first hits the 64 shared tokens of 72-88
        assert 50 < got["prefix_hit_tokens_share_pct"]["value"] < 90
        # contexts of 73-112 against a top-k of 12 and a window of 21
        assert 10 < got["index_selected_share_pct"]["value"] < 17
        assert got["decode_window_tokens_mean"]["value"] == 21
        assert 72 < got["decode_context_tokens_mean"]["value"] < 128
        assert 0 < got["moe_experts_touched_mean"]["value"] <= 4
        assert "dots3_decode_roofline_pct" not in got
        assert "burst_token_share_pct" in got


def test_a_broken_timed_path_is_not_correct(monkeypatch):
    """The first token of every request altered where the engine produces
    it: the tokens come, `correct` must come out false."""
    cell = tiny_cell(CELL, "tiny_dots3.json", "tiny_repoagent.json")
    monkeypatch.setattr(serve, "CHILD",
                        os.path.join(HERE, "altered_child.py"))
    pieces = serve.run(cell, seed=39, seconds=3, trace=False, platform="cpu")
    assert pieces[0] is False
    assert pieces[2] == 0                   # no request failed


def test_serve_other_storage_than_stated_is_not_correct():
    """The latent rows are kept in the parameters' type (float32 here):
    a file that states another makes the run not correct."""
    cell = tiny_cell(CELL, "tiny_dots3.json", "tiny_repoagent.json")
    cell["config"]["deployment"]["kv_dtype"] = "bfloat16"
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


def test_control_fails_and_reference_passes():
    """Greedy tokens of the float32 reference pass the tiny limits, the
    float8 control's fail them."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(HERE, "tiny_dots3.json")) as f:
        cfg = json.load(f)
    rng = random.Random(5)
    fwd = dots3.make_forward(cfg, "float32")
    params = dots3.init_params(cfg, 39)
    samples = []
    for _ in range(3):
        seq = [rng.randrange(cfg["vocab_size"]) for _ in range(70)]
        for _ in range(16):
            pad = np.zeros((1, 128), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        samples.append({"tokens": seq[:70], "served": seq[70:]})
    out = refcheck.serve_numbers(dots3, cfg, 39, samples,
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
    want = {"hidden_size": 5120, "intermediate_size": 13824,
            "moe_intermediate_size": 1536, "q_lora_rank": 1024,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
            "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
            "swa_v_head_dim": 128, "index_n_heads": 64,
            "index_head_dim": 128, "index_topk": 2048,
            "sliding_window_size": 513, "num_experts_per_tok": 8,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "rope_theta": 80000000, "swa_rope_theta": 50000,
            "rms_norm_eps": 1e-05, "max_position_embeddings": 524288,
            "routed_scaling_factor": 1, "norm_topk_prob": True,
            "apply_mla_qkv_lora_rescale": True}
    assert {k: cfg[k] for k in want} == want
    cut = {"num_hidden_layers": (5, 46), "n_routed_experts": (32, 256),
           "num_attention_heads": (16, 128), "num_key_value_heads": (16, 128),
           "swa_num_attention_heads": (8, 64),
           "swa_num_key_value_heads": (8, 64), "vocab_size": (19008, 152064)}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"] \
        + list(cut)[1:]
    for key, (here, published) in cut.items():
        assert cfg[key] == here and cfg["published"][key] == published
        assert key in cfg["reduced_why"]
    assert cfg["layer_types"] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    assert cfg["n_routed_experts_published"] == 256
    dep = cfg["deployment"]
    assert dep["max_len"] == 27136 and dep["max_slots"] == 64
    assert 16385 <= dep["num_blocks"] <= 24577
    assert dep["prefill_buckets"] == [1024, 4096] and dep["scan_steps"] == 8
    assert all(v is not None for v in cfg["check"]["limits"].values())


def test_the_manifest_names_the_cell_as_the_issue_does():
    man = common.manifest()
    cell = common.resolve_cell(CELL)
    assert cell["chips"] == 1
    t = cell["traffic"]
    assert (t["loop"], t["clients"], t["shared_prefix_tokens"]) \
        == ("closed", 64, 24576)
    assert t["prompt_tokens"] == {"dist": "uniform", "min": 24832,
                                  "max": 25600}
    assert t["output_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert t["greedy"] is True and t["total_max"] == 27136
    assert (t["ramp_seconds"], t["drain_seconds"],
            t["requests_per_client"]) == (16, 0, 8)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert e2e == {"gap_p95_ms", "serve_out_tok_s", "setup_s"}
    per_layer = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"moe_experts_touched_mean",
                       "decode_context_tokens_mean",
                       "decode_window_tokens_mean",
                       "prefix_hit_tokens_share_pct"} <= per_layer
    assert "paged_run_groups_share_pct" not in per_layer
    for m in man["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gap_p95_ms"


def test_counts_are_the_issues_arithmetic():
    """ISSUE 39: an expert 23,592,960; 779,878,400 an expert layer; the
    dense FFN 212,336,640; a full layer's attention 33,374,208 and a
    sliding layer's 20,815,872 (matrices); 3.66 B parameters = 7.31 GB;
    9,344 B of cache a token."""
    import numpy as np
    cfg = _real_config()
    d, V = 5120, 19008
    assert oc.expert_params(cfg) == 23_592_960
    assert 32 * oc.expert_params(cfg) + oc.expert_params(cfg) + d * 256 \
        == 779_878_400
    norms_full = 1024 + 512 + 2 * 128
    assert oc.attention_params(cfg, False) == 33_374_208 + norms_full
    assert oc.attention_params(cfg, True) == 20_815_872 + 1024 + 1024
    assert oc.layer_kinds(cfg) == (2, 3)
    shapes = [dots3.layer_shapes(cfg, i) for i in range(5)]
    total = sum(int(np.prod(s)) for sh in shapes for s in sh.values()) \
        + 2 * d * V + d
    assert total == oc.always_read_params(cfg) + d * V \
        + 4 * 32 * oc.expert_params(cfg)
    assert 3.65e9 < total < 3.67e9 and 7.30e9 < 2 * total < 7.33e9
    assert oc.kv_bytes_per_token(cfg, 2) == 9344
    assert oc.row_features(cfg, False) == 704
    assert oc.row_features(cfg, True) == 1088
    assert oc.held_pairs_a_token(cfg) == 1.0
    # 64 streams of 26,000 written positions, 111 experts touched (27.8 a
    # layer), 513 in the window, 2,048 chosen
    flops, nbytes = oc.decode_step(cfg, 64, 111.0, 26000, 513, 2048, 2, 2)
    always = oc.always_read_params(cfg)
    index = 2 * 64 * 26000 * 128 * 2
    chosen = 2 * 64 * 2048 * 576 * 2
    window = 3 * 64 * 513 * 1088 * 2
    assert (always + 111 * 23_592_960) * 2 + index + chosen + window \
        < nbytes < 1.01 * ((always + 111 * 23_592_960) * 2 + index + chosen
                           + window)
    assert 0.84e9 < index < 0.86e9 and 0.29e9 < chosen < 0.31e9 \
        and 0.20e9 < window < 0.22e9        # the issue's 0.85, 0.30, 0.21 GB
    assert 7.5e9 < nbytes < 7.8e9 and flops / 197e12 < 0.2 * nbytes / 819e9
    # the kernels' own counts
    f, b = oc.latent_decode_call(cfg, 64, 513, 2)
    assert f == 2.0 * 8 * (1088 + 1024) * 64 * 513
    assert b == 1088 * 2 * 64 * 513 + 64 * 8 * (1088 * 2 + 1024 * 4)
    f, b = oc.index_select_call(cfg, 64, 26000, 2)
    assert f == (2.0 * 128 + 3.0) * 64 * 64 * 26000
    f, b = oc.sparse_latent_call(cfg, 64, 2048, 2)
    assert f == 2.0 * 16 * (576 + 512) * 64 * 2048
    # a hit of 640 positions after 24,576: the weights' bytes lead
    f, b = oc.prefill_call(cfg, 640, 24576, 128, 2, 2)
    assert b > 7.1e9 and (f / 197e12) < (b / 819e9)
    # a chunk of 4,096 after 12,288: compute leads
    f, b = oc.prefill_call(cfg, 4096, 12288, 128, 2, 2)
    assert (f / 197e12) > (b / 819e9)


def _ctx(scored, chosen, window, context, tokens, touched, bursts):
    def snap(scale):
        return {"metrics": {"counters": {
            name: {"values": {"model=m": scale * v, "model=other": 5.0}}
            for name, v in (
                ("mxtpu_index_keys_scored", scored),
                ("mxtpu_index_keys_selected", chosen),
                ("mxtpu_decode_window_tokens", window),
                ("mxtpu_decode_context_tokens", context),
                ("mxtpu_moe_experts_touched", touched),
                ("mxtpu_prefix_hit_tokens", 24576.0 * 4))}
            | {"mxtpu_generate_tokens": {"values": {
                "model=m,path=burst": scale * tokens}},
               "mxtpu_prefill_tokens": {"values": {
                   "model=m,path=hit": scale * 640.0 * 4}}}},
            "programs": {"engines": {"m": {"programs": {
                "serving:m:decode_burst": {"dispatches": scale * bursts},
                "serving:m:prefill_ext": {"dispatches": scale * 4}}}}}}
    return {"snap0": snap(1), "snap1": snap(3)}


def test_the_new_metrics_read_the_trace_and_the_counters():
    cfg = _real_config()
    # 10 bursts of 8 steps, 64 live slots: 5,120 tokens in the window's
    # delta (x 2: the snapshots are 1 x and 3 x)
    tokens = 64 * 8 * 5.0
    ctx = _ctx(scored=2 * 26000.0 * tokens, chosen=2 * 2048.0 * tokens,
               window=513.0 * tokens, context=26000.0 * tokens,
               tokens=tokens, touched=111.0 * 40, bursts=5)
    ctx["config"] = dict(cfg, deployment=dict(cfg["deployment"],
                                              model_name="m"))
    ctx.update(served={"param_bytes": 2, "kv_bytes": 2},
               peaks=common.peaks_for("TPU v5 lite"),
               trace={"programs": {
                   "jit__decode_burst_paged_pure": {"count": 10,
                                                    "seconds": 2.0},
                   "jit__prefill_ext_pure": {"count": 8, "seconds": 0.8}}})
    got = readers.read_all([{"name": n, "unit": "%"} for n in NEW], ctx)
    assert got["index_selected_share_pct"][0] == pytest.approx(
        100.0 * 2048 / 26000)
    f, b = oc.decode_step(cfg, 64, 111.0, 26000, 513, 2048, 2, 2)
    least = max(f / 197e12, b / 819e9)
    assert got["dots3_decode_roofline_pct"][0] == pytest.approx(
        100.0 * least * 80 / 2.0)
    assert all(0 < got[n][0] < 100 for n in NEW)


def test_new_metrics_find_nothing_in_an_older_program():
    """A program without the counters (the parent commit): each reader
    returns None and the line leaves the metric out."""
    ctx = {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
           "snap0": {"metrics": {"counters": {}}, "programs": {}},
           "snap1": {"metrics": {"counters": {}}, "programs": {}},
           "samples": [{"models": {"m": {"kv_blocks_in_use": 1}}}],
           "trace": {"programs": {"jit__decode_paged_pure": {
               "count": 3, "seconds": 0.1}}},
           "served": {"param_bytes": 2, "kv_bytes": 2}, "peaks": {}}
    assert readers.read_all([{"name": n, "unit": "%"} for n in NEW],
                            ctx) == {}
