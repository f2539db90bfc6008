"""The Granite 4.0-H configuration's own pieces on the CPU: the whole run of
its cell at a tiny size (`tiny_granite4.json`, `tiny_chatrate.json`), `correct`
turning false on a broken timed path, on an altered state row and on a state
kept in another type, its control, the readers of its three metrics on
hand-made snapshots, and the operation and byte count against ISSUE 42's
arithmetic.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests/test_granite4_cell.py -q
"""
import json
import os
import random

import pytest

import checks
import common
import opcount_granite4 as oc
import readers
import refcheck
from reference import granite_hybrid
from runners import serve
from test_rehearsal import KEYS, result_line, tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "granite4hm-chat-open"


def _real_config():
    with open(os.path.join(common.HERE, "configs",
                           "granite4-h-micro-serve.json")) as f:
        return json.load(f)


def _tiny():
    return tiny_cell(CELL, "tiny_granite4.json", "tiny_chatrate.json")


@pytest.mark.parametrize("trace", [False, True])
def test_serve_rehearsal_granite4(trace):
    cell = _tiny()
    pieces = serve.run(cell, seed=2**31 + 42, seconds=5, trace=trace,
                       platform="cpu")
    line = result_line(pieces)
    assert KEYS <= set(line)
    assert line["correct"] is True, pieces[6]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = {m["name"] for m in cell["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == {"gap_p95_ms", "req_latency_mean_ms",
                                        "setup_s"}
    else:       # no device trace on the CPU: the counters' metrics are read
        got = line["metrics"]
        # a live slot's state is a fixed share of a step whatever the batch
        assert 0 < got["ssm_state_bytes_share_pct"]["value"] < 100
        assert 0 < got["state_rows_peak_pct"]["value"] <= 100
        assert 8 < got["decode_context_tokens_mean"]["value"] < 440
        assert "granite4_decode_roofline_pct" not in got
        assert "granite4_prefill_roofline_pct" not in got
        for name in ("ttft_mean_ms", "queue_wait_mean_ms",
                     "burst_token_share_pct", "join_edit_ms_mean"):
            assert name in got


@pytest.mark.parametrize("child", ["altered_child.py",
                                   "altered_row_child.py"])
def test_a_broken_path_is_not_correct(child, monkeypatch):
    """The first token of every request altered, or a slot's state row
    turned round after its join: the tokens come, `correct` must come out
    false."""
    monkeypatch.setattr(serve, "CHILD", os.path.join(HERE, child))
    pieces = serve.run(_tiny(), seed=42, seconds=4, trace=False,
                       platform="cpu")
    assert pieces[0] is False
    assert pieces[2] == 0                   # no request failed


@pytest.mark.parametrize("key", ["state_dtype", "kv_dtype", "param_dtype"])
def test_serve_other_storage_than_stated_is_not_correct(key):
    cell = _tiny()
    stated = cell["config"]["deployment"]
    if key == "param_dtype":    # the child builds what the file states
        cell["config"]["deployment"] = dict(stated, param_dtype="float32")
        pieces = serve.run(cell, seed=6, seconds=2, trace=False,
                           platform="cpu")
        served = {"param_dtype": "float32", "kv_dtype": "float32"}
        assert checks.judge_stated(served, stated, "test") is False
        assert pieces[0] is False           # the pool's type is stated too
        return
    stated[key] = "float16"
    pieces = serve.run(cell, seed=6, seconds=2, trace=False, platform="cpu")
    assert pieces[0] is False


def test_control_fails_and_reference_passes():
    """Greedy tokens of the float32 reference pass the tiny limits, the
    float8 control's fail them."""
    import jax.numpy as jnp
    import numpy as np
    with open(os.path.join(HERE, "tiny_granite4.json")) as f:
        cfg = json.load(f)
    rng = random.Random(5)
    fwd = granite_hybrid.make_forward(cfg, "float32")
    params = granite_hybrid.init_params(cfg, 42)
    samples = []
    for _ in range(3):
        seq = [rng.randrange(cfg["vocab_size"]) for _ in range(70)]
        for _ in range(16):
            pad = np.zeros((1, 256), np.int32)
            pad[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(fwd(params, jnp.asarray(pad))
                                      [0, len(seq) - 1])))
        samples.append({"tokens": seq[:70], "served": seq[70:]})
    out = refcheck.serve_numbers(granite_hybrid, cfg, 42, samples,
                                 ["float32", "float8"])
    limits = cfg["check"]["limits"]
    assert checks.judge({k: out["float32"][k] for k in limits}, limits,
                        "sound") is True
    assert checks.judge({k: out["float8"][k] for k in limits}, limits,
                        "control") is False


def test_the_file_keeps_every_published_number():
    """The catalog row's ``config``, key for key and nothing reduced."""
    cfg = _real_config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = [json.loads(line) for line in f
                   if '"granite-4.0-h-micro"' in line][0]
        assert {k: cfg[k] for k in row["config"]} == row["config"]
        assert cfg["source"] == row["source_url"]
    want = {"hidden_size": 2048, "num_hidden_layers": 40,
            "vocab_size": 100352, "shared_intermediate_size": 8192,
            "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
            "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "attention_multiplier": 0.015625, "embedding_multiplier": 12,
            "residual_multiplier": 0.22, "logits_scaling": 8,
            "tie_word_embeddings": True, "num_local_experts": 0}
    assert {k: cfg[k] for k in want} == want
    assert cfg["reduced"] == []
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    dep = cfg["deployment"]
    assert dep["max_len"] == 4096 and dep["block_size"] == 16
    assert dep["scan_steps"] == 8 and dep["prefix_cache"] is True
    assert dep["max_slots"] >= 32
    assert dep["num_blocks"] == 1 + dep["max_slots"] * 256
    assert 0 < dep["state_snapshot_rows"] <= dep["max_slots"] // 2
    assert dep["state_snapshot_tokens"] % 256 == 0
    assert all(b % 256 == 0 for b in dep["prefill_buckets"])
    assert all(v is not None for v in cfg["check"]["limits"].values())


def test_the_traffic_is_the_issues():
    with open(os.path.join(common.HERE, "traffic",
                           "chat-rate-open.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "open" and mix["arrivals"]["process"] == "poisson"
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.8, "min": 32, "max": 3072}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.7, "min": 32, "max": 768}
    assert (mix["total_max"], mix["ramp_seconds"], mix["drain_seconds"],
            mix["shuffle_block"], mix["shared_prefix_tokens"],
            mix["greedy"]) == (4096, 8, 30, 8, 0, True)


@pytest.mark.parametrize("group", ["configs", "workloads", "per_layer"])
def test_a_line_of_words_has_at_most_200_characters(group):
    """The driver refuses the file before any run otherwise (it refused PR
    42's first hand-in for a configuration's ``why`` of 202); the accepted
    ``test_manifest.py`` holds the cells' lines only."""
    for entry in common.manifest()[group]:
        for key in ("why", "layer", "source"):
            text = entry.get(key)
            if text is not None:
                assert 1 <= len(text) <= 200, (entry["name"], key, len(text))
                assert text.isprintable(), (entry["name"], key)


def test_counts_are_the_issues_arithmetic():
    """ISSUE 42: a Mamba-2 mixer 25.85 M, an MLP 50.33 M, attention 10.49 M,
    a Mamba layer 76.2 M, an attention layer 60.8 M, the tied embedding
    205.5 M held once: 3.19 B = 6.38 GB; a sequence's state 77.4 MB; 8,192 B
    of keys and values a token; a step of 48 live slots moves 7.4 GB of
    state beside 6.38 GB of weights."""
    import numpy as np
    cfg = _real_config()
    d, V = 2048, 100352
    assert oc.layer_kinds(cfg) == (36, 4)
    assert oc.mamba_params(cfg) == d * 8512 + 4352 * 4 + 4352 + 3 * 64 \
        + 4096 + 4096 * d == 25_847_232
    assert oc.attention_params(cfg) == 2 * d * d + 2 * d * 512 == 10_485_760
    assert oc.layer_common_params(cfg) == 3 * d * 8192 + 2 * d
    shapes = [granite_hybrid.layer_shapes(cfg, i) for i in range(40)]
    total = sum(int(np.prod(s)) for sh in shapes for s in sh.values()) \
        + V * d + d
    assert total == oc.total_params(cfg)
    assert 3.19e9 < total < 3.195e9 and 6.38e9 < 2 * total < 6.39e9
    assert oc.state_elements(cfg) == 64 * 64 * 128 + 3 * 4352
    assert oc.state_bytes_a_sequence(cfg) == 36 * 2_149_376 == 77_377_536
    assert oc.kv_bytes_a_token(cfg, 2) == 8192
    # 48 streams, 700 written positions each
    flops, nbytes = oc.decode_step(cfg, 48, 700, 2, 2, 4)
    state = 2 * 48 * 77_377_536
    kv = 4 * (2 * 8 * 64 * 2 * 700 * 48 + 2 * 32 * 64 * 4 * 48) + 8192 * 48
    assert nbytes == 2 * total + state + kv
    assert 7.4e9 < state < 7.45e9 and 14.0e9 < nbytes < 14.2e9
    assert flops / 197e12 < 0.2 * nbytes / 819e9        # bandwidth bound
    # the kernels' own counts
    f, b = oc.ssd_step_call(cfg, 48)
    assert f == 5.0 * 64 * 64 * 128 * 48
    assert b == (2 * 64 * 64 * 128 * 4 + (3 * 4096 + 256) * 4) * 48
    f, b = oc.ssd_prefill_call(cfg, 530, 1, 2)
    assert f == 5.0 * 64 * 64 * 128 * 530
    assert b == 530 * (4352 * 2 + 128 * 4 + 4096 * 4) \
        + 2 * 64 * 64 * 128 * 4
    f, b = oc.paged_gqa_call(cfg, 48, 700, 2)
    assert f == 4.0 * 32 * 64 * 700 * 48
    # a miss of 530 positions: compute bound, weights once
    f, b = oc.prefill_call(cfg, 530, 0, 2, 2, 4)
    assert 1.5 < (f / 197e12) / (b / 819e9) < 3.0


def _ctx(rows, steps_burst, tokens, context, prefills=0, computed=0.0):
    def snap(k):
        return {"metrics": {
            "counters": {
                "mxtpu_ssm_step_rows_total": {"values": {
                    "model=m": k * rows, "model=other": 9.0}},
                "mxtpu_generate_tokens": {"values": {
                    "model=m,path=burst": k * tokens}},
                "mxtpu_decode_context_tokens": {"values": {
                    "model=m": k * context}},
                "mxtpu_prefill_tokens": {"values": {
                    "model=m,path=miss": k * computed}}},
            "gauges": {"mxtpu_ssm_state_bytes": {"values": {
                "model=m": 77_377_536.0, "model=other": 1.0}}}},
            "programs": {"engines": {"m": {"programs": {
                "serving:m:decode_burst": {"dispatches": k * steps_burst},
                "serving:m:prefill": {"dispatches": k * prefills}}}}}}
    cfg = _real_config()
    return {"config": dict(cfg, deployment=dict(cfg["deployment"],
                                                model_name="m")),
            "snap0": snap(1), "snap1": snap(3),
            "served": {"param_bytes": 2, "kv_bytes": 2, "state_bytes": 4},
            "peaks": common.peaks_for("TPU v5 lite")}


def test_state_share_is_a_window_delta():
    # 100 bursts of 8 steps, 40 live slots a step, 700 positions behind each
    ctx = _ctx(rows=32000.0, steps_burst=100, tokens=32000.0,
               context=700.0 * 32000)
    got = readers.read_all([{"name": "ssm_state_bytes_share_pct",
                             "unit": "%"}], ctx)
    _, moved = oc.decode_step(ctx["config"], 40.0, 700.0, 2, 2, 4)
    assert got["ssm_state_bytes_share_pct"][0] == pytest.approx(
        100.0 * 2 * 40 * 77_377_536 / moved)
    assert 45 < got["ssm_state_bytes_share_pct"][0] < 50


def test_rooflines_read_the_trace_and_the_counters():
    ctx = _ctx(rows=32000.0, steps_burst=100, tokens=32000.0,
               context=700.0 * 32000, prefills=50, computed=530.0 * 50)
    ctx["trace"] = {"programs": {
        "jit__decode_burst_paged_pure": {"count": 10, "seconds": 1.6},
        "jit__prefill_paged_pure": {"count": 5, "seconds": 0.2}}}
    got = readers.read_all([{"name": n, "unit": "%"} for n in (
        "granite4_decode_roofline_pct", "granite4_prefill_roofline_pct")],
        ctx)
    f, b = oc.decode_step(ctx["config"], 40.0, 700.0, 2, 2, 4)
    assert got["granite4_decode_roofline_pct"][0] == pytest.approx(
        100.0 * max(f / 197e12, b / 819e9) * 80 / 1.6)
    f, b = oc.prefill_call(ctx["config"], 530.0, 0.0, 2, 2, 4)
    assert got["granite4_prefill_roofline_pct"][0] == pytest.approx(
        100.0 * max(f / 197e12, b / 819e9) * 5 / 0.2)
    assert all(0 < v[0] < 100 for v in got.values())


def test_new_metrics_find_nothing_in_an_older_program():
    """A program without the counters or the state (the parent commit), or
    another configuration's cell: each reader returns None and the line
    leaves the metric out."""
    ctx = {"config": {"deployment": {"model_name": "m", "scan_steps": 8}},
           "snap0": {"metrics": {"counters": {}}, "programs": {}},
           "snap1": {"metrics": {"counters": {}}, "programs": {}},
           "samples": [{"models": {"m": {"kv_blocks_in_use": 1}}}],
           "trace": {"programs": {"jit__decode_paged_pure": {
               "count": 3, "seconds": 0.1}}},
           "served": {"param_bytes": 2, "kv_bytes": 2}, "peaks": {}}
    entries = [{"name": n, "unit": "%"} for n in (
        "granite4_decode_roofline_pct", "granite4_prefill_roofline_pct",
        "ssm_state_bytes_share_pct")]
    assert readers.read_all(entries, ctx) == {}
