"""BENCHMARK.json against the harness's files: names and units, every file
found by name, every `moves` target reported by every cell that reports the
metric."""
import json
import os
import re

import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_is_consistent():
    man = common.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in man["workloads"]]
    metrics = man["end_to_end"] + man["per_layer"]
    for n in cells + [m["name"] for m in metrics] \
            + [c["name"] for c in man["configs"]] \
            + [w["traffic"] for w in man["workloads"]]:
        assert NAME.match(n), n
    assert len(set(cells)) == len(cells)
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    assert any(m["name"] == "setup_s" for m in man["end_to_end"])
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        # every cell that reports the metric reports what it moves
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]], m["name"]
    for cell in cells:
        assert sum(cell in e for e in e2e.values()) >= 2       # setup_s + one
        assert any(cell in m.get("workloads", cells)
                   for m in man["per_layer"])


def test_every_entry_has_its_files():
    man = common.manifest()
    for c in man["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in man["paths"]))
        with open(os.path.join(common.ROOT, c["file"])) as f:
            cfg = json.load(f)
        for key in ("kind", "reference", "program", "check"):
            assert key in cfg, (c["name"], key)
        assert os.path.exists(os.path.join(
            common.HERE, "reference", cfg["reference"] + ".py"))
        assert os.path.exists(os.path.join(
            common.HERE, "programs", cfg["program"] + ".py"))
        assert os.path.exists(os.path.join(
            common.HERE, "runners", cfg["kind"] + ".py"))
        assert all(v is not None for v in cfg["check"]["limits"].values())
    for w in man["workloads"]:
        cell = common.resolve_cell(w["name"])
        assert cell["traffic"]["name"] == w["traffic"]
        assert len(w["why"]) <= 200
    for m in man["end_to_end"] + man["per_layer"]:
        spec = common.load_json("metrics", m["name"] + ".json")
        assert spec.get("layer") == m.get("layer"), m["name"]
        if spec["source_kind"] == "custom":
            assert os.path.exists(os.path.join(
                common.HERE, "metrics", m["name"] + ".py"))


def test_peaks_name_their_source():
    for kind, row in common.load_json("peaks.json").items():
        assert row["flops_per_s"] > 0 and row["bytes_per_s"] > 0
        assert row["source"], kind
