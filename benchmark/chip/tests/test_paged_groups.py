"""``paged_run_groups_share_pct`` on hand-made snapshots of the served
program's ``/metrics.json``: the window's delta of
``mxtpu_paged_groups_total`` by ``fetch``, the served model's alone; None
where the program has no such series."""
import pytest

import readers

CFG = {"deployment": {"model_name": "m"}}


def snap(values=None):
    counters = {"mxtpu_generate_tokens": {
        "help": "", "values": {"model=m,path=burst": 10.0}}}
    if values is not None:
        counters["mxtpu_paged_groups_total"] = {"help": "", "values": values}
    return {"metrics": {"counters": counters, "gauges": {},
                        "histograms": {}}}


def read(ctx):
    name = "paged_run_groups_share_pct"
    got = readers.read_all([{"name": name, "unit": "%"}], ctx)
    return got[name][0] if name in got else None


def test_share_is_the_windows_runs_over_its_groups():
    before = {"model=m,fetch=run": 1000.0, "model=m,fetch=blocks": 500.0}
    after = {"model=m,fetch=run": 1900.0, "model=m,fetch=blocks": 600.0,
             "model=draft,fetch=blocks": 5000.0}
    ctx = {"config": CFG, "snap0": snap(before), "snap1": snap(after)}
    assert read(ctx) == pytest.approx(90.0)


def test_a_table_all_in_a_row_reads_100_and_no_run_reads_0():
    ctx = {"config": CFG, "snap0": snap({}),
           "snap1": snap({"model=m,fetch=run": 64.0,
                          "model=m,fetch=blocks": 0.0})}
    assert read(ctx) == pytest.approx(100.0)
    ctx["snap1"] = snap({"model=m,fetch=run": 0.0,
                         "model=m,fetch=blocks": 8.0})
    assert read(ctx) == pytest.approx(0.0)


@pytest.mark.parametrize("after", [
    None,                                           # a program older than it
    {"model=other,fetch=run": 7.0},                 # another model's
    {"model=m,fetch=run": 3.0, "model=m,fetch=blocks": 1.0},   # no step
], ids=["no_series", "other_model", "empty_window"])
def test_none_where_there_is_nothing_to_read(after):
    before = after if after and "model=m,fetch=run" in after else None
    ctx = {"config": CFG, "snap0": snap(before), "snap1": snap(after)}
    assert read(ctx) is None
