"""``ssm_step_rows_skipped_share_pct`` on hand-made snapshots of the served
program's ``/metrics.json``: the window's delta of
``mxtpu_ssm_step_rows_skipped_total`` over it plus
``mxtpu_ssm_step_rows_total``'s, the served model's alone; None where the
program has no such series."""
import pytest

import readers

CFG = {"deployment": {"model_name": "m"}}
NAME = "ssm_step_rows_skipped_share_pct"


def snap(skipped=None, updated=None):
    counters = {}
    for name, values in (("mxtpu_ssm_step_rows_skipped_total", skipped),
                         ("mxtpu_ssm_step_rows_total", updated)):
        if values is not None:
            counters[name] = {"help": "", "values": values}
    return {"metrics": {"counters": counters, "gauges": {},
                        "histograms": {}}}


def read(ctx):
    got = readers.read_all([{"name": NAME, "unit": "%"}], ctx)
    return got[NAME][0] if NAME in got else None


def test_share_is_the_windows_skipped_rows_over_all_it_could_visit():
    ctx = {"config": CFG,
           "snap0": snap({"model=m": 100.0}, {"model=m": 1000.0}),
           "snap1": snap({"model=m": 140.0, "model=draft": 5000.0},
                         {"model=m": 1060.0, "model=draft": 1.0})}
    assert read(ctx) == pytest.approx(40.0)


@pytest.mark.parametrize("skipped, updated, want", [
    (0.0, 384.0, 0.0),              # every slot live at every step
    (384.0, 0.0, 100.0),            # a burst whose slots had all ended
], ids=["full_batch", "nobody_live"])
def test_the_ends_of_the_scale(skipped, updated, want):
    ctx = {"config": CFG, "snap0": snap({}, {}),
           "snap1": snap({"model=m": skipped}, {"model=m": updated})}
    assert read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("after", [
    snap(),                                             # no state-space layer
    snap(None, {"model=m": 60.0}),                      # older than the list
    snap({"model=other": 7.0}, {"model=other": 9.0}),   # another model's
    snap({"model=m": 3.0}, {"model=m": 5.0}),           # no step in the window
], ids=["no_series", "older_program", "other_model", "empty_window"])
def test_none_where_there_is_nothing_to_read(after):
    counters = after["metrics"]["counters"]
    mine = "model=m" in counters.get(
        "mxtpu_ssm_step_rows_skipped_total", {}).get("values", {})
    ctx = {"config": CFG, "snap0": after if mine else snap(),
           "snap1": after}
    assert read(ctx) is None
