"""Every per-layer metric of BENCHMARK.json reads a number from a window
the harness drove (tiny size, CPU), given a device trace; without a trace
the device metrics read nothing and are left out."""

import pytest

from bench import common, drive, run


def _window(cell):
    spec = common.cell(cell)
    config, traffic = spec["config"], spec["traffic"]
    data = common.load_module("data", config["dataset"])
    built = data.build(config, 17, rehearsal=True)
    warm, drive_run, end_to_end = drive.DRIVERS[traffic["driver"]]
    warm(built, traffic, config, 17)
    window = drive_run(built, traffic, config, 17, 1.0)
    rows = built.rows
    built.close()
    return spec, window, rows, end_to_end(window)


@pytest.mark.parametrize("cell", ["pavlo-scan", "sqlml-logreg"])
def test_per_layer_metrics_read_numbers(cell):
    spec, window, rows, e2e = _window(cell)
    e2e["setup_s"] = 1.0
    trace = {"busy_s": 0.5, "window_s": 1.0, "idle_share": 0.5}
    record = run.RunRecord(window, trace, rows, "TPU v5 lite")
    bench = spec["benchmark"]
    got = run.metrics_of(bench, cell, True, e2e, record)
    want = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"]}
    assert set(got) == want
    assert all(v["value"] >= 0 for v in got.values())
    ends = run.metrics_of(bench, cell, False, e2e, record)
    assert "setup_s" in ends and len(ends) >= 2

    no_trace = run.RunRecord(window, None, rows, "cpu")
    got = run.metrics_of(bench, cell, True, e2e, no_trace)
    assert not any("roofline" in k or "idle" in k for k in got)


def test_train_rows_count_the_cut_workflow_pro_rata():
    # two workflows of 1000 rows each: one wholly inside the 10 s window,
    # one cut by its close halfway through its own span
    w = drive.Window("workflows", 100.0, 10.0)
    w.requests = [
        {"error": None, "t_issue": 100.0, "t_done": 106.0,
         "rows": [100] * 10, "columns": 11},
        {"error": None, "t_issue": 106.0, "t_done": 114.0,
         "rows": [100] * 10, "columns": 11},
        {"error": "RuntimeError()", "t_issue": 114.0, "t_done": 115.0},
    ]
    assert drive.workflow_end_to_end(w)["train_rows_per_s"] == 150.0
    assert drive.workflow_bytes(w) == 1500 * 11 * 4
