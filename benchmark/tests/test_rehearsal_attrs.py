"""A CPU rehearsal of a traced run at a tiny size: its result line holds
the ten metrics that read the spans' attributes, beside the ones the cell
already reports, and the span log that fed them holds the attributes on
every ``step``, ``iter``, ``collect`` and ``h2d`` span of the window."""

import json
import time

from benchmark.lib import drive, spans as spans_mod
from test_rehearsal import tiny_cell
from test_span_attrs import BY_HAND


def test_a_traced_run_reports_the_ten_attribute_metrics(monkeypatch):
    logs = []
    read_log = spans_mod.read_log
    monkeypatch.setattr(spans_mod, "read_log",
                        lambda path: logs.append(read_log(path)) or logs[-1])
    cell = tiny_cell()
    result = drive.run_cell(cell, 2**31 + 1027, 2.0, True, time.time())
    line = json.loads(drive.dumps(result))
    assert line["correct"] is True, line["numbers"]
    assert set(BY_HAND) <= set(line["metrics"]), sorted(line["metrics"])
    units = {m["name"]: m["unit"] for m in cell.per_layer}
    for name in BY_HAND:
        metric = line["metrics"][name]
        assert metric["unit"] == units[name]
        assert isinstance(metric["value"], float)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["compile_in_window_ms"] == 0.0
    assert 0.0 <= m["step_blocked_ms"] <= m["step_call_ms_p50"] * 50
    assert m["feed_row_cpu_us"] > 0 and m["feed_row_wall_us"] > 0
    assert m["step_call_ms_p50"] <= m["iter_ms_p50"]
    # the log itself: attributes on every span of the loop and the feed,
    # collect and h2d labelled with the step that consumes the batch
    win = spans_mod.window(logs[0], int(cell.traffic["warmup_iters"]))
    steps = {s["step"] for s in win.iters}
    for s in win.spans:
        if s["name"] in ("step", "iter", "collect", "h2d"):
            assert "attrs" in s, s
        if s["name"] in ("collect", "h2d"):
            assert s["step"] >= min(steps)
        if s["name"] == "data_wait":
            assert "attrs" not in s
    assert any(s["name"].startswith("setup.") for s in logs[0])
