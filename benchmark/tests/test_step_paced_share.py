"""``step_paced_share``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5) with ``paced`` written onto
its ``step`` spans by the test, on the logs of a program without the
attribute, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "step_paced_share"


def _context(path: str, warmup: int) -> dict:
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name="vitb16-fit-1chip")}


def _log_with(tmp_path, paced_steps) -> str:
    """The fixture's log with ``paced`` on every ``step`` span: true for
    the steps named. The warm-up steps (0, 1) read the other way round
    from the timed ones, so a reader that forgot the window reads wrong."""
    out = tmp_path / "obs_log_paced.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "step":
                rec["attrs"]["paced"] = rec["step"] in paced_steps
            dst.write(json.dumps(rec) + "\n")
    return str(out)


@pytest.mark.parametrize("paced_steps,share", [
    ({2, 3, 4, 5}, 100.0),       # the device sets the pace
    ({0, 1, 3, 5}, 50.0),        # on some
    ({0, 1}, 0.0),               # the host sets the pace (warm-up aside)
])
def test_share_of_the_timed_steps_that_waited(tmp_path, paced_steps, share):
    ctx = _context(_log_with(tmp_path, paced_steps), 2)
    value = cells.reader(NAME).read(ctx)
    assert isinstance(value, float) and value == pytest.approx(share)


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # PR 27's program: attrs, none called paced
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_nothing(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


def test_the_untraced_part_of_a_traced_window_is_what_is_read(tmp_path):
    ctx = _context(_log_with(tmp_path, {2, 5}), 2)
    assert cells.reader(NAME).read(ctx) == pytest.approx(50.0)
    ctx["window"] = spans.before(ctx["window"], 1000.4)  # steps 2 and 3
    assert cells.reader(NAME).read(ctx) == pytest.approx(50.0)
    ctx["window"] = spans.before(ctx["window"], 1000.3)  # step 2 alone
    assert cells.reader(NAME).read(ctx) == pytest.approx(100.0)


def test_listed_for_the_cells_that_report_the_step_time_tail():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {k: spec[k] for k in entry}
    assert bench["per_layer"][-1] is entry  # added at the end
    assert entry["moves"] == "step_ms_p95"
    tail = next(m for m in bench["end_to_end"] if m["name"] == "step_ms_p95")
    assert entry["workloads"] == tail["workloads"]
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  cells.load_cell(cell["name"], bench).per_layer}
        assert (NAME in listed) is (cell["name"] in entry["workloads"])
