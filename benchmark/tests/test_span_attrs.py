"""The ten readers of span attributes: on a small log whose numbers are
worked out by hand below, on the log of a program that writes no
attributes (every one finds nothing to read), and as listed for every
cell of the manifest.

``fixtures/obs_log_attrs.jsonl``: six iterations of 0.1 s from wall time
1000.0, two of them warm-up, the fence at 1000.62. The warm-up spans carry
other values (a slow, CPU-bound, unready step), so a reader that forgot the
window reads wrong. Timed iterations 2..5:

* ``step``: 60, 70, 80, 50 ms -> median 65; ``cpu_s`` 20, 30, 20, 10 ms ->
  blocked 40, 40, 60, 40 -> mean 45; ``inflight`` 0, 1, 2, 2 -> median 1.5;
  ``input_ready`` three of four -> 75%;
* ``iter``: ``cpu_s`` 25, 35, 30, 10 ms -> mean 25;
* ``collect``: ``ready`` three of four -> 75%; 8 rows each; ``cpu_s`` 2 + 4
  + 2 + 8 = 16 ms over 32 rows -> 500 us; ``wall_s`` 4 + 8 + 12 + 8 = 32 ms
  -> 1000 us;
* ``h2d``: 2, 3, 2, 5 ms -> median 2.5;
* ``compile``: 20 ms on the loop thread with a nested 5 ms event (once),
  10 ms on another thread -> 30; the 0.5 s compile of set-up is outside.
"""

import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
BY_HAND = {
    "step_call_ms_p50": 65.0,
    "step_blocked_ms": 45.0,
    "step_inflight_p50": 1.5,
    "step_input_ready_share": 75.0,
    "iter_cpu_ms": 25.0,
    "feed_ready_share": 75.0,
    "feed_row_cpu_us": 500.0,
    "feed_row_wall_us": 1000.0,
    "h2d_ms_p50": 2.5,
    "compile_in_window_ms": 30.0,
}


def _context(log: str, warmup: int) -> dict:
    recorded = spans.read_log(os.path.join(FIXTURES, log))
    return {"window": spans.window(recorded, warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name="rn50-fit-1chip")}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_against_the_value_worked_out_by_hand(name):
    value = cells.reader(name).read(_context("obs_log_attrs.jsonl", 2))
    assert isinstance(value, float)
    assert value == pytest.approx(BY_HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_finds_nothing_in_a_log_without_attributes(name):
    """The log of the parent commit: the same span names, no ``attrs``."""
    assert cells.reader(name).read(_context("obs_log.jsonl", 5)) is None


def test_window_keeps_the_attributes_and_the_untraced_part_reads_less():
    ctx = _context("obs_log_attrs.jsonl", 2)
    win = ctx["window"]
    assert all("attrs" in s for s in win.spans
               if s["name"] in ("step", "iter", "collect", "h2d"))
    # a traced run hands the readers the part before the profiler opened
    ctx["window"] = spans.before(win, 1000.4)
    assert cells.reader("step_call_ms_p50").read(ctx) == pytest.approx(65.0)
    assert cells.reader("step_inflight_p50").read(ctx) == pytest.approx(0.5)
    assert cells.reader("compile_in_window_ms").read(ctx) == \
        pytest.approx(20.0)


def test_a_feed_of_processes_reports_no_cpu_time_per_row():
    ctx = _context("obs_log_attrs.jsonl", 2)
    kept = []
    for s in ctx["window"].spans:
        if s["name"] == "collect":
            attrs = {k: v for k, v in s["attrs"].items() if k != "cpu_s"}
            s = dict(s, attrs=attrs)
        kept.append(s)
    ctx["window"] = spans.Window(
        ctx["window"].t_first_iter, ctx["window"].t_start,
        ctx["window"].t_end, ctx["window"].iters, tuple(kept))
    assert cells.reader("feed_row_cpu_us").read(ctx) is None
    assert cells.reader("feed_row_wall_us").read(ctx) == pytest.approx(1000.0)
    assert cells.reader("feed_ready_share").read(ctx) == pytest.approx(75.0)


def test_every_cell_lists_the_ten():
    bench = cells.manifest()
    for entry in bench["workloads"]:
        listed = {m["name"]: m for m in
                  cells.load_cell(entry["name"], bench).per_layer}
        assert set(BY_HAND) <= set(listed)
        for name in BY_HAND:
            spec = cells.layer_metric(name)
            assert spec["layer"] == listed[name]["layer"]
            assert spec["unit"] == listed[name]["unit"]
            assert listed[name]["source"] == "program_span"
            assert listed[name]["moves"] == "train_img_s_chip"
