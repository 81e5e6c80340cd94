"""The trace reduction on a small recorded trace: busy time as a union,
idle share, exposed collective time on two overlapping tracks, and the
idle gaps named by the host span that covered them."""

import json
import os
import types

import pytest

from benchmark.lib import tracered

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(FIXTURES, "trace_small.json")) as f:
        return json.load(f)


def test_interval_arithmetic():
    assert tracered.union([(0, 5), (3, 8), (10, 12), (12, 12)]) == [
        (0, 8), (10, 12)]
    assert tracered.total([(0, 8), (10, 12)]) == 10
    assert tracered.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == [
        (0, 2), (3, 5), (25, 30)]
    assert tracered.gaps([(2, 4)], 0, 10) == [(0, 2), (4, 10)]


def test_busy_is_a_union_and_idle_is_the_rest(trace):
    dev = tracered.reduce_device(trace["planes"][0], (900, 6000))
    # three executions of 1000 ns busy (the nested event counted once),
    # 500 ns of waiting after each: the stretch is two whole periods,
    # from the first start to the last start
    assert dev["steps"] == 2
    assert dev["busy_ns"] == 2000
    assert dev["window_ns"] == 3000
    reduced = tracered.reduce_trace(trace)
    assert reduced["busy_s"] == pytest.approx(2000e-9)
    assert reduced["window_s"] == pytest.approx(3000e-9)
    assert 1.0 - reduced["busy_s"] / reduced["window_s"] == \
        pytest.approx(1 / 3)


def test_collective_exposure_counts_only_what_no_compute_covers(trace):
    dev = tracered.reduce_device(trace["planes"][0], (900, 6000))
    # per step the all-reduce spans 400 ns: 300 under fusion.2, 100 exposed
    # (the core's all-reduce-done), so 2 x 100 over the two whole periods
    assert dev["has_collectives"]
    assert dev["collective_exposed_ns"] == 200


def test_marker_modules_bound_the_stretch_and_give_the_offset(trace):
    # from the end of the open marker's module to the start of the close
    # marker's, though only device 0 ran them
    assert tracered.marker_bounds(trace) == (900, 6000)
    assert tracered.reduce_trace(trace)["stretch"] == "between the markers"
    # bounds that end inside the third execution leave one whole period
    dev = tracered.reduce_device(trace["planes"][0], (900, 4200))
    assert dev["steps"] == 1 and dev["window_ns"] == 1500
    # each marker was seen ready a little after its module ended (900,
    # 6010 ns): the one seen soonest gives the offset
    walls = {tracered.MARKER_OPEN: 100.0 + 900e-9 + 3e-6,
             tracered.MARKER_CLOSE: 100.0 + 6010e-9 + 1e-6}
    reduced = tracered.reduce_trace(trace, walls)
    assert reduced["offset_s"] == pytest.approx(100.0 + 1e-6, abs=1e-9)
    assert tracered.reduce_trace(trace)["offset_s"] is None
    # no close marker (the run gave up first): to the last operation
    unclosed = json.loads(json.dumps(trace))
    modules = unclosed["planes"][0]["lines"][0]["events"]
    modules[:] = [e for e in modules if "marker_close" not in e[0]]
    assert tracered.marker_bounds(unclosed) == (900, 6009)


def test_markers_that_hold_no_whole_step_fall_back_to_the_whole_trace(trace):
    stalled = json.loads(json.dumps(trace))
    for e in stalled["planes"][0]["lines"][0]["events"]:
        if "marker_open" in e[0]:
            e[1:] = [2100, 10]   # both markers in one gap between steps
        if "marker_close" in e[0]:
            e[1:] = [2400, 10]
    reduced = tracered.reduce_trace(stalled)
    assert reduced["stretch"].startswith("the whole trace: under two")
    assert reduced["steps"] == 2 and reduced["busy_s"] > 0
    unmarked = json.loads(json.dumps(trace))
    for plane in unmarked["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if "marker" not in e[0]]
    assert tracered.reduce_trace(unmarked)["stretch"] == \
        "the whole trace: no marker program found"


MS = 1_000_000


def _steady(starts_ms, devices=1, skip=()):
    """A trace of 50 ms steps at ``starts_ms`` on every device (device d
    skips the starts in ``skip`` if d == 1: a stall of its own), one
    marker before the first and one after the last."""
    planes = []
    for d in range(devices):
        mine = [t for t in starts_ms if not (d == 1 and t in skip)]
        modules = [["jit_step(1)", t * MS, 50 * MS] for t in mine]
        ops = [["fusion.1 bf16[8]", t * MS, 50 * MS] for t in mine]
        if d == 0:
            lo, hi = starts_ms[0] - 10, starts_ms[-1] + 60
            modules += [["jit_bench_marker_open(2)", lo * MS, MS],
                        ["jit_bench_marker_close(3)", hi * MS, MS]]
            ops += [["add.1 s32[]", lo * MS, MS], ["add.1 s32[]", hi * MS, MS]]
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": sorted(modules, key=lambda e: e[1])},
            {"name": "XLA Ops", "events": sorted(ops, key=lambda e: e[1])}]})
    return {"planes": planes}


def _every_70ms(lo_ms, hi_ms):
    return list(range(lo_ms, hi_ms, 70))


def test_a_stretch_that_holds_a_stall_is_not_clean_and_the_last_clean_is_taken():
    read_s, max_gap_s = 1.0, 0.14   # twice a 70 ms iteration
    # steady, a 1 s stall, steady again for 1.5 s: the last second is read
    starts = _every_70ms(100, 1000) + _every_70ms(2000, 3500)
    reduced = tracered.reduce_trace(_steady(starts), None, read_s, max_gap_s)
    assert reduced["stretch"] == "between the markers, clean"
    dev = reduced["busiest"]
    assert dev["lo_ns"] >= 2000 * MS and dev["steps"] >= 12
    assert 1.0 - reduced["busy_s"] / reduced["window_s"] == \
        pytest.approx(20 / 70)
    # without the rule the same trace reads the stall as idleness
    held = tracered.reduce_trace(_steady(starts))
    assert 1.0 - held["busy_s"] / held["window_s"] > 0.45
    # the stall near the end: the piece behind it is too short, the last
    # clean SECOND lies before it
    starts = _every_70ms(100, 2000) + _every_70ms(3000, 3200)
    reduced = tracered.reduce_trace(_steady(starts), None, read_s, max_gap_s)
    assert reduced["stretch"] == "between the markers, clean"
    assert reduced["busiest"]["hi_ns"] <= 2000 * MS
    # no clean second anywhere: the longest clean piece, and the note
    starts = _every_70ms(100, 600) + _every_70ms(1500, 2100)
    reduced = tracered.reduce_trace(_steady(starts), None, read_s, max_gap_s)
    assert reduced["stretch"].startswith("between the markers, clean for 0.6")
    assert "beside a stall of 0.8" in reduced["stretch"]
    assert reduced["busiest"]["lo_ns"] >= 1500 * MS
    # a stall on ONE of two devices spoils the stretch for both
    starts = _every_70ms(100, 4000)
    reduced = tracered.reduce_trace(_steady(starts, 2, set(starts[20:30])),
                                    None, read_s, max_gap_s)
    assert reduced["stretch"] == "between the markers, clean"
    assert all(d["lo_ns"] >= starts[30] * MS for d in reduced["devices"])
    # every piece between stalls holds under two whole steps: all of it is
    # read, and the stretch names the stall
    starts = [100, 170, 1000, 1070, 2000, 2070]
    reduced = tracered.reduce_trace(_steady(starts), None, read_s, max_gap_s)
    assert "holding a stall of 0.8" in reduced["stretch"]
    assert reduced["steps"] == 5


class _Line:
    """Shaped like a ``ProfileData`` line; notes every event it hands out."""

    def __init__(self, name, n, visits):
        self.name, self._n, self._visits = name, n, visits

    @property
    def events(self):
        for i in range(self._n):
            self._visits.append(self.name)
            yield types.SimpleNamespace(
                name="%f = bf16[8]{0} fusion(%p)", start_ns=10 * i,
                duration_ns=5)


def test_host_lines_are_loaded_without_a_visit():
    visits = []
    planes = [
        types.SimpleNamespace(name="/host:CPU", lines=[
            _Line(f"pjrt-tpu-tasks/{i}", 100_000, visits) for i in range(3)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            _Line("XLA Ops", 40, visits), _Line("Steps", 1000, visits)]),
    ]
    loaded = tracered.read_planes(planes)
    assert visits == ["XLA Ops"] * 40   # not one of the 300,000, nor Steps
    assert [p["name"] for p in loaded["planes"]] == ["/device:TPU:0"]
    (ops,) = loaded["planes"][0]["lines"]
    assert ops["name"] == "XLA Ops" and len(ops["events"]) == 40
    assert ops["events"][1] == ["f bf16[8]", 10, 5]


def test_idle_gaps_are_named_by_the_covering_host_span(trace):
    reduced = tracered.reduce_trace(trace, {tracered.MARKER_OPEN: 100.0})
    off = reduced["offset_s"]
    dev = reduced["devices"][0]
    spans = [  # host wall clock = trace ns / 1e9 + off
        {"name": "iter", "ts": off + 1000e-9, "dur_s": 4000e-9},
        {"name": "data_wait", "ts": off + 2000e-9, "dur_s": 500e-9},
        {"name": "fetch", "ts": off + 3500e-9, "dur_s": 450e-9},
    ]
    named = tracered.idle_gaps(dev, spans, off)
    assert sorted(g[0] for g in named) == ["data_wait", "fetch"]
    assert all(g[1] == pytest.approx(500e-9) for g in named)
    assert [g[0] for g in tracered.idle_gaps(dev, spans, None)] == [
        "unattributed", "unattributed"]


def test_top_ops_sum_by_short_name(trace):
    reduced = tracered.reduce_trace(trace)
    names = dict(reduced["top_ops"])
    assert names["fusion.1 bf16[8,8]"] == pytest.approx(800e-9)
    assert tracered.short_name(
        "%convert_reduce_fusion.8 = (f32[256]{0}, bf16[128,56,56,256]{3,0}) "
        "fusion(f32[256]{0} %copy-done.326)") == \
        "convert_reduce_fusion.8 bf16[128,56,56,256]"
