"""The trace reduction on a small recorded trace: busy time as a union,
idle share, exposed collective time on two overlapping tracks, and the
idle gaps named by the host span that covered them."""

import json
import os

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
    # three steps of 1000 ns busy (the nested event counted once), 500 ns
    # of waiting between them; the stretch ends with the last whole step
    assert dev["steps"] == 3
    assert dev["busy_ns"] == 3000
    assert dev["window_ns"] == 4000
    reduced = tracered.reduce_trace(trace, [10.0, 10.0000051])
    assert reduced["busy_s"] == pytest.approx(3000e-9)
    assert reduced["window_s"] == pytest.approx(4000e-9)
    assert 1.0 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.25)


def test_collective_exposure_counts_only_what_no_compute_covers(trace):
    dev = tracered.reduce_device(trace["planes"][0], (900, 6000))
    # per step the all-reduce spans 400 ns: 300 under fusion.2, 100 exposed
    # (the core's all-reduce-done), so 3 x 100
    assert dev["has_collectives"]
    assert dev["collective_exposed_ns"] == 300


def test_anchors_bound_the_stretch_and_align_the_clocks(trace):
    assert tracered.anchor_starts(trace) == [900, 6000]
    # bounds that end inside the third step leave two whole steps
    dev = tracered.reduce_device(trace["planes"][0], (900, 4200))
    assert dev["steps"] == 2 and dev["window_ns"] == 2500
    reduced = tracered.reduce_trace(trace, [100.0, 100.0000051])
    assert reduced["offset_s"] == pytest.approx(100.0 - 900e-9)
    assert tracered.reduce_trace(trace, [])["offset_s"] is None


def test_anchors_that_hold_no_whole_step_fall_back_to_the_whole_trace(trace):
    stalled = json.loads(json.dumps(trace))
    host = next(p for p in stalled["planes"] if p["name"] == "/host:CPU")
    host["lines"][0]["events"] = [["bench_anchor", 2100, 10],
                                  ["bench_anchor", 2400, 10]]  # in a gap
    reduced = tracered.reduce_trace(stalled, [5.0, 5.0000003])
    assert reduced["stretch"] == "the whole trace"
    assert reduced["steps"] == 3 and reduced["busy_s"] > 0
    assert tracered.reduce_trace(trace, [])["stretch"] == "between the anchors"


def test_idle_gaps_are_named_by_the_covering_host_span(trace):
    reduced = tracered.reduce_trace(trace, [100.0, 100.0000051])
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
    reduced = tracered.reduce_trace(trace, [])
    names = dict(reduced["top_ops"])
    assert names["fusion.1 bf16[8,8]"] == pytest.approx(1200e-9)
    assert tracered.short_name(
        "%convert_reduce_fusion.8 = (f32[256]{0}, bf16[128,56,56,256]{3,0}) "
        "fusion(f32[256]{0} %copy-done.326)") == \
        "convert_reduce_fusion.8 bf16[128,56,56,256]"
