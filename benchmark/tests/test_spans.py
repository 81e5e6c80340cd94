"""The span reader on a recorded obs log, and that one stalled iteration
moves both end-to-end metrics."""

import os

import pytest

from benchmark.lib import spans

LOG = os.path.join(os.path.dirname(__file__), "fixtures", "obs_log.jsonl")
WARMUP = 5


@pytest.fixture(scope="module")
def recorded():
    return spans.read_log(LOG)


def test_window_runs_from_the_first_timed_iteration_to_the_fence(recorded):
    iters = [s for s in recorded if s["name"] == "iter"]
    win = spans.window(recorded, WARMUP)
    assert win.steps == len(iters) - WARMUP
    assert win.t_start == iters[WARMUP]["ts"]
    fences = [s["ts"] + s["dur_s"] for s in recorded if s["name"] == "fetch"]
    assert win.t_end == max(fences)
    assert win.t_end >= iters[-1]["ts"] + iters[-1]["dur_s"]
    rate = spans.images_per_second_per_chip(win, global_batch=4, chips=1)
    assert rate == pytest.approx(win.steps * 4 / win.seconds)


def test_no_fence_no_window(recorded):
    unfenced = [s for s in recorded if s["name"] != "fetch"]
    with pytest.raises(ValueError, match="not fenced"):
        spans.window(unfenced, WARMUP)
    with pytest.raises(ValueError, match="no window"):
        spans.window(recorded, 10_000)


def test_percentile_is_numpys():
    np = pytest.importorskip("numpy")
    xs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 50, 95, 100):
        assert spans.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def _stall(recorded, which: int, extra_s: float):
    """The log of the same run had iteration ``which`` stalled for
    ``extra_s``: it lasts longer and everything after it comes later."""
    iters = sorted((s for s in recorded if s["name"] == "iter"),
                   key=lambda s: s["ts"])
    at = iters[which]["ts"]
    out = []
    for s in recorded:
        s = dict(s)
        if s["name"] == "iter" and s["ts"] == at:
            s["dur_s"] += extra_s
        elif s["ts"] > at:
            s["ts"] += extra_s
        out.append(s)
    return out


def test_one_stalled_iteration_moves_both_end_to_end_metrics(recorded):
    # a steady copy of the run: every timed iteration as long as the median
    iters = sorted((s for s in recorded if s["name"] == "iter"),
                   key=lambda s: s["ts"])
    base = spans.window(recorded, WARMUP)
    steady_ms = spans.percentile([s["dur_s"] * 1e3 for s in base.iters], 50)
    stalled = spans.window(_stall(recorded, len(iters) - 3, 2.0), WARMUP)
    assert stalled.steps == base.steps
    assert stalled.seconds == pytest.approx(base.seconds + 2.0)
    # the rate is all images over all seconds: the stall is in it
    r0 = spans.images_per_second_per_chip(base, 4, 1)
    r1 = spans.images_per_second_per_chip(stalled, 4, 1)
    assert r1 == pytest.approx(r0 * base.seconds / (base.seconds + 2.0))
    assert r1 < r0
    # the tail is the tail of all iterations: the stalled one is in it
    assert max(s["dur_s"] for s in stalled.iters) >= 2.0
    assert spans.step_ms_p95(stalled) > spans.step_ms_p95(base)
    assert spans.step_ms_p95(stalled) > steady_ms


def test_before_cuts_the_profiled_tail_off(recorded):
    win = spans.window(recorded, WARMUP)
    cut = win.iters[3]["ts"] + win.iters[3]["dur_s"] + 1e-9
    head = spans.before(win, cut)
    assert head.steps == 4 and head.t_end == cut
    assert all(s["ts"] + s["dur_s"] <= cut for s in head.spans)
