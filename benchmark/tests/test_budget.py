"""Every wait of a traced run is cut from the run's one limit: the budget
on a fake clock, and the clock thread giving up on a profiler that never
lets the loop go on."""

import types

import pytest

from benchmark.lib import drive, tracered


class FakeTime:
    def __init__(self, now=1000.0):
        self.now, self.sleeps = now, 0

    def clock(self):
        return self.now

    def sleep(self, seconds):
        self.sleeps += 1
        self.now += seconds


def budget_at(elapsed_s, fake):
    return drive.TailBudget(fake.now - elapsed_s, clock=fake.clock,
                            sleep=fake.sleep)


def test_the_budget_is_the_limit_less_what_the_run_still_has_to_do():
    fake = FakeTime()
    budget = budget_at(100.0, fake)
    assert budget.to_limit() == pytest.approx(drive.RUN_LIMIT_S - 100.0)
    assert budget.left() == pytest.approx(
        drive.RUN_LIMIT_S - drive.AFTER_TRACE_S - 100.0)
    fake.now += 50.0
    assert budget.left() == pytest.approx(
        drive.RUN_LIMIT_S - drive.AFTER_TRACE_S - 150.0)


def test_a_wait_gives_up_at_its_cap_and_at_the_budgets_end():
    fake = FakeTime()
    budget = budget_at(100.0, fake)   # 140 s left
    t = fake.now
    assert budget.wait(lambda: False, cap_s=4.0) is False
    assert fake.now - t == pytest.approx(4.0, abs=0.01)   # the cap
    # what it waits for comes: no longer than that
    t = fake.now
    assert budget.wait(lambda: fake.now >= t + 1.5, cap_s=4.0) is True
    assert fake.now - t == pytest.approx(1.5, abs=0.01)
    # 2 s before the budget's end a 4 s cap is cut to 2 s
    fake.now += budget.left() - 2.0
    t = fake.now
    assert budget.wait(lambda: False, cap_s=4.0) is False
    assert fake.now - t == pytest.approx(2.0, abs=0.01)
    # a spent budget does not wait at all, whatever the cap
    sleeps = fake.sleeps
    assert budget.left() <= 0.01
    fake.now += 30.0
    assert budget.wait(lambda: False, cap_s=4.0) is False
    assert fake.sleeps == sleeps
    assert budget.wait(lambda: True, cap_s=4.0) is True


def test_the_clock_gives_up_on_a_stall_that_outlasts_its_cap():
    fake = FakeTime()
    traffic = {"trace_read_s": 2.0, "trace_stall_cap_s": 4.0}
    stuck = types.SimpleNamespace(calls=40)   # the step call never returns
    clock = drive._Clock(stuck, 30.0, budget_at(90.0, fake), "trace", traffic)
    t = fake.now
    assert clock._behind_the_stall(calls_at_start=40) is False
    assert fake.now - t == pytest.approx(4.0, abs=0.01)
    assert tracered.MARKER_OPEN not in clock.marker_walls

    # the loop goes on after 3 s, but the marker's result never comes: the
    # wait for it gets what is left of the cap, one second, not a new cap
    class NeverReady:
        def is_ready(self):
            return False

    class Moving:
        calls = property(lambda self: 42 if fake.now >= t2 + 3.0 else 40)

    clock = drive._Clock(Moving(), 30.0, budget_at(90.0, fake), "trace",
                         traffic)
    clock._markers = {tracered.MARKER_OPEN: lambda x: NeverReady()}
    clock._marker_arg = None
    t2 = fake.now
    assert clock._behind_the_stall(calls_at_start=40) is False
    assert fake.now - t2 == pytest.approx(4.0, abs=0.01)

    # and a marker that runs opens the stretch, stamped when it was seen
    class Ready:
        def is_ready(self):
            return True

    clock._markers = {tracered.MARKER_OPEN: lambda x: Ready()}
    t2 = fake.now
    assert clock._behind_the_stall(calls_at_start=40) is True
    assert tracered.MARKER_OPEN in clock.marker_walls
