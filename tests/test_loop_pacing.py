"""The train loop's bounded run-ahead (``dptpu/train/loop.py``,
``MAX_IN_FLIGHT``): the real loop on a stand-in device whose steps land
when the test says, or when the loop waits for them."""

import time

import numpy as np
import pytest

from dptpu import obs
from dptpu.train import loop
from dptpu.train.loop import train_one_epoch
from dptpu.utils.meters import AverageMeter


class Device:
    """Steps land in order. ``speed`` is how many land per iteration of
    the host on their own (0: none unless waited for; 1: every one before
    the next dispatch); a wait lands the step waited for."""

    def __init__(self, speed, wait_s=0.0):
        self.speed, self.wait_s = speed, wait_s
        self.dispatched = 0
        self.landed = 0
        self.credit = 0.0
        self.probes = 0
        self.waits = []        # steps the loop blocked on before a dispatch
        self.fetch_waits = []  # steps a device_get had to wait for
        self.unlanded_at_dispatch = []

    def land_through(self, j):
        self.landed = max(self.landed, j + 1)

    def tick(self):
        self.credit += self.speed
        while self.credit >= 1.0 and self.landed < self.dispatched:
            self.credit -= 1.0
            self.landed += 1
        self.credit = min(self.credit, 1.0)


class Value:
    """A step's metric on the stand-in device. Read (``float``) before
    its step has landed, it raises: ``jax.device_get`` asks for the copy
    first (``copy_to_host_async``), and that is the wait a real fetch of
    an unlanded array makes."""

    def __init__(self, device, step, value):
        self.device, self.step, self.value = device, step, value

    def is_ready(self):
        self.device.probes += 1
        return self.step < self.device.landed

    def block_until_ready(self):
        self.device.waits.append(self.step)
        time.sleep(self.device.wait_s)
        self.device.land_through(self.step)
        return self

    def copy_to_host_async(self):
        if self.step >= self.device.landed:
            self.device.fetch_waits.append(
                (self.step, self.device.dispatched))
            self.device.land_through(self.step)

    def __float__(self):
        assert self.step < self.device.landed, \
            f"step {self.step} was read before it landed"
        return self.value


def _values(steps, seed=0):
    rng = np.random.RandomState(seed)
    return [{"loss": float(rng.rand() * 7), "top1": float(rng.rand() * 100),
             "top5": float(rng.rand() * 100), "n": int(rng.randint(1, 9))}
            for _ in range(steps)]


def _run(values, speed, print_freq=10, stop_after=None, start_step=0,
         wait_s=0.0):
    device = Device(speed, wait_s)

    def train_step(state, batch):
        j = device.dispatched
        device.unlanded_at_dispatch.append(device.dispatched - device.landed)
        device.dispatched += 1
        v = values[j]
        return state, {k: Value(device, j, v[k])
                       for k in ("loss", "top1", "top5")}

    def batches():
        for v in values:
            device.tick()  # the device works while the host turns around
            yield {"images": np.zeros((1,), np.uint8),
                   "labels": np.zeros((v["n"],), np.int32)}

    should_stop = None
    if stop_after is not None:
        should_stop = lambda: device.dispatched >= stop_after  # noqa: E731
    _, stats = train_one_epoch(
        None, train_step, batches(), epoch=0, num_batches=len(values),
        print_freq=print_freq, verbose=False, should_stop=should_stop,
        start_step=start_step)
    return device, stats


@pytest.fixture
def tracer():
    real = obs.set_tracer(obs.Tracer(capacity=4096))
    try:
        yield real
    finally:
        obs.reset()


@pytest.fixture(params=[1, 2, 3])
def depth(request, monkeypatch):
    monkeypatch.setattr(loop, "MAX_IN_FLIGHT", request.param)
    return request.param


def test_the_depth_is_a_constant_of_the_module():
    assert loop.MAX_IN_FLIGHT == 2


# (a) ------------------------------------------------------------------------


@pytest.mark.parametrize("speed", [0.0, 0.4, 0.7])
@pytest.mark.parametrize("print_freq", [1, 2, 10])
def test_never_more_than_the_depth_in_flight(depth, speed, print_freq):
    device, stats = _run(_values(25), speed, print_freq=print_freq)
    assert stats["steps_done"] == 25
    # what a dispatch finds, and with the step it adds
    assert max(device.unlanded_at_dispatch) <= depth - 1
    if speed == 0.0 and print_freq == 10:
        # a device that sets the pace always holds as much as it may:
        # step 0 is fetched by the first display, and from step
        # depth + 1 on the loop waits for step i - depth, once
        assert device.unlanded_at_dispatch[depth:] == \
            [depth - 1] * (25 - depth)
        assert device.waits == list(range(1, 25 - depth))


# (b) ------------------------------------------------------------------------


@pytest.mark.parametrize("print_freq", [1, 2, 10])
def test_a_host_bound_run_never_waits(tracer, depth, print_freq):
    device, _ = _run(_values(25), 1.0, print_freq=print_freq)
    spans = tracer.drain()
    assert device.waits == []
    assert not [s for s in spans if s["name"] == "pace"]
    steps = [s for s in spans if s["name"] == "step"]
    assert len(steps) == 25
    assert all(s["attrs"]["paced"] is False for s in steps)
    assert all(s["attrs"]["inflight"] == 0 for s in steps)


def test_a_wait_is_a_pace_span_between_data_wait_and_step(tracer):
    _run(_values(12), 0.0)
    spans = tracer.drain()
    by = {name: {s["step"]: s for s in spans if s["name"] == name}
          for name in ("data_wait", "pace", "step", "iter")}
    assert sorted(by["pace"]) == list(range(3, 12))
    for i in range(12):
        step = by["step"][i]
        assert step["attrs"]["paced"] is (i in by["pace"])
        assert "attrs" not in by["data_wait"][i]
        if i in by["pace"]:
            pace, wait = by["pace"][i], by["data_wait"][i]
            assert "attrs" not in pace
            # data_wait ends where the wait starts, the step span starts
            # where it ends, and the iteration holds all three
            assert wait["t0"] + wait["dur_s"] == pytest.approx(
                pace["t0"], abs=1e-9)
            assert pace["t0"] + pace["dur_s"] == pytest.approx(
                step["t0"], abs=1e-9)
            it = by["iter"][i]
            assert it["t0"] <= wait["t0"]
            assert step["t0"] + step["dur_s"] <= it["t0"] + it["dur_s"]
    assert obs.SPAN_CATEGORY["pace"] == "device"


def test_the_wait_for_the_device_is_not_billed_to_the_feed():
    device, stats = _run(_values(12), 0.0, wait_s=0.02)
    assert len(device.waits) == 9
    assert stats["batch_time"] >= 0.02 * 9 / 12
    # the Data meter and the starvation share stop before the wait
    assert stats["data_time"] < 0.005 and stats["starvation"] < 0.25


# (c) ------------------------------------------------------------------------


@pytest.mark.parametrize("speed", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("print_freq", [1, 2, 10])
def test_the_display_reads_only_landed_steps(depth, speed, print_freq):
    # Value.__float__ raises on a step that has not landed
    device, stats = _run(_values(31), speed, print_freq=print_freq)
    assert stats["steps_done"] == 31
    if min(depth, print_freq - 1) == depth:
        # the fetch never waits: every step it reads landed before the
        # dispatch of its iteration. Only the first display (step 0, the
        # queue is cold) and the epoch's tail drain wait.
        assert {step for step, _ in device.fetch_waits} <= \
            {0} | set(range(31 - depth, 31))
    else:
        # a print interval shorter than the depth: the fetch is itself a
        # wait, for none but the newest steps, which may be in flight
        assert all(step >= dispatched - depth
                   for step, dispatched in device.fetch_waits)
        if speed == 0.0:
            assert device.fetch_waits


# (d) ------------------------------------------------------------------------


@pytest.mark.parametrize("stop_after", [None, 7, 11])
@pytest.mark.parametrize("speed", [0.0, 1.0])
def test_the_epoch_tail_fetch_closes_the_epoch(tracer, speed, stop_after):
    device, stats = _run(_values(25), speed, stop_after=stop_after)
    done = 25 if stop_after is None else stop_after
    assert stats["steps_done"] == done
    assert stats["preempted"] is (stop_after is not None)
    spans = tracer.drain()
    last_iter = max((s for s in spans if s["name"] == "iter"),
                    key=lambda s: s["t0"])
    tail = spans[-1]
    assert tail["name"] == "fetch" and tail["step"] == done - 1
    assert tail["t0"] >= last_iter["t0"] + last_iter["dur_s"]
    # everything has landed once the epoch returns
    assert device.landed == device.dispatched == done


# (e) ------------------------------------------------------------------------


def _unpaced_reference(values):
    meters = {k: AverageMeter(k) for k in ("loss", "top1", "top5")}
    for v in values:
        for k, m in meters.items():
            m.update(v[k], v["n"])
    return {k: m.avg for k, m in meters.items()}


@pytest.mark.parametrize("speed", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("print_freq", [1, 2, 10])
@pytest.mark.parametrize("stop_after", [None, 13])
def test_the_averages_are_those_of_an_unpaced_loop(depth, speed, print_freq,
                                                   stop_after):
    values = _values(29, seed=print_freq)
    _, stats = _run(values, speed, print_freq=print_freq,
                    stop_after=stop_after, start_step=3)
    done = 29 if stop_after is None else stop_after
    want = _unpaced_reference(values[:done])
    assert stats["steps_done"] == 3 + done
    for k in ("loss", "top1", "top5"):
        assert stats[k] == want[k]  # bit for bit


# (f) ------------------------------------------------------------------------


@pytest.mark.parametrize("speed", [0.0, 1.0])
def test_untraced_the_bound_costs_one_probe_an_iteration(monkeypatch, speed):
    assert isinstance(obs.get_tracer(), obs.NullTracer)
    asked = []
    real = time.thread_time
    monkeypatch.setattr(time, "thread_time",
                        lambda: asked.append(1) or real())
    device, _ = _run(_values(40), speed)
    assert asked == []
    assert 0 < device.probes <= 40


# the report ----------------------------------------------------------------


def _span(name, t0, dur_s, step, attrs=None):
    s = {"name": name, "ts": 1000.0 + t0, "t0": t0, "dur_s": dur_s,
         "step": step, "tid": 1}
    if attrs is not None:
        s["attrs"] = attrs
    return s


def test_the_report_prints_paced_beside_in_flight():
    spans, t = [], 0.0
    for i in range(8):
        paced = i >= 2
        wait = 0.0 if not paced else 1.0 if i == 6 else 0.08
        spans.append(_span("data_wait", t, 0.002, i))
        if paced:
            spans.append(_span("pace", t + 0.002, wait, i))
        spans.append(_span("step", t + 0.002 + wait, 0.06, i, attrs={
            "cpu_s": 0.02, "inflight": 1 if paced else 0, "paced": paced,
            "input_ready": True}))
        spans.append(_span("iter", t, wait + 0.065, i,
                           attrs={"cpu_s": 0.03}))
        t += wait + 0.07
    rep = obs.attribute_epoch(spans, wall_s=2.2, anomaly_x=3.0)
    assert rep["step_call"]["paced_pct"] == pytest.approx(75.0)
    # the wait is the device's time, like the step call and the fetch
    assert rep["device_s"] == pytest.approx(8 * 0.06 + 5 * 0.08 + 1.0)
    (a,) = rep["anomalous_steps"]
    assert a["step"] == 6 and a["step_call"]["paced"] is True
    assert a["phases"]["device"] == pytest.approx(1.06)
    text = obs.format_report(rep, epoch=1)
    assert "in flight p50 1, paced 75%, input ready 100%" in text
    assert "inflight=1 paced=True" in text
