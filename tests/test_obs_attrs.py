"""Spans that carry counts and states (``attrs``): the tracer and its
sinks, what the train loop, the feed and the compile listener record on
them, ``fit()``'s set-up phases, and the report lines that read them."""

import concurrent.futures
import gc
import json
import os
import threading
import time

import numpy as np
import pytest

from dptpu import obs
from dptpu.data.loader import DataLoader, DevicePrefetcher
from dptpu.train.loop import train_one_epoch


@pytest.fixture
def tracer():
    real = obs.set_tracer(obs.Tracer(capacity=4096))
    try:
        yield real
    finally:
        obs.reset()


# ------------------------------------------------------- tracer + sinks ----


def test_attrs_round_trip_to_jsonl_and_chrome_args(tmp_path):
    t = obs.Tracer(capacity=16)
    attrs = {"cpu_s": 0.25, "inflight": 3, "input_ready": True, "event": "x"}
    t.record("step", 10.0, 0.5, step=7, attrs=attrs)
    assert t.snapshot()[0]["attrs"] == attrs
    spans = t.drain()
    assert spans[0]["attrs"] == attrs and t.drain() == []
    sink = obs.TraceSink(str(tmp_path))
    sink.add_spans(spans)
    sink.close()
    (line,) = open(sink.jsonl_path).read().splitlines()
    rec = json.loads(line)
    assert rec["attrs"] == attrs and rec["step"] == 7
    (x,) = [e for e in json.load(open(sink.chrome_path))["traceEvents"]
            if e["ph"] == "X"]
    assert x["args"] == {"step": 7, **attrs}


def test_a_record_without_attrs_is_written_exactly_as_before(tmp_path):
    t = obs.Tracer(capacity=16)
    t.record("data_wait", 10.0, 0.5, step=2)
    (span,) = t.drain()
    assert set(span) == {"name", "ts", "t0", "dur_s", "step", "tid"}
    sink = obs.TraceSink(str(tmp_path))
    sink.add_spans([span])
    sink.close()
    (line,) = open(sink.jsonl_path).read().splitlines()
    # the five keys and the kind, in the order they always had
    assert line == json.dumps({
        "name": "data_wait", "ts": span["ts"], "dur_s": 0.5, "step": 2,
        "tid": span["tid"], "kind": "span"})
    (x,) = obs.spans_to_chrome_events([span])[1:]
    assert x["args"] == {"step": 2}


def test_reanchor_moves_later_spans_only(monkeypatch):
    t = obs.Tracer(capacity=16)
    t0 = time.perf_counter()
    t.record("a", t0, 0.1)
    before = t.snapshot()[0]["ts"]
    # the wall clock was stepped by an hour (or perf_counter drifted)
    wall = time.time
    monkeypatch.setattr(time, "time", lambda: wall() + 3600.0)
    t.reanchor()
    t.record("b", t0, 0.1)
    a, b = t.drain()
    assert a["ts"] == before  # converted with the anchor it was taken under
    assert b["ts"] - a["ts"] == pytest.approx(3600.0, abs=1.0)


def test_null_tracer_takes_and_drops_attrs():
    t = obs.NullTracer()
    t.record("x", 0.0, 1.0, step=1, attrs={"a": 1})
    t.reanchor()
    assert t.snapshot() == [] and t.drain() == [] and not t.enabled


@pytest.mark.parametrize("written", [{"bytes": 7, "store_s": 0.5}, {}],
                         ids=["filled", "left-empty"])
def test_a_span_records_what_the_work_inside_wrote_into_its_attrs(written):
    """``with tracer.span(...) as span``: the work measures itself into
    ``span.attrs`` (a save's bytes and parts); left empty the record has
    none; the null tracer's span takes the writes and drops them."""
    t = obs.Tracer(capacity=8)
    with t.span("ckpt", step=3) as span:
        span.attrs.update(written)
    (rec,) = t.snapshot()
    assert (rec["name"], rec["step"]) == ("ckpt", 3)
    assert rec.get("attrs") == (written or None)
    with obs.NullTracer().span("ckpt") as span:
        span.attrs.update(written)
        assert span.attrs == {}


# ------------------------------------------------------------- the loop ----


class FakeArray:
    """A device value whose landing the test decides; the loop's wait for
    it (the bounded run-ahead) makes it land."""

    calls = 0

    def __init__(self, landed, value=1.0):
        self._landed = landed
        self._value = value
        self._waited_for = False

    def is_ready(self):
        FakeArray.calls += 1
        return self._waited_for or bool(self._landed())

    def block_until_ready(self):
        self._waited_for = True
        return self

    def __float__(self):
        return self._value


def _run_loop(steps, lag, input_ready=True, step_sleep=0.0):
    """``steps`` iterations of the real loop on a fake step: the loss of
    step j has landed at the entry of step k iff ``j < k - lag``."""
    dispatched = [0]

    def train_step(state, batch):
        j = dispatched[0]
        dispatched[0] += 1
        if step_sleep:
            time.sleep(step_sleep)
        landed = lambda: j < dispatched[0] - lag  # noqa: E731
        return state, {"loss": FakeArray(landed), "top1": FakeArray(landed),
                       "top5": FakeArray(landed)}

    def batches():
        for _ in range(steps):
            yield {"images": FakeArray(lambda: input_ready),
                   "labels": np.zeros((4,), np.int32)}

    return train_one_epoch(None, train_step, batches(), epoch=0,
                           num_batches=steps, print_freq=100, verbose=False)


@pytest.mark.parametrize("lag,expected,paced_from", [
    (0, [0, 0, 0, 0, 0, 0], None),   # every earlier step has landed
    (1, [0, 0, 1, 1, 1, 1], None),   # the device runs one behind
    # two behind, or nothing lands unless waited for: the loop waits for
    # step i - 2 from step 3 on (MAX_IN_FLIGHT = 2) and the dispatch
    # finds one step in flight, never the whole queue
    (2, [0, 0, 1, 1, 1, 1], 3),
    (10**6, [0, 0, 1, 1, 1, 1], 3),
])
def test_step_span_counts_steps_in_flight(tracer, lag, expected, paced_from):
    # print_freq 100: the first display fetches step 0, later steps queue
    _run_loop(6, lag)
    steps = [s for s in tracer.drain() if s["name"] == "step"]
    assert [s["step"] for s in steps] == list(range(6))
    assert [s["attrs"]["inflight"] for s in steps] == expected
    assert [s["attrs"]["paced"] for s in steps] == [
        paced_from is not None and i >= paced_from for i in range(6)]


@pytest.mark.parametrize("landed", [True, False])
def test_step_span_says_whether_the_input_had_landed(tracer, landed):
    _run_loop(3, 0, input_ready=landed)
    steps = [s for s in tracer.drain() if s["name"] == "step"]
    assert [s["attrs"]["input_ready"] for s in steps] == [landed] * 3


def test_cpu_seconds_fit_inside_their_spans(tracer):
    _run_loop(3, 0, step_sleep=0.02)
    spans = tracer.drain()
    tick = 0.0101  # thread_time may tick in 10 ms steps
    for name in ("step", "iter"):
        found = [s for s in spans if s["name"] == name]
        assert len(found) == 3
        for s in found:
            assert 0.0 <= s["attrs"]["cpu_s"] <= s["dur_s"] + tick
    # a step that sleeps is blocked, not on the CPU
    for s in spans:
        if s["name"] == "step":
            assert s["dur_s"] >= 0.02
            assert s["dur_s"] - s["attrs"]["cpu_s"] >= 0.02 - tick
    assert all("attrs" not in s for s in spans
               if s["name"] in ("data_wait", "fetch"))


def test_with_tracing_off_the_loop_asks_for_nothing(monkeypatch):
    assert isinstance(obs.get_tracer(), obs.NullTracer)
    asked = {"thread_time": 0}
    real = time.thread_time

    def counting():
        asked["thread_time"] += 1
        return real()

    monkeypatch.setattr(time, "thread_time", counting)
    FakeArray.calls = 0
    _run_loop(5, 2)
    # untraced: no clock of the thread's own, and the one probe an
    # iteration that bounds the run-ahead (steps 3 and 4 here)
    assert asked["thread_time"] == 0 and FakeArray.calls == 2
    obs.set_tracer(obs.Tracer(capacity=256))
    try:
        _run_loop(5, 2)
    finally:
        obs.reset()
    # traced: four clock reads an iteration (one more as the epoch ends),
    # one look at the input, one at each step back to the first landed
    assert asked["thread_time"] == 4 * 5 + 1
    assert FakeArray.calls > 5


def test_attrs_cost_microseconds_an_iteration(monkeypatch):
    """The budget is 50 us of loop-thread time an iteration: measured as
    the difference in the loop thread's own CPU time between the traced
    and the untraced loop on a step that does nothing (CPU time, not
    wall time: another test's load on the machine is not in it; nor is
    the garbage collector's walk over whatever heap the process has)."""
    n = 2000
    # the ring's lock as production builds it: the suite runs with the
    # lock-order checker on, which makes every record ten times dearer
    monkeypatch.setenv("DPTPU_SYNC_CHECK", "0")

    def timed(traced):
        if traced:
            obs.set_tracer(obs.Tracer(capacity=8 * n))
        # the collector off: what it costs to walk the heap of a process
        # that has run half the suite is not the tracer's
        gc.collect()
        gc.disable()
        try:
            c0 = time.thread_time()
            _run_loop(n, 2)
            return (time.thread_time() - c0) / n
        finally:
            gc.enable()
            obs.reset()

    timed(True)  # warm both paths
    off = min(timed(False) for _ in range(3))
    on = min(timed(True) for _ in range(3))
    # all the spans of the iteration AND their attributes (measured 6-9
    # us on the sandbox, of which the attributes are 3-6)
    assert on - off < 50e-6, (on, off)


# ------------------------------------------------------------- the feed ----


class RowSet:
    """A dataset of constant rows; row ``stall`` waits for ``gate``."""

    def __init__(self, n=24, stall=None):
        self.n, self.stall = n, stall
        self.gate = threading.Event()

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.stall:
            assert self.gate.wait(5.0)
        return np.full((8, 8, 3), i % 251, np.uint8), i % 10


def test_collect_and_h2d_carry_the_consuming_step(tracer):
    loader = DataLoader(RowSet(), batch_size=4, num_workers=2)
    try:
        put = lambda b: dict(b)  # noqa: E731
        got = list(DevicePrefetcher(loader.epoch(0, start_batch=2), put,
                                    first_step=2))
    finally:
        loader.close()
    assert len(got) == 4  # batches 2..5 of six
    spans = tracer.drain()
    collects = [s for s in spans if s["name"] == "collect"]
    h2ds = [s for s in spans if s["name"] == "h2d"]
    assert [s["step"] for s in collects] == [2, 3, 4, 5]
    assert [s["step"] for s in h2ds] == [2, 3, 4, 5]
    for s in collects:
        a = s["attrs"]
        assert a["rows"] == 4 and isinstance(a["ready"], bool)
        assert a["workers"] == loader.num_workers
        assert 0.0 <= a["cpu_s"] <= a["wall_s"] + 0.0101 * 2
    row_bytes = 4 * 8 * 8 * 3 + 4 * 4  # uint8 images + int32 labels
    assert [s["attrs"]["bytes"] for s in h2ds] == [row_bytes] * 4


@pytest.mark.parametrize("stalled", [False, True])
def test_collect_says_whether_the_workers_were_done(tracer, stalled):
    ds = RowSet(stall=5 if stalled else None)
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    try:
        loader._item_shape = (8, 8, 3)
        item = loader._submit_batch(np.arange(4, 8), 0)
        if stalled:
            # one worker waits on row 5 until after the loop has come
            threading.Timer(0.05, ds.gate.set).start()
        else:
            concurrent.futures.wait(item[0], timeout=5.0)
        batch = loader._finalize(*item, step=1)
    finally:
        ds.gate.set()
        loader.close()
    assert batch["labels"].tolist() == [4, 5, 6, 7]
    (span,) = [s for s in tracer.drain() if s["name"] == "collect"]
    assert span["step"] == 1 and span["attrs"]["rows"] == 4
    assert span["attrs"]["ready"] is (not stalled)
    if stalled:
        # the stalled worker's wait is wall time, not CPU time
        assert span["dur_s"] >= 0.03
        assert span["attrs"]["wall_s"] - span["attrs"]["cpu_s"] >= 0.03


def test_process_mode_collect_reads_what_the_acks_carry(tracer):
    from dptpu.data.dataset import SyntheticDataset

    ds = SyntheticDataset(num_samples=16, image_size=8, num_classes=4)
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        workers_mode="process")
    try:
        got = list(loader.epoch(0, start_batch=1))
    finally:
        loader.close()
    assert len(got) == 3
    collects = [s for s in tracer.drain() if s["name"] == "collect"]
    assert [s["step"] for s in collects] == [1, 2, 3]
    for s in collects:
        a = s["attrs"]
        # the workers' acks carry their own wall and CPU seconds: the
        # thread path's attributes exactly
        assert set(a) == {"rows", "ready", "workers", "cpu_s", "wall_s"}
        assert a["rows"] == 4 and isinstance(a["ready"], bool)
        assert a["workers"] == 2  # the pool's size, beside what it made
        assert a["wall_s"] > 0.0 and 0.0 <= a["cpu_s"] <= a["wall_s"] + 1e-3


def test_untraced_feed_times_nothing():
    loader = DataLoader(RowSet(), batch_size=4, num_workers=2)
    try:
        loader._item_shape = (8, 8, 3)
        futs = loader._submit_batch(np.arange(4), 0)[0]
        assert [f.result() for f in futs] == [None, None]
    finally:
        loader.close()


# ------------------------------------------------------ compile listener ----


def test_compile_listener_spans_a_new_function_once(tracer):
    import jax
    import jax.numpy as jnp

    from dptpu.utils import compile_cache

    compile_cache.install_compile_listener()
    assert compile_cache.install_compile_listener() is False  # once only
    x = jnp.ones((3,))
    tracer.drain()

    def fresh_listener_target(v):
        return v * 3.0 + 1.0

    f = jax.jit(fresh_listener_target)
    t0 = time.perf_counter()
    f(x).block_until_ready()
    t1 = time.perf_counter()
    spans = [s for s in tracer.drain() if s["name"] == "compile"]
    backend = [s for s in spans if s["attrs"]["event"] == "backend_compile"
               and "fresh_listener_target" in s["attrs"].get("fun", "")]
    assert len(backend) == 1
    assert {s["attrs"]["event"] for s in spans} == {
        "jaxpr_trace", "jaxpr_to_mlir", "backend_compile"}
    for s in spans:  # t0 = now - seconds: inside the call that compiled
        assert t0 - 1e-3 <= s["t0"] and s["t0"] + s["dur_s"] <= t1 + 1e-3
        assert s["tid"] == threading.get_ident()
    f(x).block_until_ready()  # served from jit's own cache: no event
    assert [s for s in tracer.drain() if s["name"] == "compile"] == []


def test_compile_listener_is_inert_outside_a_run():
    import jax
    import jax.numpy as jnp

    from dptpu.utils import compile_cache

    compile_cache.install_compile_listener()
    assert isinstance(obs.get_tracer(), obs.NullTracer)
    jax.jit(lambda v: v - 7.0)(jnp.ones((2,))).block_until_ready()
    assert obs.get_tracer().drain() == []


# ------------------------------------------------------- fit()'s set-up ----


@pytest.fixture(scope="module")
def fit_log(tmp_path_factory):
    """Two tiny ``fit()`` runs in one process, their span log, and how
    often the compile listener registered."""
    import jax.monitoring as monitoring

    from dptpu.config import Config
    from dptpu.train import fit
    from dptpu.utils import compile_cache

    registered = []
    real = monitoring.register_event_duration_secs_listener
    monitoring.register_event_duration_secs_listener = (
        lambda cb: registered.append(cb) or real(cb))
    d = tmp_path_factory.mktemp("fit_setup")
    cwd = os.getcwd()
    os.chdir(d)
    os.environ["DPTPU_OBS_DIR"] = str(d / "obs")
    was_listening = compile_cache._listening
    try:
        cfg = dict(data="synthetic:96", arch="resnet18", epochs=1,
                   batch_size=24, lr=0.02, workers=2, print_freq=100,
                   seed=1, gpu=0)
        results = [fit(Config(**cfg), image_size=32, verbose=False)
                   for _ in range(2)]
    finally:
        monitoring.register_event_duration_secs_listener = real
        os.environ.pop("DPTPU_OBS_DIR", None)
        os.chdir(cwd)
    (log,) = [p for p in os.listdir(d / "obs") if p.endswith(".jsonl")]
    recs = [json.loads(line) for line in open(d / "obs" / log)]
    ours = [cb for cb in registered
            if getattr(cb, "__module__", "") == compile_cache.__name__]
    return {"recs": recs, "results": results,
            "registrations": len(ours) + (1 if was_listening else 0)}


def test_setup_phases_are_consecutive_and_end_before_the_loop(fit_log):
    recs = fit_log["recs"]
    # the first run's records: up to its set-up report
    first_report = next(i for i, r in enumerate(recs)
                        if r["kind"] == "setup_report")
    setup = [r for r in recs[:first_report] if r["kind"] == "span"
             and r["name"].startswith("setup.")]
    names = [r["name"] for r in setup]
    assert names[:3] == ["setup.knobs_mesh", "setup.data",
                         "setup.model_init"]
    assert {"setup.state_commit", "setup.step_build"} <= set(names)
    assert "setup.pretrained" not in names  # not a --pretrained run
    for a, b in zip(setup, setup[1:]):  # each starts where the last ended
        assert b["ts"] == pytest.approx(a["ts"] + a["dur_s"], abs=1e-4)
    first_iter = min(r["ts"] for r in recs if r["kind"] == "span"
                     and r["name"] == "iter")
    assert setup[-1]["ts"] + setup[-1]["dur_s"] <= first_iter
    # model.init's many small programs show as compile spans inside it
    init = next(r for r in setup if r["name"] == "setup.model_init")
    inside = [r for r in recs[:first_report] if r["kind"] == "span"
              and r["name"] == "compile"
              and init["ts"] <= r["ts"] <= init["ts"] + init["dur_s"]]
    assert len(inside) > 10
    report = recs[first_report]
    assert [p["phase"] for p in report["phases"]][:3] == [
        "knobs_mesh", "data", "model_init"]
    assert report["total_s"] == pytest.approx(
        sum(r["dur_s"] for r in setup), abs=0.01)
    assert next(p for p in report["phases"]
                if p["phase"] == "model_init")["compiles"] > 10


def test_epoch_attribution_sees_only_its_own_spans(fit_log):
    for result in fit_log["results"]:
        rep = result["history"][0]["obs"]
        # set-up left the ring at loop entry: its seconds are not billed
        # to the epoch, whose coverage invariant holds
        assert 0.95 <= rep["coverage"] <= 1.001
        assert rep["compile_s"] > 0  # the step's own compile, in step 0
        assert rep["step_call"]["input_ready_pct"] >= 0
        assert rep["feed"]["ready_pct"] >= 0
    spans = [r for r in fit_log["recs"] if r["kind"] == "span"]
    for name in ("step", "iter", "collect", "h2d"):
        assert all("attrs" in s for s in spans if s["name"] == name), name
    # one listener for the process, however many runs
    assert fit_log["registrations"] == 1
    assert isinstance(obs.get_tracer(), obs.NullTracer)


def test_a_failed_knob_leaves_no_tracer_behind(monkeypatch):
    from dptpu.config import Config
    from dptpu.train import fit

    monkeypatch.setenv("DPTPU_WARMUP_POLY", "-1")
    with pytest.raises(ValueError, match="DPTPU_WARMUP_POLY"):
        fit(Config(data="synthetic:8", arch="resnet18", epochs=1,
                   batch_size=4), image_size=32, verbose=False)
    assert isinstance(obs.get_tracer(), obs.NullTracer)


# --------------------------------------------------------------- report ----


def _span(name, t0, dur, step=-1, tid=1, attrs=None):
    s = {"name": name, "ts": t0, "t0": t0, "dur_s": dur, "step": step,
         "tid": tid}
    if attrs is not None:
        s["attrs"] = attrs
    return s


def _epoch_spans():
    spans = []
    for i in range(8):
        t = 4.0 * i
        slow = i == 5
        spans.append(_span("collect", t + 0.001, 0.002, step=i + 1, attrs={
            "ready": i % 4 != 0, "rows": 10, "cpu_s": 0.004,
            "wall_s": 0.01}))
        spans.append(_span("data_wait", t, 0.01, step=i))
        if slow:
            spans.append(_span("compile", t + 0.2, 3.0, attrs={
                "event": "backend_compile", "fun": "jit(step)",
                "cache_hit": False}))
        spans.append(_span("step", t + 0.01, 3.5 if slow else 0.06, step=i,
                           attrs={"cpu_s": 0.02, "inflight": i % 3,
                                  "input_ready": i != 2}))
        spans.append(_span("iter", t, 3.6 if slow else 0.08, step=i,
                           attrs={"cpu_s": 0.03}))
    return spans


def test_epoch_report_reads_the_attributes():
    rep = obs.attribute_epoch(_epoch_spans(), wall_s=33.0, anomaly_x=3.0)
    call, feed = rep["step_call"], rep["feed"]
    assert call["p50_ms"] == pytest.approx(60.0)
    assert call["cpu_ms"] == pytest.approx(20.0)
    assert call["blocked_ms"] == pytest.approx(
        (7 * 60.0 + 3500.0) / 8 - 20.0)
    assert call["inflight_p50"] == pytest.approx(1.0)
    assert call["input_ready_pct"] == pytest.approx(87.5)
    assert feed == {"ready_pct": 75.0, "row_cpu_us": 400.0,
                    "row_wall_us": 1000.0}
    # the compile inside the step call is its own category, not device
    assert rep["compile_s"] == pytest.approx(3.0)
    assert rep["device_s"] == pytest.approx(7 * 0.06 + 0.5)
    (a,) = rep["anomalous_steps"]
    assert a["step"] == 5
    assert a["step_call"] == {"cpu_s": 0.02, "inflight": 2,
                              "input_ready": True}
    assert a["compiles"]["count"] == 1
    assert a["compiles"]["longest"][0]["fun"] == "jit(step)"
    text = obs.format_report(rep, epoch=3)
    assert "step call p50 60.0ms" in text and "in flight p50 1" in text
    assert "feed ready 75%, cpu 400us/row, wall 1000us/row" in text
    assert "compile 3.00s" in text
    assert "inflight=2" in text and "jit(step) 3.000s" in text


def test_report_without_attributes_prints_what_it_always_did():
    spans = [s for s in _epoch_spans() if s["name"] != "compile"]
    for s in spans:
        s.pop("attrs", None)
    rep = obs.attribute_epoch(spans, wall_s=33.0)
    assert "step_call" not in rep and "feed" not in rep
    text = obs.format_report(rep, epoch=0)
    assert "step call" not in text and "compile" not in text
    assert len(text.splitlines()) == 2 + len(rep["anomalous_steps"])


def test_setup_report_counts_compiles_by_phase():
    spans = [
        _span("setup.knobs_mesh", 0.0, 1.0),
        _span("setup.model_init", 1.0, 10.0),
        _span("setup.state_commit", 11.0, 1.0),
        _span("setup.step_build", 12.0, 0.5),
        _span("setup.state_commit", 12.5, 2.0),
    ]
    for k in range(4):  # four small programs: traced, lowered, compiled
        t = 2.0 + k
        spans.append(_span("compile", t, 0.1, attrs={
            "event": "jaxpr_trace", "fun": "op"}))
        spans.append(_span("compile", t + 0.1, 0.4, attrs={
            "event": "backend_compile", "fun": "jit(op)"}))
    spans.append(_span("compile", 13.0, 1.5, attrs={
        "event": "backend_compile", "fun": "jit(put)", "cache_hit": True}))
    rep = obs.setup_report(spans)
    assert rep["total_s"] == pytest.approx(14.5)
    rows = {p["phase"]: p for p in rep["phases"]}
    assert list(rows) == ["knobs_mesh", "model_init", "state_commit",
                          "step_build"]
    assert rows["model_init"] == {
        "phase": "model_init", "s": 10.0, "compile_s": 2.0, "compiles": 4,
        "compiles_under_1s": 4, "compiles_under_1s_s": 1.6,
        "cache_hits": 0}
    assert rows["state_commit"]["s"] == pytest.approx(3.0)  # entered twice
    assert rows["state_commit"]["compiles"] == 1
    assert rows["state_commit"]["cache_hits"] == 1
    line = obs.format_setup(rep)
    assert line.startswith("=> set-up: 14.5s to loop entry | knobs_mesh 1.0s")
    assert "model_init 10.0s (compile 2.0s in 4, 4 under 1s: 1.6s)" in line
    assert "\n" not in line


def test_profiler_sessions_open_with_the_host_tracers_off(tmp_path,
                                                         monkeypatch):
    import jax

    from dptpu.utils.profiling import device_profile_options

    opts = device_profile_options()
    assert opts.host_tracer_level == 0 and opts.python_tracer_level == 0
    seen = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda path, **kw: seen.append(kw.get("profiler_options")))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    trig = obs.ProfileTrigger(str(tmp_path), trace_steps=1, verbose=False)
    trig.arm()
    trig.tick(0)
    trig.uninstall()
    (used,) = seen
    assert used.host_tracer_level == 0 and used.python_tracer_level == 0
