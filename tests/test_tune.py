"""The self-tuning control plane (ISSUE 19): artifact precedence, the
fail-fast DPTPU_TUNE_* knobs, and BOUNDED actuation for all three
online controllers — each loop must be rate-limited, monotonic (no
reverse actuation exists, so oscillation is structurally impossible),
budget-capped, and cleanly disarmable."""

import json
import os

import pytest

from dptpu.tune.artifact import (
    ACTUATOR_NAMES,
    TUNABLE_KNOBS,
    TuningError,
    apply_tuning,
    load_tuning,
    save_tuning,
    tune_knobs,
)
from dptpu.tune.controller import (
    Actuator,
    Controller,
    decode_ahead_actuator,
    host_lost_actuator,
    serve_ladder_actuator,
)

HOST = {"platform": "test", "cpu_count": 4}


# ---------------------------------------------------------- artifact ----


def _write(tmp_path, knobs, **kw):
    path = str(tmp_path / "TUNING.json")
    save_tuning(path, knobs, kw.get("objective", {"o": 1}),
                kw.get("probes", {}), host=kw.get("host", HOST))
    return path


def test_save_load_roundtrip(tmp_path):
    path = _write(tmp_path, {"DPTPU_BUCKET_MB": "2",
                             "DPTPU_DECODE_AHEAD": "8"})
    rec = load_tuning(path)
    assert rec["knobs"] == {"DPTPU_BUCKET_MB": "2",
                            "DPTPU_DECODE_AHEAD": "8"}
    assert rec["schema"] == "dptpu-tuning-v1"
    assert len(rec["crc32"]) == 8


def test_save_refuses_untunable_knob(tmp_path):
    with pytest.raises(TuningError, match="DPTPU_OBS"):
        _write(tmp_path, {"DPTPU_OBS": "1"})


def test_load_missing_names_retune(tmp_path):
    with pytest.raises(TuningError, match="dptpu tune --out"):
        load_tuning(str(tmp_path / "absent.json"))


def test_load_rejects_tamper(tmp_path):
    path = _write(tmp_path, {"DPTPU_BUCKET_MB": "2"})
    rec = json.load(open(path))
    rec["knobs"]["DPTPU_BUCKET_MB"] = "999"  # hand-edit
    json.dump(rec, open(path, "w"))
    with pytest.raises(TuningError, match="CRC"):
        load_tuning(path)


def test_load_rejects_wrong_schema(tmp_path):
    path = str(tmp_path / "t.json")
    json.dump({"schema": "something-else"}, open(path, "w"))
    with pytest.raises(TuningError, match="schema"):
        load_tuning(path)


def test_apply_injects_only_unset(tmp_path):
    path = _write(tmp_path, {"DPTPU_BUCKET_MB": "2",
                             "DPTPU_DECODE_AHEAD": "8"})
    env = {"DPTPU_DECODE_AHEAD": "16"}  # the operator's hand
    out = apply_tuning(path, environ=env, log=None)
    assert env["DPTPU_BUCKET_MB"] == "2"
    assert env["DPTPU_DECODE_AHEAD"] == "16"  # explicit env wins
    assert out["applied"] == {"DPTPU_BUCKET_MB": "2"}
    assert "DPTPU_DECODE_AHEAD" in out["overridden"]


def test_apply_respects_cli_set(tmp_path):
    """A knob whose CLI twin was explicitly given never gets the tuned
    value — the serve --buckets / fit --accum-steps precedence."""
    path = _write(tmp_path, {"DPTPU_SERVE_BUCKETS": "1,2,4",
                             "DPTPU_BUCKET_MB": "2"})
    env = {}
    out = apply_tuning(path, cli_set={"DPTPU_SERVE_BUCKETS"},
                       environ=env, log=None)
    assert "DPTPU_SERVE_BUCKETS" not in env
    assert out["overridden"]["DPTPU_SERVE_BUCKETS"] == "explicit CLI flag"
    assert env["DPTPU_BUCKET_MB"] == "2"


def test_apply_banner_names_every_decision(tmp_path):
    path = _write(tmp_path, {"DPTPU_BUCKET_MB": "2",
                             "DPTPU_DECODE_AHEAD": "8"})
    lines = []
    apply_tuning(path, environ={"DPTPU_DECODE_AHEAD": "4"},
                 log=lambda s: lines.append(s))
    banner = "\n".join(lines)
    assert "applied DPTPU_BUCKET_MB=2" in banner
    assert "kept explicit DPTPU_DECODE_AHEAD" in banner
    assert "crc" in banner


# ------------------------------------------------------ tune_knobs ------


def test_tune_knobs_defaults():
    conf = tune_knobs({})
    assert conf == {"artifact": "", "control": (), "interval_s": 10.0}


def test_tune_knobs_control_all():
    conf = tune_knobs({"DPTPU_TUNE_CONTROL": "all"})
    assert conf["control"] == ACTUATOR_NAMES


def test_tune_knobs_control_csv():
    conf = tune_knobs({"DPTPU_TUNE_CONTROL": "host_lost, serve_ladder"})
    assert conf["control"] == ("host_lost", "serve_ladder")


def test_tune_knobs_control_junk_fails_fast():
    with pytest.raises(ValueError, match="DPTPU_TUNE_CONTROL"):
        tune_knobs({"DPTPU_TUNE_CONTROL": "decode_ahaed"})


def test_tune_knobs_interval_fails_fast():
    with pytest.raises(ValueError, match="DPTPU_TUNE_INTERVAL_S"):
        tune_knobs({"DPTPU_TUNE_INTERVAL_S": "0"})
    with pytest.raises(ValueError, match="DPTPU_TUNE_INTERVAL_S"):
        tune_knobs({"DPTPU_TUNE_INTERVAL_S": "fast"})


def test_tunable_knobs_all_registered():
    """Every tunable knob (and every DPTPU_TUNE_* knob) is declared in
    the knob registry — the artifact cannot inject an undeclared env
    read past the knob-contract lint."""
    from dptpu.analysis.knobs import KNOB_REGISTRY

    for k in TUNABLE_KNOBS:
        assert k in KNOB_REGISTRY, k
    for k in ("DPTPU_TUNE_ARTIFACT", "DPTPU_TUNE_CONTROL",
              "DPTPU_TUNE_INTERVAL_S"):
        assert k in KNOB_REGISTRY, k


# ------------------------------------------------------- Actuator -------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _actuator(read, act, clock, **kw):
    kw.setdefault("threshold", 0.5)
    kw.setdefault("persist", 2)
    kw.setdefault("interval_s", 10.0)
    kw.setdefault("max_actions", 2)
    return Actuator("t", read, act, kw.pop("threshold"),
                    persist=kw.pop("persist"),
                    interval_s=kw.pop("interval_s"),
                    max_actions=kw.pop("max_actions"),
                    clock=clock, **kw)


def test_actuator_validates_config():
    for bad in ({"persist": 0}, {"interval_s": 0.0}, {"max_actions": 0}):
        with pytest.raises(ValueError):
            _actuator(lambda: 0.0, lambda v: {}, _Clock(), **bad)


def test_actuator_rate_limits_reads():
    clock = _Clock()
    reads = []
    a = _actuator(lambda: reads.append(1) or 1.0, lambda v: {"ok": 1},
                  clock, persist=99)
    for i in range(101):
        clock.t = i * 0.1  # 10 s of ticks at 10 Hz
        a.tick()
    # first eval at t=0, next not before t=10: exactly 2 reads in 10 s
    assert len(reads) == 2


def test_actuator_persist_then_act_then_fresh_window():
    clock = _Clock()
    acts = []
    a = _actuator(lambda: 1.0, lambda v: acts.append(v) or {"ok": 1},
                  clock, persist=3, max_actions=5)
    for i in range(1, 8):
        clock.t = i * 10.0
        a.tick()
    # strikes 1,2,3 -> act; fresh window: strikes 1,2,3 -> act again
    assert len(acts) == 2


def test_actuator_below_threshold_resets_strikes():
    clock = _Clock()
    vals = iter([1.0, 0.0, 1.0, 1.0])
    acts = []
    a = _actuator(lambda: next(vals), lambda v: acts.append(v) or {},
                  clock, persist=2)
    for i in range(1, 5):
        clock.t = i * 10.0
        a.tick()
    # the healthy read between strikes resets the count: only the
    # final consecutive pair actuates
    assert len(acts) == 1


def test_actuator_none_read_freezes_verdict():
    clock = _Clock()
    vals = iter([1.0, None, 1.0])
    acts = []
    a = _actuator(lambda: next(vals), lambda v: acts.append(v) or {},
                  clock, persist=2)
    for i in range(1, 4):
        clock.t = i * 10.0
        a.tick()
    # None is no fresh evidence: neither a strike nor a reset — the
    # two real strikes (ticks 1 and 3) still convict
    assert len(acts) == 1


def test_actuator_budget_disarms():
    clock = _Clock()
    a = _actuator(lambda: 1.0, lambda v: {"ok": 1}, clock,
                  persist=1, max_actions=2)
    for i in range(1, 10):
        clock.t = i * 10.0
        a.tick()
    assert a.actions == 2  # hard cap: never exceeds the budget
    assert not a.armed
    assert a.disarm_reason == "action budget spent"


def test_actuator_seam_none_disarms():
    clock = _Clock()
    a = _actuator(lambda: 1.0, lambda v: None, clock, persist=1)
    clock.t = 10.0
    a.tick()
    assert not a.armed
    assert a.disarm_reason == "no headroom at the seam"
    clock.t = 1000.0
    assert a.tick() is None  # disarmed = never reads again


def test_actuator_read_exception_disarms_never_raises():
    clock = _Clock()

    def bad_read():
        raise RuntimeError("kv store down")

    a = _actuator(bad_read, lambda v: {}, clock)
    clock.t = 10.0
    a.tick()  # must not raise into the train loop
    assert not a.armed and "kv store down" in a.disarm_reason


def test_actuator_events_are_loud():
    clock = _Clock()
    events = []
    a = Actuator("x", lambda: 1.0, lambda v: {"ok": 1}, 0.5,
                 persist=1, interval_s=1.0, max_actions=1,
                 on_event=lambda k, p: events.append((k, p)), clock=clock)
    clock.t = 1.0
    a.tick()
    kinds = [k for k, _ in events]
    assert kinds == ["tune_verdict", "tune_actuate", "tune_disarm"]


# ------------------------------------------- the three actuators --------


class _FakeCoord:
    def __init__(self):
        self.missing = []

    def missing_hosts(self, timeout_s=None):
        return list(self.missing)


def test_host_lost_actuator_declares_once():
    clock = _Clock()
    coord = _FakeCoord()
    lost = []
    a = host_lost_actuator(coord, lambda m: lost.append(m),
                           deadline_s=5.0, interval_s=10.0, persist=2,
                           clock=clock)
    coord.missing = ["host3"]
    for i in range(1, 6):
        clock.t = i * 10.0
        a.tick()
    assert lost == [["host3"]]  # exactly one declaration
    assert not a.armed  # one action, then disarmed: bounded


def test_host_lost_actuator_host_returns_in_time():
    clock = _Clock()
    coord = _FakeCoord()
    lost = []
    a = host_lost_actuator(coord, lambda m: lost.append(m),
                           deadline_s=5.0, interval_s=10.0, persist=2,
                           clock=clock)
    coord.missing = ["host3"]
    clock.t = 10.0
    a.tick()  # strike 1
    clock.t = 20.0
    coord.missing = []

    # the act-time re-poll: verdict reached but the host came back —
    # never declare, disarm via the seam's None
    class _Flip:
        calls = 0

    orig = coord.missing_hosts

    def flip(timeout_s=None):
        _Flip.calls += 1
        return ["host3"] if _Flip.calls == 1 else []

    coord.missing_hosts = flip
    a.tick()  # strike 2 (read sees missing) -> act re-polls: empty
    coord.missing_hosts = orig
    assert lost == []
    assert not a.armed and "headroom" in a.disarm_reason


class _FakeRingLoader:
    def __init__(self):
        self.wait = 0.0
        self.ahead = 4
        self.grow_calls = 0

    def io_wait_total_s(self):
        return self.wait

    def grow_decode_ahead(self, max_ahead=16):
        if self.ahead >= max_ahead:
            return None
        self.ahead += 1
        self.grow_calls += 1
        return self.ahead


def test_decode_ahead_actuator_grows_under_io_wait():
    clock = _Clock()
    loader = _FakeRingLoader()
    a = decode_ahead_actuator(loader, interval_s=10.0, persist=2,
                              io_fraction=0.25, max_ahead=6,
                              clock=clock)
    for i in range(1, 10):
        clock.t = i * 10.0
        loader.wait += 5.0  # 50% of wall blocked on spans
        a.tick()
    # baseline eval + 2-strike windows; capped at max_ahead=6 (two
    # grows from 4), then the seam's None disarms — monotonic, bounded
    assert loader.ahead == 6
    assert not a.armed


def test_decode_ahead_actuator_quiet_feed_never_acts():
    clock = _Clock()
    loader = _FakeRingLoader()
    a = decode_ahead_actuator(loader, interval_s=10.0, persist=2,
                              io_fraction=0.25, clock=clock)
    for i in range(1, 10):
        clock.t = i * 10.0
        loader.wait += 0.5  # 5% io wait: below threshold
        a.tick()
    assert loader.grow_calls == 0
    assert a.armed  # still armed, just nothing to do


def test_decode_ahead_actuator_follows_rebuild():
    """The callable-loader indirection: after a ramp-style pool rebuild
    the actuator reads and acts on the NEW loader, and the counter
    reset reads as a negative interval (below threshold), never a
    crash."""
    clock = _Clock()
    loaders = {"cur": _FakeRingLoader()}
    a = decode_ahead_actuator(lambda: loaders["cur"], interval_s=10.0,
                              persist=1, io_fraction=0.25, clock=clock)
    loaders["cur"].wait = 100.0
    clock.t = 10.0
    a.tick()  # baseline
    new = _FakeRingLoader()  # rebuild: cumulative counter restarts at 0
    loaders["cur"] = new
    clock.t = 20.0
    a.tick()  # negative delta: no strike, no crash
    assert new.grow_calls == 0 and a.armed
    new.wait = 8.0
    clock.t = 30.0
    a.tick()  # 80% of the interval blocked -> grow the NEW loader
    assert new.grow_calls == 1


class _FakeEngine:
    def __init__(self, buckets):
        self.buckets = tuple(sorted(buckets))
        self.added = []

    @property
    def max_bucket(self):
        return self.buckets[-1]

    def add_bucket(self, b):
        if b <= 0 or b >= self.max_bucket or b in self.buckets:
            return None
        self.buckets = tuple(sorted(self.buckets + (b,)))
        self.added.append(b)
        return b


class _FakeBatcher:
    def __init__(self):
        self.pad = 0
        self.ex = 0

    def padding_counts(self):
        return self.pad, self.ex


def test_serve_ladder_actuator_densifies_widest_gap():
    clock = _Clock()
    engine = _FakeEngine((1, 4, 16, 64))
    batcher = _FakeBatcher()
    a = serve_ladder_actuator(engine, batcher, interval_s=10.0,
                              persist=2, waste=0.25, max_actions=2,
                              clock=clock)
    clock.t = 10.0
    a.tick()  # baseline
    for i in range(2, 5):
        clock.t = i * 10.0
        batcher.pad += 40
        batcher.ex += 100  # 40% padding waste, sustained
        a.tick()
    # every gap is 4x: ties go to the FIRST widest — midpoint of 1..4
    assert engine.added == [2]
    assert engine.buckets == (1, 2, 4, 16, 64)


def test_serve_ladder_actuator_budget_and_admission_bound():
    clock = _Clock()
    engine = _FakeEngine((1, 4, 16, 64))
    batcher = _FakeBatcher()
    a = serve_ladder_actuator(engine, batcher, interval_s=10.0,
                              persist=1, waste=0.25, max_actions=3,
                              clock=clock)
    clock.t = 10.0
    a.tick()
    for i in range(2, 20):
        clock.t = i * 10.0
        batcher.pad += 50
        batcher.ex += 100
        a.tick()
    assert len(engine.added) <= 3  # the hard budget
    assert engine.max_bucket == 64  # admission bound NEVER moves
    assert all(1 < b < 64 for b in engine.added)  # interior only
    assert not a.armed


def test_serve_ladder_actuator_gapless_disarms():
    clock = _Clock()
    engine = _FakeEngine((1, 2, 3, 4))  # no interior midpoint anywhere
    batcher = _FakeBatcher()
    a = serve_ladder_actuator(engine, batcher, interval_s=10.0,
                              persist=1, waste=0.25, clock=clock)
    clock.t = 10.0
    a.tick()
    clock.t = 20.0
    batcher.pad, batcher.ex = 50, 100
    a.tick()
    assert engine.added == []
    assert not a.armed and "headroom" in a.disarm_reason


def test_serve_ladder_actuator_idle_batcher_freezes():
    clock = _Clock()
    engine = _FakeEngine((1, 4, 16, 64))
    batcher = _FakeBatcher()
    a = serve_ladder_actuator(engine, batcher, interval_s=10.0,
                              persist=1, waste=0.25, clock=clock)
    for i in range(1, 6):
        clock.t = i * 10.0
        a.tick()  # exec counter never moves: no verdict either way
    assert engine.added == [] and a.armed


def test_controller_ticks_all_and_reports():
    clock = _Clock()
    a1 = _actuator(lambda: 0.0, lambda v: {}, clock)
    a2 = _actuator(lambda: 0.0, lambda v: {}, clock)
    a2.name = "t2"
    c = Controller([a1])
    c.add(a2)
    clock.t = 10.0
    c.tick()
    stats = c.stats()
    assert set(stats) == {"t", "t2"}
    assert all(s["armed"] for s in stats.values())


# ------------------------------------------ straggler rebind (ramp) -----


class _FakePoolLoader:
    def __init__(self, script, num_workers=2):
        self.script = list(script)
        self.num_workers = num_workers
        self.resplit_calls = []
        self.evict_calls = []
        self.restore_calls = []

    def worker_latency_observations(self):
        return self.script.pop(0) if self.script else []

    def resplit_worker(self, w):
        self.resplit_calls.append(w)
        return 1

    def restore_worker(self, w):
        self.restore_calls.append(w)

    def evict_worker(self, w):
        self.evict_calls.append(w)
        return 1


def test_straggler_rebind_resets_verdicts():
    """Ramp x straggler composition: the phase switch rebuilds the pool
    and rebinds the controller — a worker convicted in the OLD pool
    must not carry strikes into the new one."""
    from dptpu.resilience.elastic import StragglerController

    old = _FakePoolLoader([[(0, 0.5), (1, 0.05)]] * 4)
    events = []
    c = StragglerController(old, factor=2.0, persist=2, min_obs=4,
                            on_event=lambda k, p: events.append(k))
    for _ in range(4):
        c.tick()  # worker 0 one tick short of conviction
    assert old.resplit_calls == []
    new = _FakePoolLoader([[(0, 0.05), (1, 0.05)]] * 8)
    c.rebind(new)
    assert "straggler_rebind" in events
    for _ in range(8):
        c.tick()
    # fresh pool, healthy worker 0: the stale near-conviction died with
    # the rebind — no escalation against either loader
    assert new.resplit_calls == [] and new.evict_calls == []
    assert old.resplit_calls == []
    assert c.loader is new


def test_straggler_rebind_keeps_run_totals():
    from dptpu.resilience.elastic import StragglerController

    old = _FakePoolLoader([[(0, 0.5), (1, 0.05)]] * 6)
    c = StragglerController(old, factor=2.0, persist=2, min_obs=4)
    for _ in range(6):
        c.tick()
    assert c.stats()["resplits"] == 1  # convicted in the old pool
    c.rebind(_FakePoolLoader([]))
    assert c.stats()["resplits"] == 1  # history describes the RUN
    assert c.stats()["suspects"] == [] if "suspects" in c.stats() \
        else True


# ------------------------------------------------ real seams ------------


def test_engine_add_bucket_interior_only():
    """The serve-ladder seam on a REAL engine: interior insertions
    only (admission never moves), compiled before publication, served
    after."""
    import numpy as np

    from dptpu.serve import ServeEngine

    engine = ServeEngine("resnet18", buckets=(1, 16), num_classes=8,
                         image_size=32)
    assert engine.add_bucket(16) is None  # already present
    assert engine.add_bucket(64) is None  # past the admission bound
    assert engine.add_bucket(0) is None
    assert engine.add_bucket(1) is None
    assert engine.add_bucket(4) == 4
    assert engine.buckets == (1, 4, 16)
    assert engine.max_bucket == 16  # the bound NEVER moves
    assert engine.bucket_for(3) == 4  # routed to the new bucket
    out = engine.infer(
        np.random.RandomState(0)
        .randint(0, 256, (3, 32, 32, 3)).astype(np.uint8)
    )
    assert out.shape == (3, 8)


def test_search_ladder_waste_and_mix():
    from dptpu.tune.search import (
        default_request_mix,
        ladder_waste,
        search_serve_buckets,
    )

    mix = default_request_mix(64)
    assert all(1 <= n <= 64 for n in mix)
    # a denser ladder can only shrink padding on the same mix
    assert ladder_waste([1, 2, 4, 8, 16, 32, 64], mix) \
        <= ladder_waste([1, 4, 16, 64], mix)
    best = search_serve_buckets(mix)
    assert best["best_waste"] <= min(r["waste"] for r in best["rows"])


def _tiny_cfg(**kw):
    from dptpu.config import Config

    base = dict(
        data="synthetic:64", variant="apex", arch="resnet18",
        epochs=1, batch_size=16, lr=0.05, workers=2,
        print_freq=10_000, seed=0, opt_level="O0",
    )
    base.update(kw)
    return Config(**base)


def _shed_tunables(monkeypatch):
    """Unset every tunable knob for one case, and again after it: a run
    under an artifact env-injects the artifact's knobs for good, and
    monkeypatch only puts back what it has seen set or deleted (a
    leaked DPTPU_DECODE_AHEAD=6 sized every later ring of the process
    that ran this file)."""
    for k in TUNABLE_KNOBS:
        monkeypatch.setenv(k, "")
        monkeypatch.delenv(k)


def test_fit_loads_artifact_with_explicit_knob_precedence(
        tmp_path, monkeypatch):
    """The ISSUE 19 acceptance lock, through a REAL fit(): one run
    under a tuning artifact where (a) an untouched knob gets the tuned
    value, (b) an explicit env twin beats the artifact, (c) an
    explicit CLI flag (--accum-steps) beats the artifact — and the
    result records every decision."""
    from dptpu.train import fit

    path = _write(tmp_path, {
        "DPTPU_DECODE_AHEAD": "6",  # nothing else sets it: applied
        "DPTPU_BUCKET_MB": "2",     # env twin below: kept explicit
        "DPTPU_ACCUM": "4",         # CLI twin below: kept explicit
    })
    _shed_tunables(monkeypatch)
    monkeypatch.setenv("DPTPU_TUNE_ARTIFACT", path)
    monkeypatch.setenv("DPTPU_BUCKET_MB", "8")
    monkeypatch.chdir(tmp_path)
    result = fit(_tiny_cfg(accum_steps=2), image_size=32,
                 verbose=False)
    tuning = result["tuning"]
    assert tuning["applied"] == {"DPTPU_DECODE_AHEAD": "6"}
    assert tuning["overridden"]["DPTPU_BUCKET_MB"].startswith("env ")
    assert tuning["overridden"]["DPTPU_ACCUM"] == "explicit CLI flag"
    # the artifact never overwrote the operator's hands
    assert os.environ["DPTPU_BUCKET_MB"] == "8"
    assert "DPTPU_ACCUM" not in os.environ
    assert result["history"]  # and the run actually trained


def test_fit_corrupt_artifact_fails_fast(tmp_path, monkeypatch):
    from dptpu.train import fit

    path = _write(tmp_path, {"DPTPU_BUCKET_MB": "2"})
    rec = json.load(open(path))
    rec["knobs"]["DPTPU_BUCKET_MB"] = "999"
    json.dump(rec, open(path, "w"))
    monkeypatch.setenv("DPTPU_TUNE_ARTIFACT", path)
    with pytest.raises(TuningError, match="CRC"):
        fit(_tiny_cfg(), image_size=32)


def test_serve_selftest_loads_artifact_ladder(tmp_path, monkeypatch):
    """dptpu serve under DPTPU_TUNE_ARTIFACT: the tuned ladder drives
    the compiled buckets; an explicit --buckets flag beats it."""
    from dptpu.cli import main_serve

    path = _write(tmp_path, {"DPTPU_SERVE_BUCKETS": "1,2"})
    _shed_tunables(monkeypatch)
    monkeypatch.setenv("DPTPU_TUNE_ARTIFACT", path)
    stats = main_serve(["--selftest", "3", "--arch", "resnet18",
                        "--num-classes", "8", "--image-size", "32"])
    assert set(stats["bucket_counts"]) <= {1, 2}  # the tuned ladder
    monkeypatch.delenv("DPTPU_SERVE_BUCKETS", raising=False)
    stats = main_serve(["--selftest", "3", "--arch", "resnet18",
                        "--num-classes", "8", "--image-size", "32",
                        "--buckets", "1,4"])
    assert set(stats["bucket_counts"]) <= {1, 4}  # explicit CLI wins
