"""Synthetic-trace unit tests for the device-time parser
(dptpu/utils/profiling.py) — the satellite hardening: a host-only trace
must raise a clear error, never silently report zero device time."""

import gzip
import json
import os

import pytest

from dptpu.utils.profiling import load_trace_dir, parse_perfetto_trace


def _meta(pid, name):
    return {"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": name}}


def _op(pid, tid, name, dur_us):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "dur": dur_us}


def test_host_only_trace_raises_with_cause():
    trace = {"traceEvents": [
        _meta(2, "Host threads"),
        _op(2, 20, "dispatch", 9999),
    ]}
    with pytest.raises(RuntimeError) as ei:
        parse_perfetto_trace(trace)
    msg = str(ei.value)
    assert "no device tracks matched" in msg
    assert "host-only" in msg
    assert "'Host threads'" in msg  # names what it DID see


def test_empty_trace_raises():
    with pytest.raises(RuntimeError, match="no device tracks matched"):
        parse_perfetto_trace({"traceEvents": []})
    with pytest.raises(RuntimeError, match="no process_name metadata"):
        parse_perfetto_trace({})


def test_device_track_with_no_ops_raises():
    # a matched device pid that emitted zero X events is still an error:
    # "the device did no work" must never be inferred from silence
    trace = {"traceEvents": [_meta(1, "/device:TPU:0")]}
    with pytest.raises(RuntimeError, match="no device tracks matched"):
        parse_perfetto_trace(trace)


def test_multi_module_jit_spans_sum_as_total():
    """Several distinct jitted modules in one trace: the module-level
    ``jit_*`` spans SUM to the total and are filtered from the per-op
    table (their children would double-count)."""
    trace = {"traceEvents": [
        _meta(1, "/device:TPU:0"),
        _op(1, 10, "jit_train_step(7)", 6000),
        _op(1, 10, "jit_eval_step(9)", 2000),
        _op(1, 10, "fusion.1", 4000),
        _op(1, 10, "copy.2", 1000),
    ]}
    total, per_op = parse_perfetto_trace(trace, iters=2)
    assert total == pytest.approx(4.0)  # (6 + 2) ms / 2 iters
    assert per_op == {"fusion.1": 2.0, "copy.2": 0.5}
    assert not any(k.startswith("jit_") for k in per_op)


def _write_gz(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_multi_file_pid_collision_is_namespaced(tmp_path):
    """Two hosts' trace files reuse pid 1 — one as a device track, one
    as a HOST track. Without per-file namespacing the host ops would
    masquerade as device time; with it, only the true device ops count
    (max-collapse picks the slowest replica per op)."""
    _write_gz(str(tmp_path / "h0" / "a.trace.json.gz"), [
        _meta(1, "/device:TPU:0"),
        _op(1, 10, "fusion.1", 4000),
    ])
    _write_gz(str(tmp_path / "h1" / "b.trace.json.gz"), [
        _meta(1, "Host threads (pid 1 reused!)"),
        _op(1, 10, "python_dispatch", 999000),
    ])
    merged = load_trace_dir(str(tmp_path))
    total, per_op = parse_perfetto_trace(merged, iters=1)
    assert per_op == {"fusion.1": 4.0}
    assert total == pytest.approx(4.0)  # the 999ms host op never leaked in


def test_multi_file_slowest_replica_wins(tmp_path):
    # same op on two hosts: the parser reports the critical path (max)
    _write_gz(str(tmp_path / "h0" / "a.trace.json.gz"), [
        _meta(1, "/device:TPU:0"), _op(1, 10, "fusion.1", 3000),
    ])
    _write_gz(str(tmp_path / "h1" / "b.trace.json.gz"), [
        _meta(1, "/device:TPU:0"), _op(1, 10, "fusion.1", 5000),
    ])
    total, per_op = parse_perfetto_trace(load_trace_dir(str(tmp_path)))
    assert per_op == {"fusion.1": 5.0}


def test_load_trace_dir_empty_raises(tmp_path):
    with pytest.raises(RuntimeError, match="no trace written"):
        load_trace_dir(str(tmp_path))
