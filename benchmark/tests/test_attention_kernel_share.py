"""``attention_kernel_share``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5; a fetch at step 0 and the
fence at step 5) with ``attention_calls`` and ``attention_kernel_calls``
written onto its ``fetch`` spans by the test, on the logs of a program
that does not count them, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "attention_kernel_share"
CELLS = ["lfm2moe-fit-8k-1chip", "joyai-fit-8k-1chip"]


def _context(path: str, warmup: int) -> dict:
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name=CELLS[1])}


def _log_with(tmp_path, calls_by_step) -> str:
    """The fixture's log with the two counts on the ``fetch`` spans of
    the steps named (beside the megabytes kept, as the program writes
    them): ``(calls on the kernel, calls)``, the first None where a
    program counts its calls alone."""
    out = tmp_path / "obs_log_attention.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "fetch" and rec["step"] in calls_by_step:
                on_kernel, calls = calls_by_step[rec["step"]]
                rec["attrs"] = {"kept_residual_mb": 845,
                                "attention_calls": calls}
                if on_kernel is not None:
                    rec["attrs"]["attention_kernel_calls"] = on_kernel
            dst.write(json.dumps(rec) + "\n")
    return str(out)


# the fixture's two fetches: one in the warm-up (step 0), the fence (step 5)
@pytest.mark.parametrize("calls_by_step,value", [
    ({0: (6, 6), 5: (6, 6)}, 100.0),  # a constant of the step program
    ({0: (6, 6), 5: (0, 6)}, 0.0),    # the warm-up's span is not read;
                                      # every call on the scan is 0
    ({5: (1, 4)}, 25.0),              # some shapes tile, some do not
])
def test_reads_the_share_off_the_timed_fetch_spans(tmp_path, calls_by_step,
                                                   value):
    ctx = _context(_log_with(tmp_path, calls_by_step), 2)
    got = cells.reader(NAME).read(ctx)
    assert isinstance(got, float) and got == value


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # a program with attrs, none of this name
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_no_share(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


@pytest.mark.parametrize("calls_by_step", [
    {0: (6, 6)},     # only the warm-up carried them
    {5: (0, 0)},     # a model without such a call
    {5: (None, 6)},  # the calls without the kernel's count
])
def test_nothing_to_divide_reads_nothing(tmp_path, calls_by_step):
    ctx = _context(_log_with(tmp_path, calls_by_step), 2)
    assert cells.reader(NAME).read(ctx) is None


def test_attention_kernel_share_is_listed_for_the_token_cells_as_its_file_has_it():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # everything but the list is the data file's, and the list starts
    # with the file's (a later cell is appended, never an edit)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in entry if k != "workloads"}
    assert spec["workloads"] == CELLS
    assert entry["workloads"][:len(CELLS)] == CELLS
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]
            ) == ("%", "higher", "program_counter", "train_img_s_chip")
    assert entry["layer"] == cells.layer_metric("kept_residual_mb")["layer"]
    for cell in CELLS:
        assert NAME in {m["name"]
                        for m in cells.load_cell(cell, bench).per_layer}
    assert NAME not in {m["name"] for m in
                        cells.load_cell("vitb16-fit-1chip", bench).per_layer}
