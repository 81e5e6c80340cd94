"""``kept_residual_mb``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5; a fetch at step 0 and the
fence at step 5) with ``kept_residual_mb``
written onto its ``fetch`` spans by the test, on the logs of a program
that does not count it, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "kept_residual_mb"
CELL = "lfm2moe-fit-8k-1chip"


def _context(path: str, warmup: int) -> dict:
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name=CELL)}


def _log_with(tmp_path, kept_by_step) -> str:
    """The fixture's log with ``kept_residual_mb`` on the ``fetch`` spans
    of the steps named (beside the expert layers' counts, as the program
    writes them)."""
    out = tmp_path / "obs_log_kept.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "fetch" and rec["step"] in kept_by_step:
                rec["attrs"] = {"moe_slots": 262144, "moe_dropped": 0,
                                "kept_residual_mb": kept_by_step[rec["step"]]}
            dst.write(json.dumps(rec) + "\n")
    return str(out)


# the fixture's two fetches: one in the warm-up (step 0), the fence (step 5)
@pytest.mark.parametrize("kept_by_step,value", [
    ({0: 1793, 5: 1793}, 1793.0),  # a constant of the step program
    ({0: 7, 5: 1793}, 1793.0),     # the warm-up's span is not read
    ({5: 0}, 0.0),                 # nothing kept is 0, not nothing to read
])
def test_reads_the_megabytes_off_the_timed_fetch_spans(tmp_path, kept_by_step,
                                                       value):
    ctx = _context(_log_with(tmp_path, kept_by_step), 2)
    got = cells.reader(NAME).read(ctx)
    assert isinstance(got, float) and got == value


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # a program with attrs, none of this name
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_nothing(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


def test_a_window_whose_only_carriers_were_warm_up_reads_nothing(tmp_path):
    ctx = _context(_log_with(tmp_path, {0: 1793}), 2)
    assert cells.reader(NAME).read(ctx) is None


def test_listed_for_the_token_cell_alone_and_as_its_data_file_has_it():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {k: spec[k] for k in entry}
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL] and entry["unit"] == "MB"
    assert entry["moves"] == "train_img_s_chip"
    # the layer's name, letter for letter, is the expert metrics'
    assert entry["layer"] == cells.layer_metric(
        "expert_dropped_tokens")["layer"]
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  cells.load_cell(cell["name"], bench).per_layer}
        assert (NAME in listed) is (cell["name"] == CELL)
