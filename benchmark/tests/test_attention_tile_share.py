"""``attention_tile_share``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5; a fetch at step 0 and the
fence at step 5) with ``attention_tiles`` and ``attention_tiles_causal``
written onto its ``fetch`` spans by the test, on the logs of a program
that does not count them, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "attention_tile_share"
CELL = "trinity-mini-fit-8k-1chip"


def _context(path: str, warmup: int) -> dict:
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name=CELL)}


def _log_with(tmp_path, tiles_by_step) -> str:
    """The fixture's log with the counts on the ``fetch`` spans of the
    steps named (beside what a token step writes there anyway):
    ``(tiles walked, the causal triangle's)``, the second None where a
    program counts the first alone."""
    out = tmp_path / "obs_log_tiles.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "fetch" and rec["step"] in tiles_by_step:
                walked, causal = tiles_by_step[rec["step"]]
                rec["attrs"] = {"kept_residual_mb": 710, "attention_calls": 5,
                                "attention_kernel_calls": 5,
                                "attention_window_calls": 4,
                                "attention_tiles": walked}
                if causal is not None:
                    rec["attrs"]["attention_tiles_causal"] = causal
            dst.write(json.dumps(rec) + "\n")
    return str(out)


# the fixture's two fetches: one in the warm-up (step 0), the fence (step 5)
@pytest.mark.parametrize("tiles_by_step,value", [
    ({0: (416, 680), 5: (416, 680)}, 100.0 * 416 / 680),  # the cell's: 61.2
    ({0: (416, 680), 5: (680, 680)}, 100.0),  # the warm-up's is not read;
                                              # a band masked, not skipped
    ({5: (272, 272)}, 100.0),                 # a model without windows
])
def test_reads_the_tiles_share_off_the_timed_fetch_spans(
        tmp_path, tiles_by_step, value):
    ctx = _context(_log_with(tmp_path, tiles_by_step), 2)
    got = cells.reader(NAME).read(ctx)
    assert isinstance(got, float) and got == pytest.approx(value)
    assert round(100.0 * 416 / 680, 1) == 61.2


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # a program with attrs, none of this name
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_no_share_of_the_tiles(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


@pytest.mark.parametrize("tiles_by_step", [
    {0: (416, 680)},   # only the warm-up carried them
    {5: (0, 0)},       # a model without such a call
    {5: (416, None)},  # the tiles without the triangle's count
])
def test_no_triangle_to_divide_by_reads_nothing(tmp_path, tiles_by_step):
    ctx = _context(_log_with(tmp_path, tiles_by_step), 2)
    assert cells.reader(NAME).read(ctx) is None


def test_attention_tile_share_is_listed_for_its_cell_as_its_file_has_it():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # everything but the list is the data file's; the list is here alone
    # and starts with the cell (a later cell is appended)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in entry if k != "workloads"}
    assert entry["workloads"][:1] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]
            ) == ("%", "lower", "program_counter", "train_img_s_chip")
    assert entry["layer"] == cells.layer_metric(
        "attention_kernel_share")["layer"]
    assert NAME in {m["name"] for m in cells.load_cell(CELL, bench).per_layer}
    for other in ("lfm2moe-fit-8k-1chip", "joyai-fit-8k-1chip",
                  "granite-ssm-fit-1chip", "vitb16-fit-1chip"):
        assert NAME not in {m["name"] for m in
                            cells.load_cell(other, bench).per_layer}
