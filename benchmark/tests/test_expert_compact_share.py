"""``expert_compact_share``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5; a fetch at step 0 and the
fence at step 5) with ``moe_compact_layers`` and ``moe_layers`` written
onto its ``fetch`` spans by the test, on the logs of a program that does
not count them, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "expert_compact_share"
CELLS = ["lfm2moe-fit-8k-1chip", "joyai-fit-8k-1chip"]


def _context(path: str, warmup: int) -> dict:
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name=CELLS[0])}


def _log_with(tmp_path, layers_by_step) -> str:
    """The fixture's log with the two counts on the ``fetch`` spans of
    the steps named (beside the slots, as the program writes them):
    ``(compact layers, layers)``, either None where a program does not
    say it."""
    out = tmp_path / "obs_log_compact.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "fetch" and rec["step"] in layers_by_step:
                compact, layers = layers_by_step[rec["step"]]
                rec["attrs"] = {"moe_slots": 262144, "moe_dropped": 0}
                if compact is not None:
                    rec["attrs"]["moe_compact_layers"] = compact
                if layers is not None:
                    rec["attrs"]["moe_layers"] = layers
            dst.write(json.dumps(rec) + "\n")
    return str(out)


# the fixture's two fetches: one in the warm-up (step 0), the fence (step 5)
@pytest.mark.parametrize("layers_by_step,value", [
    ({0: (40, 40), 5: (400, 400)}, 100.0),  # every layer of every step
    ({0: (40, 40), 5: (0, 400)}, 0.0),      # the warm-up's span is not
                                            # read; none compact is 0
    ({5: (300, 400)}, 75.0),                # one layer in four overflowed
])
def test_reads_the_compact_share_off_the_timed_fetch_spans(
        tmp_path, layers_by_step, value):
    ctx = _context(_log_with(tmp_path, layers_by_step), 2)
    got = cells.reader(NAME).read(ctx)
    assert isinstance(got, float) and got == value


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # a program with attrs, none of this name
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_no_compact_share(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


@pytest.mark.parametrize("layers_by_step", [
    {0: (40, 40)},      # only the warm-up carried them
    {5: (0, 0)},        # no expert layer ran
    {5: (400, None)},   # the compact layers without the layers run
    {5: (None, 400)},   # a parent's expert model: slots, no buffer to count
    {5: (None, None)},
])
def test_no_layers_to_divide_by_reads_no_compact_share(tmp_path,
                                                       layers_by_step):
    ctx = _context(_log_with(tmp_path, layers_by_step), 2)
    assert cells.reader(NAME).read(ctx) is None


def test_expert_compact_share_is_listed_for_the_two_expert_cells_alone():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # everything but the list is the data file's; the list is here alone
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in entry if k != "workloads"}
    assert entry["workloads"][:len(CELLS)] == CELLS
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]
            ) == ("%", "higher", "program_counter", "train_img_s_chip")
    # the layer's name, letter for letter, is the other expert metrics'
    assert entry["layer"] == cells.layer_metric(
        "expert_local_slot_share")["layer"]
    for cell in bench["workloads"]:
        listed = {m["name"] for m in
                  cells.load_cell(cell["name"], bench).per_layer}
        # the cells that read the experts' load are the ones that read this
        assert (NAME in listed) is (cell["name"] in entry["workloads"])
        assert (NAME in listed) <= ("expert_local_slot_share" in listed)
