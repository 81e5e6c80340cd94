"""``mtp_loss_share``: on ``fixtures/obs_log_attrs.jsonl`` (six
iterations, two of warm-up, timed steps 2..5; a fetch at step 0 and the
fence at step 5) with ``loss`` and ``mtp_loss`` written onto its
``fetch`` spans by the test, on the logs of a program that does not count
them, and as the manifest lists it."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import cells, spans

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
NAME = "mtp_loss_share"
CELL = "joyai-fit-8k-1chip"


def _context(path: str, warmup: int, weight=0.1) -> dict:
    config = {} if weight is None else {"mtp_loss_weight": weight}
    return {"window": spans.window(spans.read_log(path), warmup), "host": {},
            "trace": None, "device": {}, "peaks": None,
            "cell": SimpleNamespace(name=CELL, config=config)}


def _log_with(tmp_path, terms_by_step) -> str:
    """The fixture's log with ``attrs`` on the ``fetch`` spans of the
    steps named (beside an expert layer's count, as the program writes
    them)."""
    out = tmp_path / "obs_log_mtp.jsonl"
    with open(os.path.join(FIXTURES, "obs_log_attrs.jsonl")) as src, \
            open(out, "w") as dst:
        for line in src:
            rec = json.loads(line)
            if rec.get("name") == "fetch" and rec["step"] in terms_by_step:
                rec["attrs"] = {"moe_dropped": 0, **terms_by_step[rec["step"]]}
            dst.write(json.dumps(rec) + "\n")
    return str(out)


# the fixture's two fetches: one in the warm-up (step 0), the fence (step 5)
@pytest.mark.parametrize("terms_by_step,weight,value", [
    # loss = main + 0.1 x mtp at seeded weights: 10 / 11 of it is main
    ({5: {"loss": 11.0, "mtp_loss": 10.0}}, 0.1, 100.0 / 11.0),
    # the warm-up's span is not read
    ({0: {"loss": 1.0, "mtp_loss": 9.0},
      5: {"loss": 11.0, "mtp_loss": 10.0}}, 0.1, 100.0 / 11.0),
    # the weight is the configuration's
    ({5: {"loss": 8.0, "mtp_loss": 4.0}}, 0.5, 25.0),
])
def test_reads_the_second_losss_share_off_the_timed_fetch_spans(
        tmp_path, terms_by_step, weight, value):
    ctx = _context(_log_with(tmp_path, terms_by_step), 2, weight)
    got = cells.reader(NAME).read(ctx)
    assert isinstance(got, float) and got == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("log,warmup", [
    ("obs_log_attrs.jsonl", 2),  # a program with attrs, none of this name
    ("obs_log.jsonl", 5),        # an older one: no attrs at all
])
def test_a_parents_log_reads_no_second_loss(log, warmup):
    assert cells.reader(NAME).read(
        _context(os.path.join(FIXTURES, log), warmup)) is None


def test_mtp_loss_share_is_listed_last_for_its_cell_as_its_file_has_it(
        tmp_path):
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    entry = bench["per_layer"][-1]
    assert entry == {k: spec[k] for k in entry} and entry["name"] == NAME
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "train_img_s_chip"
    assert entry["layer"] == cells.layer_metric(
        "expert_dropped_tokens")["layer"]
    # a configuration without the weight, or a loss of zero: nothing read
    carrying = _log_with(tmp_path, {5: {"loss": 11.0, "mtp_loss": 10.0}})
    assert cells.reader(NAME).read(_context(carrying, 2, None)) is None
    zero = _log_with(tmp_path, {5: {"loss": 0.0, "mtp_loss": 0.0}})
    assert cells.reader(NAME).read(_context(zero, 2)) is None
