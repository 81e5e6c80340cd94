"""``attention_kernel_roofline_share``: the count of a call's operations
and bytes (``readers/attention_cost.py``) against the numbers ISSUE 43
reckoned by hand and against the tile walk's definition; the reader on a
reduced trace made here (the two kernels' device seconds by name among
other operations), on traces without the kernels, and as the manifest
lists it."""

from types import SimpleNamespace

import pytest

from benchmark.lib import cells
from benchmark.readers import attention_cost

NAME = "attention_kernel_roofline_share"
CELL = "trinity-mini-fit-8k-1chip"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _cell():
    return cells.load_cell(CELL)


def _context(ops, steps=4, cell=None, peaks=PEAKS) -> dict:
    trace = None if ops is None else {"ops": ops, "steps": steps}
    return {"window": None, "host": {}, "trace": trace, "device": {},
            "peaks": peaks, "cell": cell or _cell()}


@pytest.mark.parametrize("length,window,block,tiles", [
    (8192, None, 512, 136), (8192, 2048, 512, 70), (8192, 8192, 512, 136),
    (8192, 1, 512, 16), (8192, 514, 512, 45), (2048, 512, 128, 70),
    (300, 130, 128, 6), (64, 16, 512, 1)])
def test_the_tiles_counted_are_those_that_hold_a_visible_pair(
        length, window, block, tiles):
    assert attention_cost.walked_tiles(length, window, block) == tiles
    if length <= 2048:
        block = min(block, length)
        n = -(-length // block)
        seen = 0
        for i in range(n):
            for j in range(i + 1):
                seen += any(
                    0 <= q - k < (window or length)
                    for q in (i * block, (i + 1) * block - 1)
                    for k in (j * block, (j + 1) * block - 1))
        assert seen == tiles


def test_the_cells_calls_by_count():
    model = _cell().config["model"]
    assert attention_cost.windows(model) == [2048, 2048, None, 2048, 2048]
    # a tile: 512 queries of each of 32 heads by 512 keys at 128, seven
    # products of 2 x 512 x 512 x 128 operations
    tile = 7 * 2 * 512 * 512 * 128 * 32
    assert attention_cost.call_flops(model, 2048) == 70 * tile
    assert attention_cost.call_flops(model, None) == 136 * tile
    # the band is 70 / 136 of the triangle; over the step 416 / 680
    step = sum(attention_cost.call_flops(model, w)
               for w in attention_cost.windows(model))
    assert step == 416 * tile == pytest.approx(6.25e12, rel=0.01)
    # q, out, d_out, dq of 32 heads, k, v, dk, dv of 4, each once a pass
    # in bfloat16, and the float32 statistics: 0.39 GB a call
    assert attention_cost.call_bytes(model) \
        == 2 * 128 * 8192 * (5 * 32 + 6 * 4) + 3 * 4 * 8192 * 32
    least, bound = attention_cost.step_seconds(model, 1, PEAKS)
    assert bound == "flops"
    assert least == pytest.approx(step / 197e12) == pytest.approx(
        0.0317, rel=0.01)
    # a chip with a tenth of the bandwidth would be bound by the bytes
    slow = dict(PEAKS, hbm_bytes_per_s=819e9 / 100)
    assert attention_cost.step_seconds(model, 1, slow)[1] == "bytes"
    assert attention_cost.step_seconds(model, 2, PEAKS)[0] \
        == pytest.approx(2 * least)


def test_reads_the_kernels_seconds_by_name_against_the_least_time():
    least, _ = attention_cost.step_seconds(_cell().config["model"], 1, PEAKS)
    ops = {
        # the TPU trace names an operation by its HLO text
        "%causal_attention_forward.1 = (bf16[1,4,128,65536]{3,2,1,0}, "
        "f32[1,4,1,65536]{3,2,1,0}) custom-call(%a, %b)": 4 * 0.030,
        "%causal_attention_forward.2 = (bf16[1,4,128,65536]) "
        "custom-call(%c)": 4 * 0.010,
        "%causal_attention_backward.7 = (bf16[1,4,128,128,512]) "
        "custom-call(%d)": 4 * 0.060,
        "%fusion.12 = bf16[8192,2048]{1,0} fusion(%e)": 4 * 0.1,
        # not the kernels: an operation that merely mentions one
        "%copy.3 = bf16[1,4,128,65536] copy(%causal_attention_forward.1)":
            4 * 0.5,
    }
    got = cells.reader(NAME).read(_context(ops, steps=4))
    assert got == pytest.approx(100.0 * least / 0.100)
    assert 0 < got < 100
    # the same seconds over twice the steps: half the time a step
    assert cells.reader(NAME).read(_context(ops, steps=8)) \
        == pytest.approx(2 * got)
    # short names, as a reduced trace's `top_ops` and the ledger have them
    short = {"causal_attention_forward.1 bf16[1,4,128,65536]": 0.16,
             "causal_attention_backward.7 bf16[1,4,128,128,512]": 0.24}
    assert cells.reader(NAME).read(_context(short, steps=4)) \
        == pytest.approx(got)


@pytest.mark.parametrize("ops,steps", [
    (None, 4),                                  # an untraced run
    ({"%fusion.1 = bf16[8] fusion(%a)": 1.0}, 4),  # the scan, or a parent
    ({"%causal_attention_forward.1 = x": 1.0}, 0),  # no whole step read
    ({}, 4),
])
def test_a_trace_without_the_kernels_reads_no_roofline_share(ops, steps):
    assert cells.reader(NAME).read(_context(ops, steps)) is None


def test_a_cell_without_such_layers_or_peaks_reads_nothing():
    ops = {"%causal_attention_forward.1 = x": 1.0}
    joyai = cells.load_cell("joyai-fit-8k-1chip")
    assert cells.reader(NAME).read(_context(ops, cell=joyai)) is None
    assert cells.reader(NAME).read(_context(ops, peaks=None)) is None
    toy = SimpleNamespace(config={"model": {}, "per_chip_batch": 1})
    assert cells.reader(NAME).read(_context(ops, cell=toy)) is None


def test_attention_kernel_roofline_share_is_listed_for_its_cell():
    bench = cells.manifest()
    spec = cells.layer_metric(NAME)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        k: spec[k] for k in entry if k != "workloads"}
    assert entry["workloads"][:1] == [CELL]
    assert (entry["unit"], entry["better"], entry["source"], entry["moves"]
            ) == ("%", "higher", "device_trace", "train_img_s_chip")
    assert entry["layer"] == cells.layer_metric(
        "attention_kernel_share")["layer"]
    assert NAME in {m["name"] for m in cells.load_cell(CELL, bench).per_layer}
    for other in ("lfm2moe-fit-8k-1chip", "joyai-fit-8k-1chip",
                  "granite-ssm-fit-1chip", "vitb16-fit-1chip"):
        assert NAME not in {m["name"] for m in
                            cells.load_cell(other, bench).per_layer}
