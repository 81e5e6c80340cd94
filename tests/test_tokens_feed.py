"""``tokens:<N>[@first]`` (``dptpu/data/tokens.py``) through the loader,
value for value against the benchmark's own copy
(``benchmark/feeds/tokens.py``); then ``fit()`` on it: a token-sequence
model trains through ``main_apex``, reports its expert layers' load,
validates per token, and a run saved after one epoch resumes into the
second as if never stopped (the tiny model of ``tests/test_lfm2.py``)."""

import os

import jax
import numpy as np
import pytest

from benchmark.feeds import tokens as bench_feed
from dptpu.data import DataLoader
from dptpu.data.sampler import ShardedSampler
from dptpu.data.tokens import KEYS, TokenDataset, parse_source

MODEL = {"sequence_length": 48, "vocab_size": 300}


def _loader(rows, first, mode):
    ds = TokenDataset(rows, MODEL["sequence_length"], MODEL["vocab_size"],
                      first)
    return DataLoader(
        ds, 4, sampler=ShardedSampler(rows, shuffle=True, seed=0),
        num_workers=2, drop_last=True, pad_final=False, seed=0,
        workers_mode=mode)


@pytest.mark.parametrize("mode", ["thread", "process"])
def test_the_loader_delivers_the_benchmarks_rows(mode):
    harness_rows = 256 + 37  # as drive.dataset_images counts: + seed % 128
    assert bench_feed.argument(harness_rows) == "tokens:256@37"
    rows, first = parse_source(bench_feed.argument(harness_rows))
    loader = _loader(rows, first, mode)
    try:
        order = bench_feed.epoch_order(harness_rows, 0, 5)
        assert len(order) == 256 and order.min() == 37
        assert KEYS == bench_feed.KEYS
        for step, batch in zip(range(3), loader.epoch(5)):
            want = bench_feed.batch(order, step, 4, MODEL)
            assert set(batch) == set(KEYS)
            for key, expected in zip(KEYS, want):
                got = np.asarray(batch[key])
                assert got.dtype == expected.dtype and \
                    np.array_equal(got, expected), (step, key)
            kept = batch["mask"].sum(axis=1)
            assert ((kept >= 45) & (kept <= 48)).all()  # a tail <= 1/16
            assert np.array_equal(batch["tokens"][:, 1:],
                                  batch["labels"][:, :-1])
    finally:
        loader.close()


def test_padded_rows_are_masked_out_whole():
    ds = TokenDataset(6, 48, 300)
    loader = DataLoader(ds, 4, num_workers=1)  # pad_final: 4 + (2 + 2 pad)
    try:
        batches = list(loader.epoch(0))
    finally:
        loader.close()
    assert batches[0]["mask"].any(axis=1).all()
    assert list(batches[1]["mask"].any(axis=1)) == [True, True, False, False]
    assert batches[1]["tokens"].shape == (4, 48)


@pytest.mark.parametrize("source,want", [
    ("tokens:64", (64, 0)), ("tokens:64@7", (64, 7)), ("tokens", (2048, 0)),
    ("synthetic:64", None)])
def test_parse_source(source, want):
    assert parse_source(source) == want


@pytest.mark.parametrize("source", ["tokens:x", "tokens:0", "tokens:8@-1"])
def test_parse_source_fails_fast(source):
    with pytest.raises(ValueError, match="tokens:<rows>|at least one row"):
        parse_source(source)


# ----------------------------------------------------------- through fit --

_ARGS = ["-a", "lfm2_test_tiny", "--optimizer", "adamw", "--beta2", "0.95",
         "--wd", "0.1", "--lr", "0.08", "-b", "2", "--layers", "1:3",
         "--experts", "0:4", "--vocab-rows", "0:128", "--seq-len", "32",
         "--opt-level", "O2", "-p", "4"]


@pytest.fixture(scope="module")
def tiny_arch():
    import tests.test_lfm2  # noqa: F401 (registers lfm2_test_tiny)


def test_fit_trains_validates_and_resumes_a_token_model(
        tiny_arch, tmp_path, monkeypatch, capsys):
    from dptpu.cli import main_apex

    from dptpu.models import lfm2

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("WORLD_SIZE", "1")
    # the CPU reports no memory, so fit() would keep nothing through the
    # rematerialisation: stand in a device of 1 GB beside the state, and
    # every run below keeps every class
    asked, fitted_to = [], lfm2.Lfm2.fitted_to

    def on_a_device_with_room(self, device_bytes, state_bytes):
        asked.append((device_bytes, state_bytes))
        return fitted_to(
            self, state_bytes + lfm2.STEP_HEADROOM_BYTES + 10**9, state_bytes)

    monkeypatch.setattr(lfm2.Lfm2, "fitted_to", on_a_device_with_room)
    ckpt = str(tmp_path / "ckpt")
    straight = main_apex(["tokens:64", *_ARGS, "--epochs", "2",
                          "--ckpt-dir", str(tmp_path / "straight")])
    out = capsys.readouterr().out
    # float32 weights and AdamW's two moments, to a few scalars
    (device_bytes, state_bytes), = asked
    params = sum(x.size for x in jax.tree_util.tree_leaves(
        straight["state"].params))
    assert device_bytes == 0 and 0 <= state_bytes - 12 * params < 4096
    assert ("=> residuals kept through the rematerialisation: attention "
            "out+lse, q/k/v projections, mixer projections, expert rows "
            "and products, dense feed-forward (0 MB a step of a budget of "
            "1,000 MB)") in out  # 0.2 MB at this size
    epoch = straight["history"][0]
    # uniform random ids: the loss cannot pass ln(128), and starts above
    assert np.log(128) - 0.05 < epoch["train_loss"] < 6.0
    assert np.isfinite(epoch["val_loss"]) and 0 <= epoch["val_top1"] <= 100
    assert epoch["val_count"] == pytest.approx(6 * 32, abs=6 * 2)  # tokens
    assert "Moe: busiest held expert" in out and "0 tokens dropped" in out
    assert "(100.0% of the layer steps in the compact buffer)" in out
    assert 40 < epoch["train_moe_local_slot_share"] < 60  # 4 of 8 held
    assert epoch["train_moe_dropped"] == 0
    assert epoch["train_moe_compact_share"] == 100.0
    # one epoch, saved; resumed for the second: the loss goes on as in the
    # run that was never stopped (parameters, both moments, the step)
    main_apex(["tokens:64", *_ARGS, "--epochs", "1", "--ckpt-dir", ckpt])
    resumed = main_apex(["tokens:64", *_ARGS, "--epochs", "2", "--resume",
                         os.path.join(ckpt, "checkpoint.pth.tar"),
                         "--ckpt-dir", ckpt])
    assert [h["epoch"] for h in resumed["history"]] == [1]
    for key in ("train_loss", "val_loss"):
        assert resumed["history"][0][key] == pytest.approx(
            straight["history"][1][key], abs=1e-6), key


@pytest.mark.parametrize("argv,message", [
    (["synthetic:64", "-a", "lfm2_test_tiny", "-b", "2"],
     "give tokens:<N> as the data source"),
    (["tokens:64", "-a", "resnet18", "-b", "2"], "source of token rows"),
    (["synthetic:64", "-a", "resnet18", "-b", "2", "--seq-len", "32"],
     "token-sequence model's arguments"),
    (["tokens:64", "-a", "lfm2_test_tiny", "-b", "2", "--accum-steps", "2"],
     "without --accum-steps")])
def test_fit_refuses_what_does_not_fit_the_task(tiny_arch, argv, message,
                                                tmp_path, monkeypatch):
    from dptpu.cli import main_apex

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("WORLD_SIZE", "1")
    with pytest.raises(ValueError, match=message):
        main_apex(argv)
