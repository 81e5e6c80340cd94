"""``dptpu.train.plan.decide``: which step family runs, on which mesh.

The precedence DPTPU_TP > DPTPU_SP > DPTPU_ZERO=3 > DPTPU_ZERO1 >
DPTPU_GSPMD, every "=> ... ignored" notice a loser prints, the refusals
and the checkpoint fingerprint, as one table over a pure function: no
mesh on devices, no compile, milliseconds a case.
"""

import types

import pytest

from dptpu.config import Config
from dptpu.train.plan import decide

_KNOBS = ("DPTPU_TP", "DPTPU_SP", "DPTPU_SP_MODE", "DPTPU_SLICES",
          "DPTPU_DCN_DTYPE", "DPTPU_ZERO", "DPTPU_ZERO1", "DPTPU_FSDP",
          "DPTPU_GSPMD", "DPTPU_OVERLAP", "DPTPU_BUCKET_MB")
_RAMP = ((0, 1), (2, 2))  # a parsed DPTPU_BATCH_RAMP: x2 from epoch 2 on


def _decide(monkeypatch, env=None, *, arch="resnet18", devices=8,
            task="images", accum=1, ramp=None, sync_bn=False, **cfg):
    for k in _KNOBS:
        monkeypatch.delenv(k, raising=False)
    for k, v in (env or {}).items():
        monkeypatch.setenv(k, v)
    return decide(
        Config(data="synthetic:8", arch=arch, **cfg),
        types.SimpleNamespace(sync_bn=sync_bn),
        task=task, n_devices=devices, accum_steps=accum, batch_ramp=ramp,
    )


def _in_order(notices, *parts):
    """Each of ``parts`` opens (after "=> ") one notice, in this order."""
    at = -1
    for part in parts:
        hits = [i for i, n in enumerate(notices)
                if n.startswith("=> " + part)]
        assert hits, (part, notices)
        assert hits[0] > at, (part, notices)
        at = hits[0]


# env, keyword arguments -> family, mesh axes, the notices' openings
_CHOSEN = [
    pytest.param({}, {}, "ddp", {"data": 8}, (), id="nothing-set"),
    pytest.param({"DPTPU_ZERO": "1"}, {}, "zero1", {"data": 8}, (),
                 id="zero1-by-stage"),
    pytest.param({"DPTPU_ZERO1": "1"}, {}, "zero1", {"data": 8}, (),
                 id="zero1-by-flag"),
    pytest.param({"DPTPU_ZERO": "3"}, {}, "zero3", {"data": 8}, (),
                 id="zero3-by-stage"),
    pytest.param({"DPTPU_FSDP": "1"}, {}, "zero3", {"data": 8}, (),
                 id="zero3-by-fsdp"),
    pytest.param({"DPTPU_GSPMD": "1"}, {}, "gspmd", {"data": 8}, (),
                 id="gspmd"),
    pytest.param({"DPTPU_GSPMD": "1"}, {"sync_bn": True}, "gspmd",
                 {"data": 8}, ("--sync-bn is implicit under DPTPU_GSPMD",),
                 id="gspmd-sync-bn"),
    pytest.param({"DPTPU_TP": "2"}, {"arch": "vit_b_32"}, "gspmd",
                 {"data": 4, "model": 2}, (), id="tp"),
    pytest.param({"DPTPU_SP": "2", "DPTPU_SP_MODE": "ring"},
                 {"arch": "vit_b_32"}, "seq", {"data": 4, "seq": 2}, (),
                 id="sp"),
    pytest.param({"DPTPU_TP": "1", "DPTPU_SP": "1", "DPTPU_SLICES": "1"},
                 {}, "ddp", {"data": 8},
                 ("DPTPU_TP=1 is a no-op", "DPTPU_SP=1 is a no-op",
                  "DPTPU_SLICES=1 is a no-op"), id="one-way-axes"),
    # the precedence, pairwise, with what the loser prints
    pytest.param({"DPTPU_TP": "2", "DPTPU_SP": "2"}, {"arch": "vit_b_32"},
                 "gspmd", {"data": 4, "model": 2},
                 ("DPTPU_SP ignored: DPTPU_TP takes precedence",),
                 id="tp-over-sp"),
    pytest.param({"DPTPU_TP": "2", "DPTPU_ZERO": "3"},
                 {"arch": "vit_b_32"}, "gspmd", {"data": 4, "model": 2},
                 ("DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_TP drives",),
                 id="tp-over-zero3"),
    pytest.param({"DPTPU_TP": "2", "DPTPU_ZERO1": "1"},
                 {"arch": "vit_b_32"}, "gspmd", {"data": 4, "model": 2},
                 ("DPTPU_ZERO1 ignored: DPTPU_TP drives the GSPMD",),
                 id="tp-over-zero1"),
    pytest.param({"DPTPU_TP": "2", "DPTPU_GSPMD": "1"},
                 {"arch": "vit_b_32"}, "gspmd", {"data": 4, "model": 2}, (),
                 id="tp-is-gspmd"),
    pytest.param({"DPTPU_SP": "2", "DPTPU_FSDP": "1"},
                 {"arch": "vit_b_32"}, "seq", {"data": 4, "seq": 2},
                 ("DPTPU_ZERO=3/DPTPU_FSDP ignored: DPTPU_SP drives",),
                 id="sp-over-zero3"),
    pytest.param({"DPTPU_SP": "2", "DPTPU_ZERO": "1"},
                 {"arch": "vit_b_32"}, "seq", {"data": 4, "seq": 2},
                 ("DPTPU_ZERO1 ignored: DPTPU_SP drives",),
                 id="sp-over-zero1"),
    pytest.param({"DPTPU_SP": "2", "DPTPU_GSPMD": "1"},
                 {"arch": "vit_b_32"}, "seq", {"data": 4, "seq": 2},
                 ("DPTPU_GSPMD ignored: DPTPU_SP drives",),
                 id="sp-over-gspmd"),
    pytest.param({"DPTPU_ZERO": "3", "DPTPU_ZERO1": "1"}, {}, "zero3",
                 {"data": 8},
                 ("DPTPU_ZERO1 noted: DPTPU_ZERO=3 supersedes it",),
                 id="zero3-over-zero1"),
    pytest.param({"DPTPU_FSDP": "1", "DPTPU_GSPMD": "1"}, {}, "zero3",
                 {"data": 8},
                 ("DPTPU_GSPMD ignored: DPTPU_ZERO=3 takes precedence",),
                 id="zero3-over-gspmd"),
    pytest.param({"DPTPU_ZERO1": "1", "DPTPU_GSPMD": "1"}, {}, "zero1",
                 {"data": 8},
                 ("DPTPU_GSPMD ignored: DPTPU_ZERO1 takes precedence",),
                 id="zero1-over-gspmd"),
    # a request the arch cannot use is demoted, and does not suppress
    # what ranks below it
    pytest.param({"DPTPU_TP": "2"}, {}, "gspmd", {"data": 8},
                 ("DPTPU_TP=2: no tensor-parallel rule for 'resnet18'",),
                 id="tp-demoted"),
    pytest.param({"DPTPU_TP": "2", "DPTPU_ZERO1": "1"}, {}, "zero1",
                 {"data": 8},
                 ("DPTPU_TP=2: no tensor-parallel rule for 'resnet18'",),
                 id="tp-demoted-leaves-zero1"),
    pytest.param({"DPTPU_SP": "2", "DPTPU_ZERO1": "1"}, {}, "zero1",
                 {"data": 8},
                 ("DPTPU_SP=2: no sequence-parallel path for 'resnet18'",),
                 id="sp-on-a-cnn"),
    pytest.param({"DPTPU_SP": "2"}, {"arch": "lfm2_8b_a1b",
                                     "task": "tokens"}, "ddp", {"data": 8},
                 ("DPTPU_SP=2: no sequence-parallel path",),
                 id="sp-on-a-token-model"),
    # nothing to shard over, or nothing trained: every request says so
    pytest.param(
        {"DPTPU_TP": "2", "DPTPU_SP": "2", "DPTPU_SLICES": "2",
         "DPTPU_DCN_DTYPE": "bf16", "DPTPU_ZERO": "3", "DPTPU_ZERO1": "1",
         "DPTPU_GSPMD": "1", "DPTPU_OVERLAP": "1"},
        {"arch": "vit_b_32", "devices": 1}, "ddp", {},
        ("DPTPU_TP ignored: single-device run",
         "DPTPU_SP ignored: single-device run",
         "DPTPU_SLICES=2 ignored: single-device run",
         "DPTPU_DCN_DTYPE=bf16 ignored: no hierarchical mesh",
         "DPTPU_GSPMD ignored: single-device run (no mesh)",
         "DPTPU_OVERLAP ignored: single-device run",
         "DPTPU_ZERO=3/DPTPU_FSDP ignored: single-device run",
         "DPTPU_ZERO1 ignored: single-device run"), id="single-device"),
    pytest.param({"DPTPU_ZERO1": "1"}, {"gpu": 0}, "ddp", {},
                 ("DPTPU_ZERO1 ignored: single-device run",),
                 id="one-device-by---gpu"),
    pytest.param(
        {"DPTPU_TP": "2", "DPTPU_SLICES": "2", "DPTPU_ZERO1": "1",
         "DPTPU_GSPMD": "1", "DPTPU_OVERLAP": "1"},
        {"arch": "vit_b_32", "evaluate": True}, "ddp", {"data": 8},
        ("DPTPU_TP ignored: --evaluate does not train",
         "DPTPU_SLICES=2 ignored: --evaluate does not train",
         "DPTPU_GSPMD ignored: --evaluate does not train",
         "DPTPU_OVERLAP ignored: --evaluate does not train",
         "DPTPU_ZERO1 ignored: --evaluate does not train"),
        id="evaluate"),
    # slices
    pytest.param({"DPTPU_SLICES": "2", "DPTPU_DCN_DTYPE": "bf16"}, {},
                 "ddp", {"slice": 2, "data": 4},
                 ("hierarchical data parallelism: 2 slices x 4 chips/slice "
                  "— gradient reduction is reduce-scatter(ICI) + "
                  "shard-sized all-reduce(DCN, bf16)",), id="slices"),
    pytest.param({}, {"slices": 4, "arch": "lfm2_8b_a1b",
                      "task": "tokens"}, "ddp", {"slice": 4, "data": 2},
                 ("hierarchical data parallelism: 4 slices x 2",),
                 id="slices-by-flag-token-model"),
    pytest.param({"DPTPU_SLICES": "2", "DPTPU_GSPMD": "1"}, {}, "gspmd",
                 {"slice": 2, "data": 4},
                 ("hierarchical data parallelism: 2 slices x 4 chips/slice "
                  "— the SPMD partitioner derives",), id="slices-gspmd"),
    pytest.param({"DPTPU_SLICES": "2", "DPTPU_ZERO": "3"}, {}, "zero3",
                 {"slice": 2, "data": 4},
                 ("hierarchical data parallelism: 2 slices x 4",),
                 id="slices-zero3"),
    pytest.param({"DPTPU_SLICES": "2", "DPTPU_TP": "2"},
                 {"arch": "vit_b_32"}, "gspmd", {"data": 4, "model": 2},
                 ("DPTPU_SLICES=2 ignored: DPTPU_TP drives",),
                 id="tp-over-slices"),
    # overlap
    pytest.param({"DPTPU_OVERLAP": "1", "DPTPU_BUCKET_MB": "2"}, {}, "ddp",
                 {"data": 8},
                 ("overlapped gradient comms: reverse-layer buckets of "
                  "<= 2 MB",), id="overlap"),
    pytest.param({"DPTPU_OVERLAP": "1", "DPTPU_SP": "2"},
                 {"arch": "vit_b_32"}, "seq", {"data": 4, "seq": 2},
                 ("DPTPU_OVERLAP ignored: DPTPU_SP drives",),
                 id="sp-over-overlap"),
    pytest.param({"DPTPU_BUCKET_MB": "0.5"}, {}, "ddp", {"data": 8},
                 ("DPTPU_BUCKET_MB=0.5 noted: the bucket bound only "
                  "applies with DPTPU_OVERLAP=1",), id="bucket-alone"),
]


@pytest.mark.parametrize("env,kw,family,axes,notices", _CHOSEN)
def test_the_family_the_mesh_and_what_each_loser_prints(
        monkeypatch, env, kw, family, axes, notices):
    plan = _decide(monkeypatch, env, **kw)
    assert (plan.family, plan.mesh_axes) == (family, axes)
    assert len(plan.notices) == len(notices), plan.notices
    _in_order(plan.notices, *notices)
    # what the builders are handed follows the family
    assert plan.overlap == (env.get("DPTPU_OVERLAP") == "1"
                            and bool(axes) and family != "seq"
                            and not kw.get("evaluate"))
    assert plan.sp_mode == env.get("DPTPU_SP_MODE", "ulysses")


_TOKENS = "is a token-sequence model: it trains on the replicated"
_REFUSED = [
    pytest.param({"DPTPU_ZERO": "3"}, {"arch": "lfm2_8b_a1b",
                                       "task": "tokens"}, _TOKENS,
                 id="tokens-zero3"),
    pytest.param({"DPTPU_ZERO1": "1"}, {"arch": "lfm2_8b_a1b",
                                        "task": "tokens"}, _TOKENS,
                 id="tokens-zero1"),
    pytest.param({"DPTPU_GSPMD": "1"}, {"arch": "lfm2_8b_a1b",
                                        "task": "tokens"}, _TOKENS,
                 id="tokens-gspmd"),
    pytest.param({"DPTPU_TP": "2"}, {"arch": "lfm2_8b_a1b",
                                     "task": "tokens"}, _TOKENS,
                 id="tokens-tp"),
    pytest.param({}, {"arch": "lfm2_8b_a1b", "task": "tokens",
                      "ramp": _RAMP}, _TOKENS, id="tokens-ramp"),
    # by task, not by name: the second token model is refused the same
    pytest.param({"DPTPU_ZERO1": "1"}, {"arch": "joyai_llm_flash",
                                        "task": "tokens"}, _TOKENS,
                 id="tokens-zero1-joyai"),
    pytest.param({}, {"arch": "joyai_llm_flash", "task": "tokens",
                      "ramp": _RAMP}, _TOKENS, id="tokens-ramp-joyai"),
    pytest.param({"DPTPU_GSPMD": "1"}, {"ramp": _RAMP},
                 "DPTPU_BATCH_RAMP has no DPTPU_GSPMD composition",
                 id="ramp-gspmd"),
    pytest.param({"DPTPU_TP": "2"}, {"arch": "vit_b_32", "ramp": _RAMP},
                 "DPTPU_BATCH_RAMP has no DPTPU_TP composition",
                 id="ramp-tp"),
    pytest.param({"DPTPU_SP": "2"}, {"arch": "vit_b_32", "ramp": _RAMP},
                 "DPTPU_BATCH_RAMP has no DPTPU_SP composition",
                 id="ramp-sp"),
    pytest.param({"DPTPU_SP": "2"}, {"arch": "vit_b_32", "accum": 2},
                 r"--accum-steps/DPTPU_ACCUM=2 has no sequence-parallel "
                 r"implementation \(DPTPU_SP=2", id="sp-accum"),
    pytest.param({"DPTPU_TP": "3"}, {"arch": "vit_b_32"},
                 "DPTPU_TP=3 does not divide the 8 available devices",
                 id="tp-does-not-divide"),
    pytest.param({"DPTPU_SP": "3"}, {"arch": "vit_b_32"},
                 "DPTPU_SP=3 does not divide the 8 available devices",
                 id="sp-does-not-divide"),
    pytest.param({"DPTPU_TP": "0"}, {}, "DPTPU_TP=0 must be a positive",
                 id="tp-zero"),
    pytest.param({"DPTPU_SP_MODE": "rings"}, {}, "DPTPU_SP_MODE",
                 id="sp-mode-junk-while-off"),
    pytest.param({"DPTPU_ZERO": "2"}, {},
                 "DPTPU_ZERO=2 is not a supported stage", id="zero-stage-2"),
    pytest.param({"DPTPU_ZERO1": "flase"}, {}, "is not a boolean",
                 id="zero1-junk"),
    pytest.param({"DPTPU_SLICES": "0"}, {},
                 "DPTPU_SLICES/--slices 0 must be >= 1", id="slices-zero"),
    pytest.param({"DPTPU_DCN_DTYPE": "f16"}, {}, "DPTPU_DCN_DTYPE",
                 id="dcn-dtype-junk"),
    pytest.param({"DPTPU_BUCKET_MB": "0"}, {},
                 "DPTPU_BUCKET_MB=0.0 must be > 0 MB",
                 id="bucket-zero-while-off"),
]


@pytest.mark.parametrize("env,kw,message", _REFUSED)
def test_what_decide_refuses_by_message(monkeypatch, env, kw, message):
    with pytest.raises(ValueError, match=message):
        _decide(monkeypatch, env, **kw)


def test_a_ramp_composes_with_the_shard_map_families(monkeypatch):
    for env in ({}, {"DPTPU_ZERO1": "1"}, {"DPTPU_ZERO": "3"},
                {"DPTPU_SLICES": "2"}):
        assert _decide(monkeypatch, env, ramp=_RAMP).family in (
            "ddp", "zero1", "zero3")


def test_the_fingerprint_follows_the_placement(monkeypatch):
    """Equal plans stamp equal fingerprints, whichever spelling chose
    them; a family that places the state otherwise stamps another."""
    prints = {
        name: _decide(monkeypatch, env, arch="vit_b_32").fingerprint
        for name, env in {
            "ddp": {}, "zero1": {"DPTPU_ZERO": "1"},
            "zero1-flag": {"DPTPU_ZERO1": "1"},
            "zero3": {"DPTPU_ZERO": "3"}, "fsdp": {"DPTPU_FSDP": "1"},
            "tp2": {"DPTPU_TP": "2"}, "tp4": {"DPTPU_TP": "4"},
            "gspmd": {"DPTPU_GSPMD": "1"}, "sp": {"DPTPU_SP": "2"},
            "slices": {"DPTPU_SLICES": "2"},
            "slices-gspmd": {"DPTPU_SLICES": "2", "DPTPU_GSPMD": "1"},
            "overlap": {"DPTPU_OVERLAP": "1"},
        }.items()
    }
    assert prints["zero1"] == prints["zero1-flag"]
    assert prints["zero3"] == prints["fsdp"]
    # replicated parameters, however the gradient travels
    assert {prints[k] for k in ("ddp", "gspmd", "sp", "slices",
                                "overlap")} == {"replicated"}
    placed = [prints[k] for k in ("ddp", "zero1", "zero3", "tp2", "tp4",
                                  "slices-gspmd")]
    assert len(set(placed)) == len(placed)
    assert [p.rsplit(":", 1)[-1] for p in placed[1:]] == [
        "zero1", "zero3", "tp2", "tp4", "fsdp"]
    # ZeRO-1 places by the generic table: another family's rules do not
    # move its stamp; ZeRO-3 and TP place by the arch's own
    cnn = {k: _decide(monkeypatch, env, arch="resnet18").fingerprint
           for k, env in (("zero1", {"DPTPU_ZERO1": "1"}),
                          ("zero3", {"DPTPU_ZERO": "3"}))}
    assert cnn["zero1"] == prints["zero1"]
    assert cnn["zero3"] != prints["zero3"]
