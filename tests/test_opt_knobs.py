"""The locked fail-fast env-knob contract, large-batch-engine edition
(mirrors tests/test_feed_knobs.py): every explicitly-set-but-invalid
value of DPTPU_OPT / DPTPU_ACCUM / DPTPU_WARMUP_EPOCHS /
DPTPU_LABEL_SMOOTH raises pre-compile with an actionable message, the
env twin overrides the CLI/config field, and config values passed
programmatically get the identical validation as env values.
"""

import pytest

from dptpu.config import Config
from dptpu.train.fit import opt_knobs

_KNOBS = ("DPTPU_OPT", "DPTPU_ACCUM", "DPTPU_WARMUP_EPOCHS",
          "DPTPU_LABEL_SMOOTH")


def _cfg(**kw):
    return Config(data="synthetic:16", **kw)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in _KNOBS:
        monkeypatch.delenv(k, raising=False)


def test_defaults_reproduce_reference(monkeypatch):
    # unset env + default config = the reference recipe exactly
    assert opt_knobs(_cfg()) == ("sgd", 1, 0, 0.0)


def test_env_overrides_config(monkeypatch):
    cfg = _cfg(optimizer="sgd", accum_steps=1, warmup_epochs=0,
                 label_smoothing=0.0)
    monkeypatch.setenv("DPTPU_OPT", "lars")
    monkeypatch.setenv("DPTPU_ACCUM", "4")
    monkeypatch.setenv("DPTPU_WARMUP_EPOCHS", "5")
    monkeypatch.setenv("DPTPU_LABEL_SMOOTH", "0.1")
    assert opt_knobs(cfg) == ("lars", 4, 5, 0.1)


def test_config_values_pass_through():
    cfg = _cfg(optimizer="lamb", accum_steps=2, warmup_epochs=3,
                 label_smoothing=0.2)
    assert opt_knobs(cfg) == ("lamb", 2, 3, 0.2)


def test_opt_choice_validated_env_and_config(monkeypatch):
    monkeypatch.setenv("DPTPU_OPT", "adam")
    with pytest.raises(ValueError, match="DPTPU_OPT"):
        opt_knobs(_cfg())
    monkeypatch.delenv("DPTPU_OPT")
    with pytest.raises(ValueError, match="--optimizer"):
        opt_knobs(_cfg(optimizer="adam"))


def test_accum_zero_negative_garbage_raise(monkeypatch):
    for bad in ("0", "-2"):
        monkeypatch.setenv("DPTPU_ACCUM", bad)
        with pytest.raises(ValueError, match="DPTPU_ACCUM"):
            opt_knobs(_cfg())
    monkeypatch.setenv("DPTPU_ACCUM", "many")
    with pytest.raises(ValueError, match="not an integer"):
        opt_knobs(_cfg())
    monkeypatch.delenv("DPTPU_ACCUM")
    # config field hits the same validation as the env twin
    for bad in (0, -1):
        with pytest.raises(ValueError, match="accum-steps"):
            opt_knobs(_cfg(accum_steps=bad))
    # =1 is the documented off value, never an error
    assert opt_knobs(_cfg(accum_steps=1))[1] == 1


def test_warmup_negative_and_garbage_raise(monkeypatch):
    monkeypatch.setenv("DPTPU_WARMUP_EPOCHS", "-1")
    with pytest.raises(ValueError, match="DPTPU_WARMUP_EPOCHS"):
        opt_knobs(_cfg())
    monkeypatch.setenv("DPTPU_WARMUP_EPOCHS", "soon")
    with pytest.raises(ValueError, match="not an integer"):
        opt_knobs(_cfg())
    monkeypatch.delenv("DPTPU_WARMUP_EPOCHS")
    with pytest.raises(ValueError, match="warmup-epochs"):
        opt_knobs(_cfg(warmup_epochs=-3))
    # explicit 0 keeps the reference schedule — valid
    assert opt_knobs(_cfg(warmup_epochs=0))[2] == 0


def test_warmup_swallowing_the_whole_run_raises(monkeypatch):
    """warmup >= epochs would clamp the cosine phase away and the run
    would never reach peak LR — silently-worse training, so it fails
    fast like every other invalid knob (env twin and config field)."""
    with pytest.raises(ValueError, match="mid-warmup"):
        opt_knobs(_cfg(epochs=10, warmup_epochs=10))
    with pytest.raises(ValueError, match="mid-warmup"):
        opt_knobs(_cfg(epochs=10, warmup_epochs=25))
    monkeypatch.setenv("DPTPU_WARMUP_EPOCHS", "90")
    with pytest.raises(ValueError, match="mid-warmup"):
        opt_knobs(_cfg(epochs=90))
    # the last warmup-compatible value is valid
    monkeypatch.delenv("DPTPU_WARMUP_EPOCHS")
    assert opt_knobs(_cfg(epochs=10, warmup_epochs=9))[2] == 9


def test_label_smooth_range_and_garbage_raise(monkeypatch):
    for bad in ("1.0", "-0.1", "2"):
        monkeypatch.setenv("DPTPU_LABEL_SMOOTH", bad)
        with pytest.raises(ValueError, match="DPTPU_LABEL_SMOOTH"):
            opt_knobs(_cfg())
    monkeypatch.setenv("DPTPU_LABEL_SMOOTH", "a little")
    with pytest.raises(ValueError, match="not a number"):
        opt_knobs(_cfg())
    monkeypatch.delenv("DPTPU_LABEL_SMOOTH")
    with pytest.raises(ValueError, match="label-smoothing"):
        opt_knobs(_cfg(label_smoothing=1.0))
    # boundary: 0 valid (off), 0.999... valid
    assert opt_knobs(_cfg(label_smoothing=0.0))[3] == 0.0
    assert opt_knobs(_cfg(label_smoothing=0.9))[3] == 0.9


def test_fit_rejects_accum_not_dividing_per_device_batch(monkeypatch):
    """fit() fails fast (pre-mesh, pre-compile) when accum does not
    divide the per-device batch — the microbatch must be integral."""
    from dptpu.train.fit import fit

    # 8 fake devices (conftest): batch 8 -> per-device 1; accum 3 can't
    # divide it
    cfg = Config(data="synthetic:16", arch="resnet18", batch_size=8,
                 epochs=1, accum_steps=3)
    with pytest.raises(ValueError, match="does not divide"):
        fit(cfg, image_size=32, verbose=False)


def test_sp_accum_error_names_knob_and_alternative(monkeypatch):
    """The sequence-parallel step's accumulation fail-fast (ROADMAP
    PR-6 follow-on) must name the offending knob AND the supported
    alternatives, not just refuse — locked here so a reworded message
    cannot silently lose the actionable half."""
    from dptpu.train.fit import fit

    monkeypatch.setenv("DPTPU_SP", "2")
    # batch 16 on the 8-device fake pod -> per-device 2, accum 2
    # divides it, so the SP x accum conflict is the FIRST error hit
    cfg = Config(data="synthetic:16", arch="vit_b_32", batch_size=16,
                 epochs=1, accum_steps=2)
    with pytest.raises(ValueError) as ei:
        fit(cfg, image_size=32, verbose=False)
    msg = str(ei.value)
    assert "DPTPU_ACCUM=2" in msg  # the offending knob, with its value
    assert "DPTPU_SP=2" in msg  # the conflicting axis knob
    # both supported alternatives are spelled out
    assert "DPTPU_ACCUM=1" in msg
    assert "unset DPTPU_SP" in msg


def test_cli_flags_parse_into_config():
    from dptpu.config import parse_config

    cfg = parse_config([
        "--optimizer", "lamb", "--accum-steps", "4",
        "--warmup-epochs", "5", "--label-smoothing", "0.1", "data",
    ], variant="ddp")
    assert (cfg.optimizer, cfg.accum_steps, cfg.warmup_epochs,
            cfg.label_smoothing) == ("lamb", 4, 5, 0.1)
    # the parser rejects an unknown optimizer at the CLI boundary too
    with pytest.raises(SystemExit):
        parse_config(["--optimizer", "adam", "data"], variant="ddp")


# --------------------------------------------------- ISSUE 13: recipe knobs
# DPTPU_BATCH_RAMP / DPTPU_WARMUP_POLY (parse in dptpu/ops/schedules.py,
# wiring + composition fail-fasts in fit) under the same locked contract.


@pytest.fixture()
def _clean_recipe_env(monkeypatch):
    for k in ("DPTPU_BATCH_RAMP", "DPTPU_WARMUP_POLY", "DPTPU_OVERLAP",
              "DPTPU_BUCKET_MB", "DPTPU_DIST_EVAL",
              "DPTPU_STRAGGLER_FACTOR"):
        monkeypatch.delenv(k, raising=False)
    return monkeypatch


def test_parse_batch_ramp_happy_path():
    from dptpu.ops.schedules import parse_batch_ramp, ramp_multiplier

    ramp = parse_batch_ramp("4:2,8:4")
    assert ramp == [(0, 1), (4, 2), (8, 4)]  # implied epoch-0 phase
    assert [ramp_multiplier(ramp, e) for e in (0, 3, 4, 7, 8, 99)] == \
        [1, 1, 2, 2, 4, 4]


def test_parse_batch_ramp_explicit_epoch0():
    from dptpu.ops.schedules import parse_batch_ramp

    assert parse_batch_ramp("0:2,5:4") == [(0, 2), (5, 4)]


@pytest.mark.parametrize("bad", ["junk", "4", "4:", ":2", "4:0", "-1:2",
                                 "4:2,4:3", "8:2,4:4", "", " , "])
def test_parse_batch_ramp_malformed_raises(bad):
    from dptpu.ops.schedules import parse_batch_ramp

    with pytest.raises(ValueError, match="DPTPU_BATCH_RAMP"):
        parse_batch_ramp(bad)


def test_fit_warmup_poly_invalid_raises(_clean_recipe_env):
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_WARMUP_POLY", "0")
    cfg = Config(data="synthetic:16", arch="resnet18", batch_size=8,
                 epochs=1, warmup_epochs=0)
    with pytest.raises(ValueError, match="DPTPU_WARMUP_POLY"):
        fit(cfg, image_size=32, verbose=False)


def test_fit_warmup_poly_needs_warmup(_clean_recipe_env):
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_WARMUP_POLY", "2")
    cfg = Config(data="synthetic:16", arch="resnet18", batch_size=8,
                 epochs=1, warmup_epochs=0)
    with pytest.raises(ValueError, match="--warmup-epochs"):
        fit(cfg, image_size=32, verbose=False)


def test_fit_batch_ramp_needs_warmup(_clean_recipe_env):
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_BATCH_RAMP", "1:2")
    cfg = Config(data="synthetic:16", arch="resnet18", batch_size=8,
                 epochs=2, warmup_epochs=0)
    with pytest.raises(ValueError, match="DPTPU_BATCH_RAMP"):
        fit(cfg, image_size=32, verbose=False)


def test_fit_batch_ramp_beyond_epochs_raises(_clean_recipe_env):
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_BATCH_RAMP", "5:2")
    cfg = Config(data="synthetic:16", arch="resnet18", batch_size=8,
                 epochs=3, warmup_epochs=1)
    with pytest.raises(ValueError, match="--epochs"):
        fit(cfg, image_size=32, verbose=False)


def test_fit_batch_ramp_straggler_composition_allowed(_clean_recipe_env):
    """The ramp x straggler refusal is gone: StragglerController
    survives the DPTPU_BATCH_RAMP pool rebuild via rebind() (semantics
    locked in tests/test_tune.py), so fit must accept the pair and run
    the ramp to completion."""
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_BATCH_RAMP", "1:2")
    _clean_recipe_env.setenv("DPTPU_STRAGGLER_FACTOR", "2.0")
    cfg = Config(data="synthetic:64", arch="resnet18", batch_size=16,
                 epochs=3, warmup_epochs=1)
    result = fit(cfg, image_size=32, verbose=False)
    assert len(result["history"]) == 3
    # the ramp actually fired: epoch 1+ trains the doubled batch
    assert result["batch_ramp"][-1]["global_batch"] == 32


def test_fit_batch_ramp_tp_composition_names_alternatives(
        _clean_recipe_env):
    from dptpu.train.fit import fit

    _clean_recipe_env.setenv("DPTPU_BATCH_RAMP", "1:2")
    _clean_recipe_env.setenv("DPTPU_TP", "2")
    cfg = Config(data="synthetic:64", arch="vit_b_32", batch_size=16,
                 epochs=3, warmup_epochs=1)
    with pytest.raises(ValueError) as ei:
        fit(cfg, image_size=32, verbose=False)
    msg = str(ei.value)
    assert "DPTPU_BATCH_RAMP" in msg and "DPTPU_TP" in msg
    assert "unset" in msg  # both alternatives spelled out


def test_poly_power_one_is_linear_warmup():
    """DPTPU_WARMUP_POLY=1 must be bit-identical to the linear ramp —
    the power path is never traced at p=1 (dptpu/ops/schedules.py)."""
    import numpy as np

    from dptpu.ops.schedules import make_warmup_cosine_schedule

    lin = make_warmup_cosine_schedule(2.0, 10, 4, 1)
    p1 = make_warmup_cosine_schedule(2.0, 10, 4, 1, power=1.0)
    for step in range(40):
        np.testing.assert_array_equal(np.asarray(lin(step)),
                                      np.asarray(p1(step)))


def test_poly_power_two_bends_warmup():
    import numpy as np

    from dptpu.ops.schedules import make_warmup_cosine_schedule

    lin = make_warmup_cosine_schedule(2.0, 10, 4, 2)
    p2 = make_warmup_cosine_schedule(2.0, 10, 4, 2, power=2.0)
    # polynomial warmup sits strictly below linear mid-ramp ...
    assert float(p2(5)) < float(lin(5))
    # ... and both land on the same peak / cosine tail
    np.testing.assert_allclose(float(p2(30)), float(lin(30)), rtol=1e-6)


def test_ramp_phase_schedule_is_continuous_at_boundary():
    """The phase schedule chains in fractional epochs: the epoch the
    ramp fires, the NEW phase's schedule evaluated at the boundary step
    equals the old phase's trajectory at the same epoch, scaled x mult
    (the linear-scaling jump is the ONLY discontinuity)."""
    from dptpu.ops.schedules import make_ramp_phase_schedule

    spe0, spe1 = 8, 4  # phase 1 has half the steps (double batch)
    s0 = make_ramp_phase_schedule(1.0, spe0, 10, 2, epoch0=0, step0=0)
    s1 = make_ramp_phase_schedule(2.0, spe1, 10, 2, epoch0=4,
                                  step0=4 * spe0)
    boundary = 4 * spe0
    lr_old = float(s0(boundary))       # what phase 0 would have taken
    lr_new = float(s1(boundary))       # what phase 1 actually takes
    assert abs(lr_new - 2.0 * lr_old) < 2.0 * 0.02  # x mult, same shape
