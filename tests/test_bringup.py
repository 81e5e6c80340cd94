"""Bring-up locks (ISSUE 21): what must stay true for the repo to run on
the installed jax and for ``chip_smoke.py`` to mean something.

* the shard_map checker is OFF in every dptpu step (with it on, the
  explicit gradient psum sums an already-reduced value: N x the update
  on N chips) and ``chip_smoke``'s update-parity check tells 1x from Nx;
* the compile cache is placed from outside or at one fixed path;
* ``chip_smoke.py`` cannot pass on a machine without the chip;
* the native decode library is keyed by its source, and a failed build
  says why.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from dptpu.parallel import make_mesh, shard_host_batch  # noqa: E402
from dptpu.parallel.mesh import DATA_AXIS  # noqa: E402
from dptpu.train import (  # noqa: E402
    create_train_state,
    make_optimizer,
    make_train_step,
)
from dptpu.train import step as step_mod  # noqa: E402
from dptpu.utils import compile_cache  # noqa: E402


class TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(8, (3, 3), use_bias=False)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
        x = nn.relu(x).mean(axis=(1, 2))
        return nn.Dense(10)(x)


def _state():
    return create_train_state(
        jax.random.PRNGKey(0), TinyNet(), make_optimizer(0.9, 1e-4),
        input_shape=(1, 8, 8, 3),
    )


def _batch(n=256):
    rng = np.random.RandomState(0)
    return {
        "images": rng.randint(0, 256, (n, 8, 8, 3)).astype(np.uint8),
        "labels": rng.randint(0, 10, (n,)).astype(np.int32),
    }


def _shard_map_eqns(jaxpr):
    """Every shard_map equation in ``jaxpr``, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            found.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                found.extend(_shard_map_eqns(inner))
    return found


# ------------------------------------------------------------ the checker --


def test_train_step_runs_with_the_checker_off():
    mesh = make_mesh(jax.devices()[:8])
    state, batch = _state(), shard_host_batch(_batch(), mesh)
    for step in (make_train_step(mesh), step_mod.make_eval_step(mesh)):
        eqns = _shard_map_eqns(jax.make_jaxpr(step)(state, batch).jaxpr)
        assert eqns, "the mesh step no longer goes through shard_map"
        assert all(e.params["check_vma"] is False for e in eqns)


def _one_step_params(step, state, batch):
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    return jax.device_get(new_state.params)


def test_update_parity_separates_one_reduction_from_two(monkeypatch):
    """The check chip_smoke.py runs on four chips, on the CPU pod: the
    shipped mesh step passes it; the same step with the gradient reduced
    twice (what the seed's silently re-enabled checker did) fails it."""
    mesh = make_mesh(jax.devices()[:4])
    before = jax.device_get(_state().params)
    single = _one_step_params(
        make_train_step(None), _state(), jax.device_put(_batch())
    )
    sharded = shard_host_batch(_batch(), mesh)
    good = _one_step_params(make_train_step(mesh), _state(), sharded)
    ratio = chip_smoke.check_update_parity(before, good, single, "ddp")
    assert 0.9 < ratio < 1.1

    real_psum = lax.psum
    monkeypatch.setattr(
        step_mod.lax, "psum",
        lambda x, axis: (
            real_psum(real_psum(x, axis), axis) if axis == DATA_AXIS
            else real_psum(x, axis)
        ),
    )
    twice = _one_step_params(make_train_step(mesh), _state(), sharded)
    monkeypatch.undo()
    with pytest.raises(chip_smoke.SmokeFailure, match="reduced exactly once"):
        chip_smoke.check_update_parity(before, twice, single, "ddp twice")
    assert chip_smoke.update_norm(before, twice) == pytest.approx(
        4 * chip_smoke.update_norm(before, good), rel=0.05
    )


# ------------------------------------------------------ the compile cache --


def test_compile_cache_left_alone_when_placed_from_outside(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert calls == []


def test_compile_cache_default_is_one_fixed_path(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.enable_compile_cache() == want
    assert calls in ([], [("jax_compilation_cache_dir", want)])
    # the same absolute path from another working directory and another
    # pid: nothing but the package location goes into it
    code = ("from dptpu.utils.compile_cache import default_cache_dir; "
            "print(default_cache_dir())")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT
    seen = {
        subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env,
            capture_output=True, text=True, check=True, timeout=60,
        ).stdout.strip()
        for cwd in (ROOT, str(tmp_path))
    }
    assert seen == {want}


# ------------------------------------------------------------- chip_smoke --


def test_chip_smoke_refuses_to_run_off_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "refusing" in proc.stderr
    # before any compile, and with no result line
    assert "=>" not in proc.stdout and '"ok"' not in proc.stdout


def test_smoke_runner_never_turns_a_failed_phase_into_success(capsys):
    class Meter:
        def snapshot(self):
            return (0.0, 0.0, 0, 0)

    smoke = chip_smoke.Smoke(Meter())
    smoke.run("good", lambda: {"n": 1})
    smoke.run("bad", lambda: chip_smoke.check(False, "kernel fell back"))
    assert smoke.failed == ["bad"]
    assert smoke.phases["good"]["ok"] and not smoke.phases["bad"]["ok"]
    assert "kernel fell back" in smoke.phases["bad"]["error"]
    capsys.readouterr()


# ------------------------------------------------------- the native build --


def test_native_library_is_keyed_by_source_and_flags(monkeypatch, tmp_path):
    from dptpu.native import build

    path = build.library_path()
    assert os.path.dirname(path) == build._BUILD_DIR
    src = tmp_path / "image_ops.cpp"
    with open(build._SRC, "rb") as f:
        src.write_bytes(f.read() + b"\n// edited\n")
    monkeypatch.setattr(build, "_SRC", str(src))
    edited = build.library_path()
    assert edited != path  # a binary built from other source cannot load
    monkeypatch.setattr(build, "_CXX", build._CXX + ["-DOTHER"])
    assert build.library_path() not in (path, edited)


def test_failed_native_build_says_why(monkeypatch, tmp_path, capsys):
    from dptpu.native import build

    src = tmp_path / "image_ops.cpp"
    src.write_text("this is not C++\n")
    monkeypatch.setattr(build, "_SRC", str(src))
    monkeypatch.setattr(build, "_BUILD_DIR", str(tmp_path / "_build"))
    monkeypatch.setattr(build, "_cached", None)
    monkeypatch.setattr(build, "_attempted", False)
    assert build.load_library() is None
    err = capsys.readouterr().err
    assert "dptpu.native: build failed" in err and "error" in err
