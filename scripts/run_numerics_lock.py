#!/usr/bin/env python3
"""Golden loss-trajectory lock: TPU numerics vs the CPU fake-pod.

Every parity test in tests/ runs on the CPU backend; the bench and real
training run on the chip. This script closes the loop between them: it
runs the SAME deterministic 20-step resnet18 training trajectory (fixed
init key, fixed synthetic batches) on the in-process backend (the chip,
when run on a machine that has one) and on a re-exec'd subprocess held
to the CPU platform by ``JAX_PLATFORMS=cpu`` (the parent keeps the chip;
the child never asks for it), in fp32 and bf16, and bounds the per-step
loss deviation.

XLA compiles different convolution/reduction orderings per backend, so
bit equality is not the contract — and neither, honestly, is a long
trajectory: measured here, the per-step relative difference grows from
~0.1% (step 1) to ~15% (step 20, lr 0.01) to ~200% (step 20, lr 0.1) —
cross-backend rounding is amplified exponentially by the training
dynamics themselves (momentum + BN + a fresh net's chaotic transient),
so ANY tight 20-step bound would be theater. What IS lockable is the
early horizon, before amplification: steps 1-3 are dominated by pure
forward/backward numerics and must agree within 5% (fp32) / 5% (bf16);
measured agreement is ~10x tighter. The full 20-step curves and
per-step diffs are recorded as the amplification evidence, and
CONVERGENCE.json separately proves end-accuracy parity where it
matters. Writes NUMERICS.json at the repo root; exits 1 on a bound
violation.

Usage: python scripts/run_numerics_lock.py  (on the chip; self-spawns CPU)
       DPTPU_NUMERICS_CHILD=1 JAX_PLATFORMS=cpu python scripts/... (child)
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dptpu.envknob import env_str  # noqa: E402

import numpy as np

STEPS = 20
LR = 0.01
LOCK_STEPS = 3   # pre-amplification horizon — see module docstring
FP32_RTOL = 5e-2
BF16_RTOL = 5e-2


def trajectory(dtype_name: str):
    import jax
    import jax.numpy as jnp

    from dptpu.models import create_model
    from dptpu.train import create_train_state, make_optimizer, make_train_step

    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    model = create_model("resnet18", num_classes=10, dtype=dtype)
    tx = make_optimizer(0.9, 1e-4)
    state = create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, 32, 32, 3)
    )
    step = make_train_step(None, dtype, lr_schedule=lambda _: LR)
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(STEPS):
        batch = {
            "images": rng.randint(0, 256, (32, 32, 32, 3)).astype(np.uint8),
            "labels": rng.randint(0, 10, (32,)).astype(np.int32),
        }
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def main():
    if env_str("DPTPU_NUMERICS_CHILD"):
        # the parent launched this child with JAX_PLATFORMS=cpu
        import jax

        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"CPU reference child landed on {jax.default_backend()}"
            )
        print(json.dumps({
            "fp32": trajectory("fp32"), "bf16": trajectory("bf16"),
        }))
        return

    import jax

    here = {"fp32": trajectory("fp32"), "bf16": trajectory("bf16")}
    env = dict(os.environ, DPTPU_NUMERICS_CHILD="1", JAX_PLATFORMS="cpu")
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if child.returncode != 0:
        sys.stderr.write(child.stderr[-2000:])
        raise RuntimeError("CPU reference subprocess failed")
    cpu = json.loads(child.stdout.strip().splitlines()[-1])

    report = {
        "steps": STEPS,
        "lr": LR,
        "lock_steps": LOCK_STEPS,
        "backend_here": jax.default_backend(),
        "device_here": str(jax.devices()[0].device_kind),
        "trajectories": {"here": here, "cpu": cpu},
        "bounds": {"fp32_rtol": FP32_RTOL, "bf16_rtol": BF16_RTOL,
                   "over_first_n_steps": LOCK_STEPS},
    }
    ok = True
    for name, rtol in (("fp32", FP32_RTOL), ("bf16", BF16_RTOL)):
        a, b = np.asarray(here[name]), np.asarray(cpu[name])
        rel = np.abs(a - b) / np.maximum(np.abs(b), 1e-9)
        report[f"{name}_rel_diff_per_step"] = [
            round(float(r), 5) for r in rel
        ]
        report[f"{name}_lock_max_rel_diff"] = round(
            float(rel[:LOCK_STEPS].max()), 6
        )
        # informational: how far amplification carries the tail
        report[f"{name}_tail_max_rel_diff"] = round(float(rel.max()), 6)
        report[f"{name}_pass"] = bool(rel[:LOCK_STEPS].max() <= rtol)
        ok = ok and report[f"{name}_pass"]
    report["pass"] = ok
    report["amplification_note"] = (
        "per-step rel diff grows ~0.1% -> ~15% over 20 steps at lr 0.01 "
        "(and ~2x at lr 0.1): training dynamics amplify cross-backend "
        "rounding exponentially, so only the pre-amplification horizon "
        "is gated; end-accuracy parity is CONVERGENCE.json's job"
    )

    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "NUMERICS.json",
    )
    from bench_util import host_provenance

    report["host"] = host_provenance()
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: report[k] for k in (
        "backend_here", "fp32_lock_max_rel_diff", "bf16_lock_max_rel_diff",
        "fp32_tail_max_rel_diff", "bf16_tail_max_rel_diff", "pass")}))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
