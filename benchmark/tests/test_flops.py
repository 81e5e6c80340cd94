"""The operation count from shapes against XLA's own count for the
program's forward-and-backward graph, at full width."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import cells, drive

CONFIGS = os.path.join(cells.BENCH_DIR, "configs")


@pytest.mark.parametrize("name,per_step_tflop", [
    ("resnet50-224-bf16", 3.08), ("vit-b16-224-bf16", 13.6)])
def test_train_flops_matches_xla_cost_analysis(name, per_step_tflop):
    from dptpu.models import create_model

    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    model_cfg = config["model"]
    size, batch = model_cfg["image_size"], 2
    model = create_model(config["arch"], num_classes=1000,
                         dtype=jnp.bfloat16)
    variables = drive.program_template(config)

    def loss(params, batch_stats, images):
        out, _ = model.apply({"params": params, "batch_stats": batch_stats},
                             images, train=True, mutable=["batch_stats"])
        return out.astype(jnp.float32).sum()

    images = jax.ShapeDtypeStruct((batch, size, size, 3), jnp.bfloat16)
    cost = jax.jit(jax.grad(loss)).lower(
        variables["params"], variables.get("batch_stats", {}),
        images).cost_analysis()
    xla = float(cost["flops"])
    ours = cells.reference(config).train_flops(model_cfg, batch)
    # XLA also counts the elementwise work (BN, LN, softmax, GELU): ours
    # is the MXU's share, a few percent under it
    assert 0.93 * xla <= ours <= xla, (ours, xla)
    # and XLA's figures for a whole step of 128 rows (ISSUE 24), which
    # hold the elementwise work too
    at_128 = cells.reference(config).train_flops(model_cfg, 128) / 1e12
    assert at_128 == pytest.approx(per_step_tflop, rel=0.03)
