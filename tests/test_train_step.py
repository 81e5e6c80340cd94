"""Train/eval step tests on the fake 8-device pod (conftest CPU mesh).

Covers: single-device step math, DDP shard_map parity (same update as
single-device on the same global batch — the DDP invariant: data-parallel
replicas with pmean'd grads must equal one big-batch step), per-replica vs
sync BN, schedule traced-vs-host parity, and checkpoint round-trip
(SURVEY.md §4 test-pyramid gap).
"""

import jax
import numpy as np
import pytest
from flax import linen as nn

from dptpu.ops.schedules import (
    make_step_decay_schedule,
    make_warmup_step_decay_schedule,
    step_decay_lr,
    warmup_step_decay_lr,
)
from dptpu.parallel import make_mesh, shard_host_batch
from dptpu.train import (
    create_train_state,
    load_checkpoint,
    make_eval_step,
    make_optimizer,
    make_train_step,
    save_checkpoint,
)


class TinyNet(nn.Module):
    """Small conv+BN net shaped like the zoo (NHWC, mutable batch_stats)."""

    num_classes: int = 10
    bn_axis_name: str = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(8, (3, 3), use_bias=False)(x)
        x = nn.BatchNorm(
            use_running_average=not train,
            momentum=0.9,
            axis_name=self.bn_axis_name,
        )(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2))
        return nn.Dense(self.num_classes)(x)


def _batch(n=16, seed=0, size=8):
    rng = np.random.RandomState(seed)
    return {
        "images": rng.randint(0, 256, (n, size, size, 3)).astype(np.uint8),
        "labels": rng.randint(0, 10, (n,)).astype(np.int32),
    }


def _make_state(bn_axis_name=None):
    tx = make_optimizer(momentum=0.9, weight_decay=1e-4)
    model = TinyNet(bn_axis_name=bn_axis_name)
    return create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, 8, 8, 3)
    )


def test_single_device_loss_decreases():
    state = _make_state()
    step = make_train_step()
    batch = _batch()
    losses = []
    for _ in range(20):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 20


@pytest.mark.parametrize("arch,image", [
    ("efficientnet_b0", 32),   # SE + BN + stochastic depth (dropout rng)
    ("convnext_tiny", 32),     # NO batch_stats collection + layer scale
])
def test_train_step_runs_zoo_arch(arch, image):
    """The generic step must drive every zoo family: stochastic-depth
    archs need the dropout rng plumbed, LayerNorm-only archs must work
    with an empty batch_stats tree."""
    from dptpu.models import create_model

    model = create_model(arch, num_classes=10)
    tx = make_optimizer(momentum=0.9, weight_decay=1e-4)
    state = create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, image, image, 3)
    )
    step = make_train_step()
    # the step donates its input state: snapshot params first
    leaves0 = [np.asarray(x) for x in jax.tree_util.tree_leaves(state.params)]
    state2, metrics = step(state, _batch(8, size=image))
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1
    leaves1 = jax.tree_util.tree_leaves(state2.params)
    assert any(
        not np.allclose(a, np.asarray(b))
        for a, b in zip(leaves0, leaves1)
    )


def test_ddp_step_matches_single_device():
    # The DDP invariant: shard_map over 8 replicas with pmean'd grads ==
    # one single-device step on the same global batch (BN caveat: TinyNet's
    # global-mean pooling makes per-replica BN differ, so compare with sync
    # BN which is mathematically identical to the big batch).
    mesh = make_mesh()
    batch = _batch(n=32)

    s_ref = _make_state(bn_axis_name=None)
    s_ddp = _make_state(bn_axis_name="data")
    single = make_train_step()
    ddp = make_train_step(mesh=mesh)

    sharded = shard_host_batch(batch, mesh)
    s_ref, m_ref = single(s_ref, batch)
    s_ddp, m_ddp = ddp(s_ddp, sharded)

    assert float(m_ddp["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-4)
    ref_leaves = jax.tree_util.tree_leaves(s_ref.params)
    ddp_leaves = jax.tree_util.tree_leaves(jax.device_get(s_ddp.params))
    for a, b in zip(ref_leaves, ddp_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_axes_open_mesh_matches_single_device():
    """Gradient scale on a factored {data, model} mesh must stay exact:
    shard_map's varying-axis tracking psums param cotangents over the
    data axis only (the model-axis duplicates are already invariant), so
    the data-axis-size normalizer is correct with inner axes open — a
    mesh.size normalizer would silently halve every update."""
    mesh2 = make_mesh(mesh_shape={"data": 4, "model": 2})
    batch = _batch(n=32)

    s_ref = _make_state(bn_axis_name=None)
    s_2ax = _make_state(bn_axis_name="data")
    single = make_train_step()
    two_axis = make_train_step(mesh=mesh2)

    s_ref, m_ref = single(s_ref, batch)
    s_2ax, m_2ax = two_axis(s_2ax, shard_host_batch(batch, mesh2))

    assert float(m_2ax["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(s_ref.params),
                    jax.tree_util.tree_leaves(jax.device_get(s_2ax.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_accum_single_device_emulates_ddp_replicas():
    """The virtual-replica contract: accum=4 on one device == the DDP
    step over a 4-replica mesh on the same global batch — per-microbatch
    BN matches per-replica BN, the dropout key of microbatch j matches
    replica j's, and the fp32 accumulation matches the psum to ulp
    reordering (measured <= 3e-8 per weight after 5 steps)."""
    mesh4 = make_mesh(jax.devices()[:4], {"data": 4})
    s_acc = _make_state()           # per-microbatch BN
    s_ddp = _make_state()           # per-replica BN (default non-sync)
    step_acc = make_train_step(accum_steps=4)
    step_ddp = make_train_step(mesh=mesh4)
    for i in range(5):
        batch = _batch(n=32, seed=i)
        s_acc, m_acc = step_acc(s_acc, batch)
        s_ddp, m_ddp = step_ddp(s_ddp, shard_host_batch(batch, mesh4))
    assert float(m_acc["loss"]) == pytest.approx(
        float(m_ddp["loss"]), rel=1e-6
    )
    for part in ("params", "batch_stats", "opt_state"):
        for a, b in zip(
            jax.tree_util.tree_leaves(getattr(s_acc, part)),
            jax.tree_util.tree_leaves(
                jax.device_get(getattr(s_ddp, part))
            ),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )


def test_accum_on_mesh_emulates_wider_pod():
    """accum=2 over 8 replicas == accum=16 on one device on the same
    global batch: the (replica, microbatch) -> virtual-replica id
    mapping r*k + j lines up sample slices and dropout streams exactly,
    so k*N replicas are emulated no matter how the product factors."""
    mesh = make_mesh()
    s_mesh = _make_state()
    s_one = _make_state()
    step_mesh = make_train_step(mesh=mesh, accum_steps=2)
    step_one = make_train_step(accum_steps=16)
    for i in range(3):
        batch = _batch(n=32, seed=i)
        s_mesh, m_mesh = step_mesh(s_mesh, shard_host_batch(batch, mesh))
        s_one, m_one = step_one(s_one, batch)
    assert float(m_mesh["loss"]) == pytest.approx(
        float(m_one["loss"]), rel=1e-6
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(s_mesh.params)),
        jax.tree_util.tree_leaves(s_one.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_per_replica_bn_differs_from_sync_bn():
    # DDP default is NON-synced BN (SURVEY.md §7 hard part (b)); the two
    # modes must produce different batch_stats on heterogeneous shards.
    mesh = make_mesh()
    batch = shard_host_batch(_batch(n=32, seed=3), mesh)
    s_local = _make_state(bn_axis_name=None)
    s_sync = _make_state(bn_axis_name="data")
    ddp = make_train_step(mesh=mesh)
    s_local, _ = ddp(s_local, batch)
    s_sync, _ = ddp(s_sync, batch)
    local_var = np.asarray(
        jax.device_get(s_local.batch_stats)["BatchNorm_0"]["var"]
    )
    sync_var = np.asarray(jax.device_get(s_sync.batch_stats)["BatchNorm_0"]["var"])
    assert not np.allclose(local_var, sync_var)


def test_eval_step_exact_sums_with_mask():
    mesh = make_mesh()
    state = _make_state()
    ev = make_eval_step(mesh=mesh)
    batch = _batch(n=32)
    mask = np.ones((32,), np.float32)
    mask[-5:] = 0.0  # padded tail
    batch["mask"] = mask
    sums = jax.device_get(ev(state, shard_host_batch(batch, mesh)))
    assert sums["count"] == 27.0
    assert 0 <= sums["correct1"] <= sums["correct5"] <= 27.0
    # masked-out samples contribute nothing
    batch27 = {k: v[:27] for k, v in _batch(n=32).items()}
    single_sums = jax.device_get(make_eval_step()(state, batch27))
    assert sums["correct1"] == single_sums["correct1"]
    assert sums["loss_sum"] == pytest.approx(single_sums["loss_sum"], rel=1e-5)


def test_traced_schedules_match_host_math():
    spe = 7
    sched = make_step_decay_schedule(0.1, spe)
    for count in [0, 29 * spe, 30 * spe, 89 * spe + 6]:
        epoch = count // spe
        assert float(sched(count)) == pytest.approx(step_decay_lr(0.1, epoch))
    wsched = make_warmup_step_decay_schedule(0.4, spe)
    for count in [0, 3, spe, 4 * spe + 6, 5 * spe, 79 * spe, 80 * spe]:
        epoch, step1 = count // spe, count % spe + 1
        assert float(wsched(count)) == pytest.approx(
            warmup_step_decay_lr(0.4, epoch, step1, spe), rel=1e-6
        )


def test_lr_schedule_follows_global_step():
    # --start-epoch N without --resume must land on epoch-N LR
    # (imagenet_ddp.py:35-36 + :374-378): the schedule reads state.step.
    from dptpu.ops.schedules import make_step_decay_schedule

    spe = 4
    sched = make_step_decay_schedule(0.1, spe)
    tx = make_optimizer(0.9, 1e-4)
    model = TinyNet()
    state = create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, 8, 8, 3),
        initial_step=35 * spe,  # epoch 35 → lr = 0.1 * 0.1
    )
    step = make_train_step(lr_schedule=sched)
    state, metrics = step(state, _batch())
    assert float(metrics["lr"]) == pytest.approx(0.01)
    assert int(state.step) == 35 * spe + 1


def test_checkpoint_roundtrip(tmp_path):
    state = _make_state()
    step = make_train_step()
    batch = _batch()
    for _ in range(3):
        state, _ = step(state, batch)
    path = save_checkpoint(
        state,
        epoch=2,
        arch="tinynet",
        best_acc1=12.5,
        is_best=True,
        directory=str(tmp_path),
    )
    assert path and (tmp_path / "model_best.pth.tar").exists()

    fresh = _make_state()
    restored, meta = load_checkpoint(path, fresh)
    assert meta["epoch"] == 2 and meta["best_acc1"] == 12.5
    assert meta["arch"] == "tinynet"
    assert int(restored.step) == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(state.params)),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # non-chief never writes (rank guard, imagenet_ddp.py:215)
    assert (
        save_checkpoint(
            state, epoch=0, arch="t", best_acc1=0, is_best=False,
            directory=str(tmp_path), is_chief=False,
        )
        is None
    )
