"""Bucketed backward-overlapped gradient comms
(dptpu/parallel/overlap.py) on the fake 8-device pod.

Locks, per ISSUE 13:

* bucket partitioner units — size bound, reverse flatten order,
  tiny-leaf coalescing, single-oversized-leaf buckets, dtype grouping,
  and the 1-bucket degeneracy;
* knob fail-fast contract for DPTPU_OVERLAP / DPTPU_BUCKET_MB;
* the parity ladder — DPTPU_OVERLAP=1 is params-Δ=0 against the
  unbucketed step at ANY bucket count (the regrouping contract), for
  DDP, ZeRO-1, --accum-steps and the --slices hierarchical mesh (fp32
  AND bf16-DCN), with multi-bucket ≡ single-bucket at Δ=0;
* HLO structure — the bucketed program's total collective bytes equal
  the unbucketed program's (pure regrouping), donation aliasing stays
  intact, and the compiled schedule interleaves >= 2 per-bucket
  reductions with backward compute (overlap_evidence — the same
  numbers `dptpu check` gates);
* overlap_evidence parser units on synthetic scheduled HLO, including
  the async start/done form this CPU backend never emits;
* distributed evaluation (DPTPU_DIST_EVAL): the sharded val pass's
  psum'd correct/count sums aggregate to the single-stream pass's
  numbers bit-identically.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import linen as nn

from dptpu.parallel import (
    gather_state,
    make_hierarchical_mesh,
    make_mesh,
    make_zero1_train_step,
    replicated_sharding,
    shard_host_batch,
    shard_zero1_state,
)
from dptpu.parallel.hlo_accounting import (
    collective_bytes_per_chip,
    donated_alias_count,
    overlap_evidence,
)
from dptpu.parallel.overlap import (
    DEFAULT_BUCKET_MB,
    bucket_sizes_bytes,
    overlap_knobs,
    partition_buckets,
)
from dptpu.train import create_train_state, make_optimizer, make_train_step
from dptpu.train.step import make_eval_step


class TinyDense(nn.Module):
    """The test_hierarchy probe: channel dims divide 2/4/8 so leaves
    scatter at every geometry; BN exercises the replicated pmean."""

    num_classes: int = 10

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.Conv(16, (3, 3), use_bias=False)(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
        x = nn.relu(x)
        x = x.mean(axis=(1, 2))
        x = nn.Dense(32)(x)
        x = nn.relu(x)
        return nn.Dense(self.num_classes)(x)


def _state():
    tx = make_optimizer(momentum=0.9, weight_decay=1e-4)
    return create_train_state(
        jax.random.PRNGKey(0), TinyDense(), tx, input_shape=(1, 8, 8, 3)
    )


def _batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "images": rng.randint(0, 256, (n, 8, 8, 3)).astype(np.uint8),
        "labels": rng.randint(0, 10, (n,)).astype(np.int32),
    }


def _replicate(state, mesh):
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, replicated_sharding(mesh)), state
    )


def _run(mesh, steps=5, zero1=False, **kw):
    st = _state()
    if zero1:
        step = make_zero1_train_step(mesh, st, **kw)
        st = shard_zero1_state(st, mesh)
    else:
        step = make_train_step(mesh, **kw)
        st = _replicate(st, mesh)
    for i in range(steps):
        st, m = step(st, shard_host_batch(_batch(16, seed=i), mesh))
    if zero1:
        st = gather_state(st, mesh)
    return jax.device_get(st.params), m


def _max_delta(a, b):
    return max(
        float(np.abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))
    )


def _flat_mesh(n=4):
    return make_mesh(jax.devices()[:n], {"data": n})


def _hier_mesh(s=2, i=2):
    return make_hierarchical_mesh(s, jax.devices()[:s * i])


# ----------------------------------------------------------- partitioner


def test_partition_respects_size_bound():
    tree = {"a": np.zeros(100, np.float32), "b": np.zeros(100, np.float32),
            "c": np.zeros(100, np.float32)}
    buckets = partition_buckets(tree, 400)  # 2 leaves of 400B fit, 3 don't
    sizes = bucket_sizes_bytes(tree, buckets)
    assert all(s <= 400 for s in sizes)
    assert len(buckets) == 3  # 400B leaves: one each


def test_partition_reverse_flatten_order():
    tree = {"a": np.zeros(4, np.float32), "b": np.zeros(4, np.float32),
            "c": np.zeros(4, np.float32)}
    [bucket] = partition_buckets(tree, 10**9)
    # one bucket holding every leaf, walked in REVERSE flatten order
    assert bucket == [2, 1, 0]


def test_partition_tiny_leaves_coalesce():
    tree = [np.zeros(2, np.float32) for _ in range(10)]  # 8 B each
    buckets = partition_buckets(tree, 64)
    assert len(buckets) == 2  # 10 x 8B pack 8-per-64B bucket
    assert [len(b) for b in buckets] == [8, 2]


def test_partition_oversized_leaf_gets_own_bucket():
    tree = [np.zeros(2, np.float32), np.zeros(1000, np.float32),
            np.zeros(2, np.float32)]
    buckets = partition_buckets(tree, 64)
    assert [sorted(b) for b in buckets] == [[2], [1], [0]]


def test_partition_never_mixes_dtypes():
    tree = [np.zeros(4, np.float32), np.zeros(4, np.int32),
            np.zeros(4, np.float32)]
    buckets = partition_buckets(tree, 10**9)
    leaves = tree
    for b in buckets:
        assert len({leaves[i].dtype for i in b}) == 1
    assert len(buckets) == 3  # f32 / s32 / f32 in reverse order


def test_partition_single_bucket_degeneracy():
    params = _state().params
    buckets = partition_buckets(params, 10**9)
    n = len(jax.tree_util.tree_leaves(params))
    assert len(buckets) == 1 and sorted(buckets[0]) == list(range(n))


def test_partition_is_deterministic():
    params = _state().params
    assert partition_buckets(params, 2048) == partition_buckets(
        params, 2048
    )


def test_partition_invalid_bound_raises():
    with pytest.raises(ValueError, match="bucket_bytes"):
        partition_buckets([np.zeros(4, np.float32)], 0)


# ----------------------------------------------------------------- knobs


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for k in ("DPTPU_OVERLAP", "DPTPU_BUCKET_MB"):
        monkeypatch.delenv(k, raising=False)


def test_knob_defaults():
    assert overlap_knobs() == (False, int(DEFAULT_BUCKET_MB * 1e6), False)


def test_knob_reads(monkeypatch):
    monkeypatch.setenv("DPTPU_OVERLAP", "1")
    monkeypatch.setenv("DPTPU_BUCKET_MB", "0.5")
    assert overlap_knobs() == (True, 500000, True)


@pytest.mark.parametrize("bad", ["0", "-3", "junk"])
def test_bucket_mb_invalid_raises(monkeypatch, bad):
    monkeypatch.setenv("DPTPU_BUCKET_MB", bad)
    with pytest.raises(ValueError, match="DPTPU_BUCKET_MB"):
        overlap_knobs()


def test_overlap_junk_raises(monkeypatch):
    monkeypatch.setenv("DPTPU_OVERLAP", "flase")
    with pytest.raises(ValueError, match="DPTPU_OVERLAP"):
        overlap_knobs()


# ---------------------------------------------------------- parity ladder


def test_ddp_overlap_single_bucket_bit_identical():
    mesh = _flat_mesh()
    base, _ = _run(mesh)
    over, _ = _run(mesh, overlap=True, bucket_bytes=10**9)
    assert _max_delta(base, over) == 0.0


def test_ddp_overlap_multi_bucket_bit_identical():
    mesh = _flat_mesh()
    base, _ = _run(mesh)
    multi, _ = _run(mesh, overlap=True, bucket_bytes=2048)
    assert _max_delta(base, multi) == 0.0


def test_overlap_accum_bit_identical():
    mesh = _flat_mesh()
    base, _ = _run(mesh, accum_steps=2)
    over, _ = _run(mesh, accum_steps=2, overlap=True, bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_zero1_overlap_bit_identical():
    mesh = _flat_mesh()
    base, _ = _run(mesh, zero1=True)
    over, _ = _run(mesh, zero1=True, overlap=True, bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_zero1_overlap_accum_bit_identical():
    mesh = _flat_mesh()
    base, _ = _run(mesh, zero1=True, accum_steps=2)
    over, _ = _run(mesh, zero1=True, accum_steps=2, overlap=True,
                   bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_hier_overlap_bit_identical():
    mesh = _hier_mesh()
    base, _ = _run(mesh)
    over, _ = _run(mesh, overlap=True, bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_hier_overlap_bf16_bit_identical():
    mesh = _hier_mesh()
    base, _ = _run(mesh, dcn_dtype="bf16")
    over, _ = _run(mesh, dcn_dtype="bf16", overlap=True,
                   bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_hier_zero1_overlap_bit_identical():
    mesh = _hier_mesh()
    base, _ = _run(mesh, zero1=True)
    over, _ = _run(mesh, zero1=True, overlap=True, bucket_bytes=2048)
    assert _max_delta(base, over) == 0.0


def test_overlap_metrics_match_unbucketed():
    mesh = _flat_mesh()
    _, m_base = _run(mesh, steps=1)
    _, m_over = _run(mesh, steps=1, overlap=True, bucket_bytes=2048)
    for k in ("loss", "top1", "top5"):
        np.testing.assert_array_equal(
            np.asarray(m_base[k]), np.asarray(m_over[k])
        )


# ------------------------------------------------------- HLO structure


def _compiled_text(mesh, **kw):
    # compiled the way `dptpu check` compiles its budget configs: with
    # XLA:CPU's all-reduce combiner off, so the schedule shows the
    # reductions the engine emitted, not one combined instruction
    from dptpu.analysis.hlo_budget import COMPILER_OPTIONS

    st = _replicate(_state(), mesh)
    step = make_train_step(mesh, **kw)
    return step.lower(st, shard_host_batch(_batch(), mesh)).compile(
        compiler_options=COMPILER_OPTIONS
    ).as_text()


def test_overlap_total_bytes_and_donation_unchanged():
    mesh = _flat_mesh()
    base = _compiled_text(mesh)
    over = _compiled_text(mesh, overlap=True, bucket_bytes=2048)
    b = collective_bytes_per_chip(base, 4)
    o = collective_bytes_per_chip(over, 4)
    # pure regrouping: identical total reduction bytes, fewer or equal
    # instructions (leaves fuse into buckets)
    assert o["total"] == b["total"]
    assert o["instructions"] <= b["instructions"]
    assert donated_alias_count(over) == donated_alias_count(base)


def test_overlap_schedule_shows_interleaved_buckets():
    mesh = _flat_mesh()
    ev = overlap_evidence(
        _compiled_text(mesh, overlap=True, bucket_bytes=2048)
    )
    assert ev["reductions"] >= 2
    assert ev["interleaved_gaps"] >= 1
    assert not ev["contiguous_tail_block"]


# ------------------------------------------------- evidence parser units


_SYNTH_SYNC = """\
HloModule m, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %f1 = f32[256]{0} fusion(f32[64]{0} %p0), kind=kLoop, calls=%fc.1
  %ar1 = f32[256]{0} all-reduce(f32[256]{0} %f1), replica_groups={{0,1}}, to_apply=%add
  %f2 = f32[256]{0} fusion(f32[256]{0} %ar1), kind=kLoop, calls=%fc.2
  %ar2 = f32[256]{0} all-reduce(f32[256]{0} %f2), replica_groups={{0,1}}, to_apply=%add
  %tiny = f32[] all-reduce(f32[] %p0), replica_groups={{0,1}}, to_apply=%add
  ROOT %out = f32[64]{0} fusion(f32[256]{0} %ar2), kind=kLoop, calls=%fc.3
}
"""

_SYNTH_TAIL = """\
HloModule m, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %f1 = f32[256]{0} fusion(f32[64]{0} %p0), kind=kLoop, calls=%fc.1
  %ar1 = f32[256]{0} all-reduce(f32[256]{0} %f1), replica_groups={{0,1}}, to_apply=%add
  %ar2 = f32[256]{0} all-reduce(f32[256]{0} %f1), replica_groups={{0,1}}, to_apply=%add
  ROOT %out = f32[64]{0} fusion(f32[256]{0} %ar2), kind=kLoop, calls=%fc.3
}
"""

_SYNTH_ASYNC = """\
HloModule m, is_scheduled=true

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %f1 = f32[256]{0} fusion(f32[64]{0} %p0), kind=kLoop, calls=%fc.1
  %ars = (f32[256]{0}, f32[256]{0}) all-reduce-start(f32[256]{0} %f1), replica_groups={{0,1}}, to_apply=%add
  %f2 = f32[128]{0} fusion(f32[64]{0} %p0), kind=kLoop, calls=%fc.2
  %f3 = f32[128]{0} fusion(f32[128]{0} %f2), kind=kLoop, calls=%fc.3
  %ard = f32[256]{0} all-reduce-done((f32[256]{0}, f32[256]{0}) %ars)
  ROOT %out = f32[64]{0} fusion(f32[256]{0} %ard), kind=kLoop, calls=%fc.4
}
"""


def test_evidence_sync_interleaved():
    ev = overlap_evidence(_SYNTH_SYNC)
    assert ev["reductions"] == 2  # the f32[] psum falls below min_bytes
    assert ev["interleaved_gaps"] == 1
    assert ev["compute_between"] == 1
    assert not ev["contiguous_tail_block"]


def test_evidence_contiguous_tail_detected():
    ev = overlap_evidence(_SYNTH_TAIL)
    assert ev["reductions"] == 2
    assert ev["interleaved_gaps"] == 0
    assert ev["contiguous_tail_block"]


def test_evidence_async_pairs():
    ev = overlap_evidence(_SYNTH_ASYNC)
    assert ev["reductions"] == 1  # the -start counts once
    assert ev["async_pairs"] == 1
    # two fusions scheduled inside the start..done window
    assert ev["async_compute_between"] == 2


def test_evidence_min_bytes_filter():
    ev = overlap_evidence(_SYNTH_SYNC, min_bytes=10**6)
    assert ev["reductions"] == 0


# ------------------------------------------------- distributed evaluation


def test_dist_eval_sharded_sums_bit_identical():
    """The DPTPU_DIST_EVAL contract: splitting the val set into host
    shards and summing the per-shard psum'd correct/count sums equals
    the single-stream pass EXACTLY — the eval step emits integer-valued
    f32 sums, so the aggregation is associative bit-for-bit."""
    from dptpu.data import ShardedSampler

    st = _state()
    eval_step = make_eval_step(None)
    images = np.random.RandomState(0).randint(
        0, 256, (48, 8, 8, 3)).astype(np.uint8)
    labels = np.random.RandomState(1).randint(0, 10, (48,)).astype(
        np.int32)

    def sums(idxs):
        out = {"loss_sum": 0.0, "correct1": 0.0, "correct5": 0.0,
               "count": 0.0}
        for lo in range(0, len(idxs), 16):
            sel = idxs[lo:lo + 16]
            s = jax.device_get(eval_step(st, {
                "images": images[sel], "labels": labels[sel]
            }))
            for k in out:
                out[k] += float(s[k])
        return out

    full = sums(np.arange(48))
    shards = [
        ShardedSampler(48, num_shards=2, shard_index=i,
                       shuffle=False).indices(0)
        for i in range(2)
    ]
    # the two shards partition the full set (no wrap padding at 48/2)
    assert sorted(np.concatenate(shards).tolist()) == list(range(48))
    merged = {k: 0.0 for k in full}
    for sh in shards:
        part = sums(sh)
        for k in merged:
            merged[k] += part[k]
    assert merged["correct1"] == full["correct1"]
    assert merged["correct5"] == full["correct5"]
    assert merged["count"] == full["count"]
    assert abs(merged["loss_sum"] - full["loss_sum"]) <= 1e-4 * max(
        abs(full["loss_sum"]), 1.0
    )
