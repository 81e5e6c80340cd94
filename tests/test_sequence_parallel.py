"""Sequence/context-parallel attention equivalence on the fake 8-device
mesh: Ulysses all-to-all and ring attention must reproduce single-device
attention (dptpu/ops/sequence_parallel.py), including through a full ViT
encoder layer and its gradients."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from dptpu.ops.sequence_parallel import (
    full_attention,
    ring_attention,
    sequence_parallel_attention,
    ulysses_attention,
)

B, S, H, D = 2, 64, 8, 16


def _qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks)


def _mesh(devs, n=8):
    return Mesh(np.array(devs[:n]), ("seq",))


@pytest.mark.parametrize("fn", [ulysses_attention, ring_attention])
def test_matches_full_attention(eight_devices, fn):
    q, k, v = _qkv()
    want = full_attention(q, k, v)
    mesh = _mesh(eight_devices)
    spec = P(None, "seq", None, None)
    sharded = shard_map(
        partial(fn, axis_name="seq"), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )
    got = jax.jit(sharded)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("fn", [ulysses_attention, ring_attention])
def test_gradients_match(eight_devices, fn):
    """Sequence parallelism must be transparent to the backward pass —
    the collectives (all_to_all / ppermute) differentiate exactly."""
    q, k, v = _qkv(1)
    mesh = _mesh(eight_devices)
    spec = P(None, "seq", None, None)
    sharded = shard_map(
        partial(fn, axis_name="seq"), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )
    want = jax.grad(lambda t: (full_attention(*t) ** 2).sum())((q, k, v))
    got = jax.grad(lambda t: (jax.jit(sharded)(*t) ** 2).sum())((q, k, v))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-5)


def test_ring_on_smaller_axis(eight_devices):
    """Ring works on any axis size (no heads-divisibility constraint):
    4-way ring with 6 heads, which Ulysses must reject."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (1, 32, 6, 8)) for kk in ks)
    mesh = Mesh(np.array(eight_devices[:4]), ("seq",))
    spec = P(None, "seq", None, None)
    got = jax.jit(shard_map(
        partial(ring_attention, axis_name="seq"), mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    ))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_attention(q, k, v)),
        atol=2e-5, rtol=2e-5,
    )
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(shard_map(
            partial(ulysses_attention, axis_name="seq"), mesh=mesh,
            in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, k, v)


@pytest.mark.parametrize("fn", [ulysses_attention, ring_attention])
def test_masked_padding_matches_unpadded(eight_devices, fn):
    """kv_mask makes PADDED sequence shards exact: 40 real tokens padded
    to 64 over 8 devices must reproduce unpadded full attention on the
    real rows, with finite (garbage, discarded) pad rows."""
    s_real = 40
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (B, s_real, H, D)) for kk in ks)
    want = full_attention(q, k, v)
    pad = S - s_real
    qp, kp, vp = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                  for t in (q, k, v))
    mask = jnp.arange(S) < s_real
    mesh = _mesh(eight_devices)
    spec = P(None, "seq", None, None)
    sharded = shard_map(
        lambda q, k, v, m: fn(q, k, v, axis_name="seq", kv_mask=m),
        mesh=mesh, in_specs=(spec, spec, spec, P("seq")),
        out_specs=spec, check_vma=False,
    )
    got = jax.jit(sharded)(qp, kp, vp, mask)
    np.testing.assert_allclose(np.asarray(got[:, :s_real]),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))  # pad rows NaN-free


def test_masked_gradients_finite_and_match(eight_devices):
    """Gradients through the masked path: pad-key columns get zero grad,
    real positions match the unpadded reference (ring exercises the
    rotating mask; the loss reads only real rows, like the trainer)."""
    s_real = 40
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q, k, v = (jax.random.normal(kk, (B, s_real, H, D)) for kk in ks)
    want = jax.grad(
        lambda t: (full_attention(*t) ** 2).sum()
    )((q, k, v))
    pad = S - s_real
    mask = jnp.arange(S) < s_real
    mesh = _mesh(eight_devices)
    spec = P(None, "seq", None, None)
    sharded = shard_map(
        lambda q, k, v, m: ring_attention(q, k, v, "seq", kv_mask=m),
        mesh=mesh, in_specs=(spec, spec, spec, P("seq")),
        out_specs=spec, check_vma=False,
    )

    def loss(t):
        qp, kp, vp = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in t)
        out = jax.jit(sharded)(qp, kp, vp, mask)
        return (out[:, :s_real] ** 2).sum()

    got = jax.grad(loss)((q, k, v))
    for g, w in zip(got, want):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-5, rtol=5e-5)


def test_dispatch():
    q, k, v = _qkv(3)
    np.testing.assert_array_equal(
        np.asarray(sequence_parallel_attention(q, k, v, None)),
        np.asarray(full_attention(q, k, v)),
    )
    with pytest.raises(ValueError, match="unknown"):
        sequence_parallel_attention(q, k, v, "seq", mode="nope")


def test_registry_accepts_seq_kwargs():
    """The fields thread through create_model down to the attention."""
    from dptpu.models import create_model

    m = create_model("vit_b_32", seq_axis_name="seq", seq_mode="ring")
    assert m.seq_axis_name == "seq" and m.seq_mode == "ring"


@pytest.mark.parametrize("mode", ["ulysses", "ring"])
def test_vit_full_encoder_sequence_parallel(eight_devices, mode):
    """The README recipe at full-Encoder scope: params replicated EXCEPT
    pos_embedding, whose token axis shards with the activations. Both
    modes must reproduce the unsharded Encoder."""
    from dptpu.models.vit import Encoder

    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 96))
    kw = dict(layers=2, heads=8, mlp_dim=192, dtype=jnp.float32,
              param_dtype=jnp.float32)
    enc = Encoder(**kw)
    params = enc.init(jax.random.PRNGKey(7), x)
    want = enc.apply(params, x)

    sp = Encoder(**kw, seq_axis_name="seq", seq_mode=mode)
    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    pspecs["params"]["pos_embedding"] = P(None, "seq", None)
    fn = shard_map(
        lambda p, t: sp.apply(p, t),
        mesh=_mesh(eight_devices),
        in_specs=(pspecs, P(None, "seq", None)),
        out_specs=P(None, "seq", None),
        check_vma=False,
    )
    got = jax.jit(fn)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


def test_vit_encoder_layer_sequence_parallel(eight_devices):
    """A full ViT encoder layer (LN + attention + MLP) under shard_map
    with the token axis sharded reproduces the unsharded layer: every
    non-attention sublayer is position-wise, so only the attention needs
    the sequence-parallel path."""
    from dptpu.models.vit import EncoderLayer

    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 96))
    layer = EncoderLayer(heads=8, mlp_dim=192, dtype=jnp.float32,
                         param_dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(5), x)
    want = layer.apply(params, x)

    sp_layer = EncoderLayer(heads=8, mlp_dim=192, dtype=jnp.float32,
                            param_dtype=jnp.float32,
                            seq_axis_name="seq", seq_mode="ulysses")
    mesh = _mesh(eight_devices)
    fn = shard_map(
        lambda p, t: sp_layer.apply(p, t),
        mesh=mesh,
        in_specs=(P(), P(None, "seq", None)),
        out_specs=P(None, "seq", None),
        check_vma=False,
    )
    got = jax.jit(fn)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
