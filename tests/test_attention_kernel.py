"""The attention's two Pallas kernels (``dptpu/ops/attention_kernel.py``)
against the plain attention, on the CPU in Pallas interpret mode:
``causal_attention`` whole (folding, padding, the forward and backward
rules, the names on the residuals) with both passes on the kernels, as a
program lowered for the TPU gets them, at the two token models' head
layouts. What the chip's compiler makes of them at the cells' shapes is
``tests/test_tpu_compile.py``'s; what they take on the chip PERF.md's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dptpu.ops import attention, attention_kernel
from dptpu.ops.attention import causal_attention, plain_causal_attention

BLOCK = 128  # the smallest the kernels tile: whole lanes


@pytest.fixture
def on_the_kernels(monkeypatch):
    """Both passes of ``causal_attention`` on the kernels, interpreted:
    the TPU lowering of ``attention._forward_p`` / ``_backward_p``, which
    a CPU process otherwise never runs."""
    def interpreted(scan, prim, *arrays, **sizes):
        del scan
        name = prim.name.rsplit("_", 1)[1]  # forward, backward
        return attention._kernel(name, interpret=True)(*arrays, **sizes)

    monkeypatch.setattr(attention, "_here", interpreted)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads,kv_heads,qk_head,v_head,length", [
    (4, 1, 64, 64, 256),     # grouped, G 4, one head size (LFM2's)
    (2, 2, 192, 128, 256),   # ungrouped, two head sizes (JoyAI's)
    (4, 2, 64, 64, 200),     # a length causal_attention pads, grouped
    (2, 2, 192, 128, 300),   # and ungrouped: three blocks of 128
], ids=["grouped-64", "ungrouped-192-128", "padded-grouped",
        "padded-ungrouped"])
def test_the_kernels_are_the_plain_attention(on_the_kernels, heads, kv_heads,
                                             qk_head, v_head, length, dtype):
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 4)
    dtype = jnp.dtype(dtype)
    q = jax.random.normal(keys[0], (2, length, heads, qk_head)).astype(dtype)
    k = jax.random.normal(keys[1], (2, length, kv_heads, qk_head)
                          ).astype(dtype)
    v = jax.random.normal(keys[2], (2, length, kv_heads, v_head)
                          ).astype(dtype)
    weight = jax.random.normal(keys[3], (2, length, heads, v_head))

    def out_and_grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v, scale=qk_head ** -0.5)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    out, got = out_and_grads(functools.partial(causal_attention,
                                               block=BLOCK))
    want_out, want = out_and_grads(plain_causal_attention)
    assert out.shape == (2, length, heads, v_head) and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out, np.float32), atol=tol)
    for g, w, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        scale_of = max(float(jnp.abs(w.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol * scale_of)


def test_the_kernels_are_the_scan_tile_for_tile():
    """At the scan's block sizes the kernels do its sums in its order:
    output and log-sum-exp to the bit, the gradients to the last bit of
    float32, and at other block sizes to rounding."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (1, 2, 2 * 512, 64))
    k = jax.random.normal(keys[1], (1, 2, 512, 64))
    v = jax.random.normal(keys[2], (1, 2, 512, 128))
    d_out = jax.random.normal(keys[3], (1, 2, 2 * 512, 128))
    out, lse = attention._forward(q, k, v, 256, 2, 0.125)
    grads = attention._backward(q, k, v, out, lse, d_out, 256, 2, 0.125)
    for block_q, block_kv, exact in ((256, 256, True), (128, 256, False),
                                     (256, 128, False)):
        sizes = dict(block=256, groups=2, scale=0.125, block_q=block_q,
                     block_kv=block_kv, interpret=True)
        got_out, got_lse = attention_kernel.forward(q, k, v, **sizes)
        got = attention_kernel.backward(q, k, v, out, lse, d_out, **sizes)
        if exact:
            np.testing.assert_array_equal(got_out, out)
            np.testing.assert_array_equal(got_lse, lse)
        for g, w in zip((got_out, got_lse) + tuple(got),
                        (out, lse) + tuple(grads)):
            np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_path_names_exactly_residual_bytes(on_the_kernels, dtype):
    """What ``save_only_these_names(*RESIDUAL_NAMES)`` holds on the
    kernel path is what ``residual_bytes`` reckons: ``out`` at the
    values' head size in the inputs' dtype and ONE float32 a query."""
    rows, length, heads, kv_heads, v_head = 2, 300, 4, 2, 128
    dtype = jnp.dtype(dtype)
    q = jnp.ones((rows, length, heads, 64), dtype)
    k = jnp.ones((rows, length, kv_heads, 64), dtype)
    v = jnp.ones((rows, length, kv_heads, v_head), dtype)

    def layer(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=0.125, block=BLOCK)
                       .astype(jnp.float32))

    policy = jax.checkpoint_policies.save_only_these_names(
        *attention.RESIDUAL_NAMES)
    from jax._src.ad_checkpoint import saved_residuals

    saved = saved_residuals(
        jax.checkpoint(layer, policy=policy), q, k, v)
    held = [aval for aval, why in saved
            if not why.startswith("from the argument")]
    padded, groups = 3 * BLOCK, heads // kv_heads
    # ``out`` in tile layout and the log-sum-exp, nothing else
    assert [(a.shape, a.dtype) for a in held] == [
        ((rows, kv_heads, padded * groups, v_head), dtype),
        ((rows, kv_heads, padded * groups), jnp.float32)]
    assert any("attention_lse" in why for _, why in saved)
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in held) \
        == attention.residual_bytes(rows, length, heads, v_head, dtype,
                                    BLOCK)


@pytest.mark.parametrize("length,groups,qk_head,v_head,dtype,block,takes", [
    (8192, 1, 192, 128, "bfloat16", 512, True),    # JoyAI's cell
    (8192, 4, 64, 64, "bfloat16", 512, True),      # LFM2's cell
    (8192, 4, 64, 64, "float32", 512, True),
    (64, 4, 8, 8, "float32", 16, False),           # the toy models': scan
    (8192, 1, 192, 128, "float16", 512, False),    # a dtype never measured
    (8192, 1, 96, 128, "bfloat16", 512, False),    # a head size in no 64s
    (8192, 1, 192, 128, "bfloat16", 192, False),   # a block in no 128s
    (65536, 4, 128, 128, "bfloat16", 512, False),  # dq of a head: 200 MB
])
def test_which_shapes_take_the_kernels_is_a_rule_on_the_shapes(
        length, groups, qk_head, v_head, dtype, block, takes):
    blocks = attention.kernel_blocks(length, groups, qk_head, v_head,
                                     jnp.dtype(dtype), block)
    assert (blocks is not None) is takes
    if takes:
        block_q, block_kv = blocks
        assert block % block_q == 0 or groups == 1
        assert length % block_q == 0 == length % block_kv
    # off the TPU no call takes them, whatever its shape: the count the
    # step reports is a constant of the program lowered HERE
    assert int(attention.kernel_calls(length, 4 * groups, 4, qk_head,
                                      v_head, jnp.dtype(dtype), block)) == 0


def test_off_the_tpu_the_same_call_is_the_scan():
    """A call whose shapes the kernels tile, lowered where this process
    runs (the CPU): the scan's two loops, no kernel, no branch on the
    platform left in the program, and the plain attention's result."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (1, 256, 4, 64))
    k = jax.random.normal(keys[1], (1, 256, 2, 64))
    v = jax.random.normal(keys[2], (1, 256, 2, 128))
    assert attention._blocks_of(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), BLOCK, 2) is not None

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v, scale=0.125) ** 2)

    blockwise = jax.jit(jax.grad(loss(functools.partial(
        causal_attention, block=BLOCK)), argnums=(0, 1, 2)))
    text = blockwise.lower(q, k, v).as_text()
    assert text.count("stablehlo.while") == 2
    assert "custom_call" not in text and "stablehlo.case" not in text
    for g, w in zip(blockwise(q, k, v),
                    jax.grad(loss(plain_causal_attention),
                             argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)
    # and eagerly, outside any jit
    np.testing.assert_allclose(
        causal_attention(q, k, v, scale=0.125, block=BLOCK),
        plain_causal_attention(q, k, v, scale=0.125), atol=2e-6)
