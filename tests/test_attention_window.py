"""The window of ``dptpu.ops.attention`` (sliding-window attention: key
``j`` is visible to query ``i`` where ``0 <= i - j < window``): the scan
and, in Pallas interpret mode, both kernels against the plain attention
with the same window, forward and all three gradients; and
``window=None`` lowered as the program it was before the window came
(PR 43), at the three token cells' call shapes, for the CPU and for a
TPU. What the chip's compiler makes of the windowed kernels at the
cell's shape is ``tests/test_tpu_compile.py``'s.
"""

import base64
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dptpu.ops import attention, attention_kernel
from dptpu.ops.attention import causal_attention, plain_causal_attention
from test_attention_kernel import BLOCK, on_the_kernels  # noqa: F401


@pytest.fixture(params=["scan", "kernels"])
def path(request):
    """The scan as the CPU runs it, or both passes on the interpreted
    kernels."""
    if request.param == "kernels":
        request.getfixturevalue("on_the_kernels")
    return request.param


def _inputs(length, heads, kv_heads, head, dtype):
    keys = jax.random.split(jax.random.PRNGKey(length + heads), 4)
    q = jax.random.normal(keys[0], (1, length, heads, head)).astype(dtype)
    k = jax.random.normal(keys[1], (1, length, kv_heads, head)).astype(dtype)
    v = jax.random.normal(keys[2], (1, length, kv_heads, head)).astype(dtype)
    weight = jax.random.normal(keys[3], (1, length, heads, head))
    return q, k, v, weight


def _out_and_grads(attend, q, k, v, weight, scale):
    def loss(q, k, v):
        out = attend(q, k, v, scale=scale)
        return jnp.sum(out.astype(jnp.float32) * weight), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return out, grads


# length, window, query heads, key/value heads, head size
WINDOWS = {
    # 8 query heads a key/value head at head size 128 (Trinity-Mini's);
    # neither the length nor the window whole blocks: queries 257-299
    # find their first walked tile (keys 0-127) wholly masked, so their
    # running maximum starts at the finite _MASKED, 128 ones are summed
    # under it, and the first visible score has to wipe them
    "first-tile-masked-8x128": (300, 130, 8, 1, 128),
    "window-1": (300, 1, 4, 2, 64),           # a query sees itself alone
    "whole-blocks": (384, 256, 4, 2, 64),     # both edges on tile borders
    "within-a-block": (200, 37, 2, 2, 64),    # two blocks, a narrow band
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", WINDOWS)
def test_a_window_is_the_plain_attention_under_the_same_mask(path, case,
                                                             dtype):
    length, window, heads, kv_heads, head = WINDOWS[case]
    dtype = jnp.dtype(dtype)
    q, k, v, weight = _inputs(length, heads, kv_heads, head, dtype)
    scale = head ** -0.5
    out, got = _out_and_grads(
        functools.partial(causal_attention, window=window, block=BLOCK),
        q, k, v, weight, scale)
    want_out, want = _out_and_grads(
        functools.partial(plain_causal_attention, window=window),
        q, k, v, weight, scale)
    assert out.shape == q.shape and out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out, np.float32), atol=tol)
    for g, w, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        scale_of = max(float(jnp.abs(w.astype(jnp.float32)).max()), 1.0)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   atol=tol * scale_of)
    # and the mask is the window's: the causal result differs
    causal = plain_causal_attention(q, k, v, scale=scale)
    assert float(jnp.abs(causal.astype(jnp.float32)
                         - want_out.astype(jnp.float32)).max()) > 0.1


@pytest.mark.parametrize("window", [300, 301, 10 ** 6])
def test_a_window_no_shorter_than_the_row_is_the_causal_program(path, window):
    q, k, v, weight = _inputs(300, 8, 1, 128, jnp.float32)
    attend = functools.partial(causal_attention, block=BLOCK)
    out, grads = _out_and_grads(functools.partial(attend, window=window),
                                q, k, v, weight, 0.1)
    want_out, want = _out_and_grads(attend, q, k, v, weight, 0.1)
    for g, w in zip((out, *grads), (want_out, *want)):
        np.testing.assert_array_equal(g, w)  # bit for bit
    assert attention.band(window, 300) is None
    assert attention.band(299, 300) == 299


def test_the_windowed_kernels_are_the_scan_tile_for_tile():
    """As the causal case: at the scan's block sizes output and
    log-sum-exp to the bit, at others (where a row block's first pair,
    a key block's last pair and both masked edges fall elsewhere) to
    rounding."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    groups, length, window = 2, 1024, 300
    q = jax.random.normal(keys[0], (1, 2, groups * length, 64))
    k = jax.random.normal(keys[1], (1, 2, length, 64))
    v = jax.random.normal(keys[2], (1, 2, length, 128))
    d_out = jax.random.normal(keys[3], (1, 2, groups * length, 128))
    out, lse = attention._forward(q, k, v, 256, groups, 0.125, window)
    grads = attention._backward(q, k, v, out, lse, d_out, 256, groups,
                                0.125, window)
    for block_q, block_kv, exact in ((256, 256, True), (128, 256, False),
                                     (256, 128, False)):
        sizes = dict(block=256, groups=groups, scale=0.125, window=window,
                     block_q=block_q, block_kv=block_kv, interpret=True)
        got_out, got_lse = attention_kernel.forward(q, k, v, **sizes)
        got = attention_kernel.backward(q, k, v, out, lse, d_out, **sizes)
        if exact:
            np.testing.assert_array_equal(got_out, out)
            np.testing.assert_array_equal(got_lse, lse)
        for g, w in zip((got_out, got_lse) + tuple(got),
                        (out, lse) + tuple(grads)):
            np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("length,window,block,tiles", [
    (8192, None, 512, 136),   # the causal triangle of 16 blocks
    (8192, 2048, 512, 70),    # Trinity-Mini's window layers: a band
    (8192, 8192, 512, 136),   # a window of the whole row
    (8192, 1, 512, 16),       # the diagonal
    (8192, 2, 512, 31),       # and the tile before it, for one query
    (8192, 513, 512, 31),
    (8192, 514, 512, 45),
    (2048, 512, 128, 70),     # the same 16 blocks a quarter the size
    (2048, 129, 128, 31),
    (2048, 130, 128, 45),
    (300, 130, 128, 6),       # a padded row: three blocks, all pairs
    (64, 16, 512, 1),         # a row shorter than a block
])
def test_the_tiles_walked_are_those_that_hold_a_visible_pair(
        length, window, block, tiles):
    assert attention.tiles_walked(length, window, block) == tiles
    block = min(block, length)
    n = -(-length // block)
    if length <= 2048:
        # by the definition, not by the rule: a tile counts if any of
        # its pairs is visible
        i, j = np.arange(n * block)[:, None], np.arange(n * block)[None, :]
        visible = (j <= i) & (i - j < (window or n * block))
        assert int(visible.reshape(n, block, n, block).any(axis=(1, 3))
                   .sum()) == tiles
    if window is not None and window < length:
        # and the kernels' pairs are the same tiles, row block by row
        # block and key block by key block
        for by_key in (False, True):
            rows, cols, *_ = attention_kernel._pairs(
                attention_kernel.row_positions(n * block, block, 1, block),
                n * block, block, block, by_key, window)
            assert len(rows) == tiles
            assert sorted(zip(rows.tolist(), cols.tolist())) == sorted(zip(
                *(a.tolist() for a in attention._tile_pairs(n, block,
                                                            window))))


def test_a_window_that_shows_nothing_is_refused():
    q, k, v, _ = _inputs(64, 2, 2, 8, jnp.float32)
    with pytest.raises(ValueError, match="window of 0"):
        causal_attention(q, k, v, scale=1.0, window=0)


# ---------------------------------------- window=None is the parent's program


def _without_locations(text: str) -> str:
    """A lowered program's text with each Pallas kernel's serialized body
    (MLIR bytecode, which carries the source's path and line numbers)
    replaced by the hash of its text without debug locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        context = mlir.make_ir_context()
        tpu.register_dialect(context)
        with context:
            context.allow_unregistered_dialects = True
            kernel = ir.Module.parse(base64.b64decode(match.group(1)))
            plain = kernel.operation.get_asm(enable_debug_info=False)
        return "body:" + hashlib.sha256(plain.encode()).hexdigest()

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)


# q, k, v shapes and the scale of the three token cells' calls, and the
# first 16 hex digits of the SHA-256 of the gradient's lowered text at
# the parent commit (89fc730, PR 42), for the CPU and for a TPU (kernel
# bodies without their locations). A change to jax, or a deliberate one
# to the causal program, moves them: recompute them THEN, on the commit
# before the change, with ``_lowered_digest``
PARENT_PROGRAMS = {
    "lfm2moe-fit-8k-1chip": (
        (2, 8192, 32, 64), (2, 8192, 8, 64), (2, 8192, 8, 64), 0.125,
        {"cpu": "555fff00f13fcc97", "tpu": "eedbe8e0eadcc753"}),
    "joyai-fit-8k-1chip": (
        (1, 8192, 32, 192), (1, 8192, 32, 192), (1, 8192, 32, 128),
        192 ** -0.5, {"cpu": "4c3a57355c4b708d", "tpu": "3f40c49c4c844f68"}),
    "granite-ssm-fit-1chip": (
        (1, 8192, 32, 64), (1, 8192, 8, 64), (1, 8192, 8, 64), 0.015625,
        {"cpu": "06cbbd93115873f0", "tpu": "6d734659a4eb38cd"}),
}


def _lowered_digest(q, k, v, scale, platform, **window):
    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=scale, **window)
                       .astype(jnp.float32))

    args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (q, k, v)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()
    return hashlib.sha256(
        _without_locations(text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("cell", PARENT_PROGRAMS)
def test_without_a_window_the_cells_calls_lower_as_they_did(cell, platform):
    q, k, v, scale, parent = PARENT_PROGRAMS[cell]
    assert _lowered_digest(q, k, v, scale, platform) == parent[platform]
    assert _lowered_digest(q, k, v, scale, platform, window=None) \
        == parent[platform]
    # a window of the row's length too; a shorter one is another program
    assert _lowered_digest(q, k, v, scale, platform, window=8192) \
        == parent[platform]
    assert _lowered_digest(q, k, v, scale, platform, window=2048) \
        != parent[platform]
