"""The token step's two heaviest pieces compiled for the real chip at the
cell's real widths, without a chip (the TPU's compiler is installed and
compiles for a described v5e): the expert layer's grouped products over
the worst-case buffer (``lax.ragged_dot`` lowers to the chip's own kernel
there, not to the CPU's dense fallback) and the blockwise attention's
scan, each forward and backward. What the chip's compiler would refuse
(a shape it cannot tile, a program that does not fit) fails here, at no
chip time. Nothing runs: no result and no time comes out of this file.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and a worker that cannot skips.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dptpu.models import lfm2
from dptpu.ops.attention import causal_attention

TOKENS, HIDDEN, WIDTH, HELD, TOP_K = 16384, 2048, 1792, 8, 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    # a compile for a described device is written to the persistent
    # cache and cannot be read back without a chip: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_the_expert_layer_compiles_for_the_chip_at_the_cells_widths(one_chip):
    def loss(x, w1, w3, w2, chosen, weights):
        out, sizes = lfm2.held_expert_outputs(x, chosen, weights, w1, w3, w2,
                                              first=0)
        return jnp.sum(out.astype(jnp.float32)), sizes

    args = (_shape((TOKENS, HIDDEN), jnp.bfloat16, one_chip),
            _shape((HELD, HIDDEN, WIDTH), jnp.bfloat16, one_chip),
            _shape((HELD, HIDDEN, WIDTH), jnp.bfloat16, one_chip),
            _shape((HELD, WIDTH, HIDDEN), jnp.bfloat16, one_chip),
            _shape((TOKENS, TOP_K), jnp.int32, one_chip),
            _shape((TOKENS, TOP_K), jnp.float32, one_chip))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3),
                                has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    # the chip's grouped-product kernel, forward and both backward forms
    assert text.count("ragged-dot") >= 9 and "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8e9


def test_blockwise_attention_compiles_for_the_chip_at_the_cells_shape(
        one_chip):
    q = _shape((2, 8192, 32, 64), jnp.bfloat16, one_chip)
    kv = _shape((2, 8192, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=0.125)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # never the [heads, S, S] scores (8.6 GB a row): two scans' carries
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert compiled.as_text().count("while(") >= 2


def test_attention_with_two_head_sizes_compiles_for_the_chip_at_the_cells_shape(
        one_chip):
    """Latent attention at the size PR 36 measured on the chip: one row of
    8,192 tokens, 32 heads, queries and keys of 192, values of 128."""
    q = _shape((1, 8192, 32, 192), jnp.bfloat16, one_chip)
    v = _shape((1, 8192, 32, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=192 ** -0.5)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile()
    text = compiled.as_text()
    # never the [heads, S, S] scores (8.6 GB): two scans, the forward's
    # accumulator at the values' head size, the backward's dq and dk at
    # the queries' and dv at the values'
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
    assert text.count("while(") >= 2
    assert "f32[1,32,8192,128]" in text and "f32[1,32,8192,192]" in text


def test_a_rematerialised_attention_block_keeps_out_and_lse_on_the_chip(
        one_chip):
    """An attention layer at the cell's shape as the model wraps it (a
    dense feed-forward behind it: the experts have their own case
    above), ``out`` and ``lse`` kept through the rematerialisation: the
    forward scan is in the program once, not twice."""
    from flax import linen as nn

    share = lfm2.Lfm2Config().held(layers=(2, 1), sequence_length=8192)
    kept = lfm2.kept_residuals(share, (2, 8192), jnp.bfloat16, 70_000_000)
    assert kept.classes == ("attention out+lse",) and kept.bytes == 69_206_016
    block = nn.remat(lfm2.Block, policy=jax.checkpoint_policies
                     .save_only_these_names(*kept.names))(
        share, "full_attention", True, jnp.bfloat16)
    x = _shape((2, 8192, HIDDEN), jnp.bfloat16, one_chip)
    variables = jax.tree_util.tree_map(
        lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip),
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x))

    def loss(params, buffers, x):
        out, sizes = block.apply({"params": params, **buffers}, x)
        return jnp.sum(out.astype(jnp.float32)), sizes

    params = variables.pop("params")
    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, variables, x).compile()
    scans = [line for line in compiled.as_text().splitlines()
             if " while(" in line and "f32[2,8,32768,64]" in line]
    assert len(scans) == 2, scans  # forward and backward, no forward again
    # one layer's activations and its weights' gradients: 1.06 GB when
    # this was written (1.50 GB and three scans with nothing kept)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.3e9
