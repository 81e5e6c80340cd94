"""The token step's two heaviest pieces compiled for the real chip at the
cell's real widths, without a chip (the TPU's compiler is installed and
compiles for a described v5e): the expert layer's grouped products over
the compact buffer and, under a conditional, over the worst-case
one (``lax.ragged_dot`` lowers to the chip's own kernel there, not to
the CPU's dense fallback), at both expert cells' shapes, and the
blockwise attention, each forward and backward. Lowered for the chip
the attention is its two Pallas kernels (``tpu_custom_call``), chosen by the platform the program
is lowered for and not by the process's backend (this one's is the CPU):
no ``while`` with the scan's float32 carries is left. What the chip's
compiler would refuse (a shape it cannot tile, more VMEM than a kernel
may take, a program that does not fit) fails here, at no chip time.
Nothing runs: no result and no time comes out of this file.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and a worker that cannot skips.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from flax import linen as nn
from jax.sharding import SingleDeviceSharding

from dptpu.models import lfm2, token_model
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


# tokens, experts a token, experts, held, expert width; the compact
# buffer's rows; the bytes of temporaries the layer's forward and backward
# take alone
EXPERT_CELLS = {
    "lfm2moe-fit-8k-1chip": (TOKENS, TOP_K, 32, HELD, WIDTH, 32768, 1.94e9),
    "joyai-fit-8k-1chip": (8192, 8, 256, HELD, 768, 4096, 1.26e9),
    "trinity-mini-fit-8k-1chip": (8192, 8, 128, 16, 1024, 16384, 1.35e9),
}


def _computations(text: str) -> dict:
    """A compiled program's computations by name, each as its lines."""
    out, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            out[name.lstrip("%")] = []
        elif name is not None:
            out[name.lstrip("%")].append(line)
    return out


def _reached(computations: dict, root: str) -> list:
    """The lines of ``root`` and of every computation it calls."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        lines += computations[name]
        for line in computations[name]:
            todo += re.findall(r"%([\w.\-]+)", line.split(" = ", 1)[-1])
    return lines


def _conditionals(computations: dict) -> list:
    """A program's conditionals: ``(what it gives, the lines its false
    branch reaches, those its true branch reaches)`` each (a cond on a
    boolean: the false branch comes first)."""
    found = []
    for lines in computations.values():
        for line in lines:
            if " conditional(" in line:
                false, true = re.search(
                    r"branch_computations=\{%([\w.\-]+), %([\w.\-]+)\}",
                    line).groups()
                found.append((line.split(" conditional(")[0],
                              _reached(computations, false),
                              _reached(computations, true)))
    return found


def _grouped_products(lines) -> int:
    """The chip's grouped-product kernel among ``lines``."""
    return sum("ragged" in ln and " custom-call(" in ln for ln in lines)


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_the_expert_layer_compiles_for_the_chip_at_the_cells_widths(
        one_chip, cell):
    tokens, top_k, experts, held, width, cap, temp = EXPERT_CELLS[cell]
    assert token_model.held_row_cap(tokens, top_k, held, experts) == cap

    def loss(x, w1, w3, w2, chosen, weights):
        out, sizes, compact = token_model.held_expert_outputs(
            x, chosen, weights, w1, w3, w2, 0, experts)
        return jnp.sum(out.astype(jnp.float32) ** 2), (sizes, compact)

    args = (_shape((tokens, HIDDEN), jnp.bfloat16, one_chip),
            _shape((held, HIDDEN, width), jnp.bfloat16, one_chip),
            _shape((held, HIDDEN, width), jnp.bfloat16, one_chip),
            _shape((held, width, HIDDEN), jnp.bfloat16, one_chip),
            _shape((tokens, top_k), jnp.int32, one_chip),
            _shape((tokens, top_k), jnp.float32, one_chip))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 5),
                                has_aux=True)).lower(*args).compile()
    text = compiled.as_text()
    # the chip's grouped-product kernel, forward and both backward forms,
    # on the compact rows and on the fallback's
    assert text.count("ragged-dot") >= 18 and "tpu_custom_call" in text
    # one conditional each way. Forward it holds the fallback alone (its
    # other branch gives zeros); backward it takes what the compact path
    # kept as operands and gives the cotangents. The branch a step takes
    # while its held slots fit moves no array of all 65,536 slots a
    # hundred wide, and no conditional GIVES a [cap, *] array
    computations = _computations(text)
    conditionals = _conditionals(computations)
    assert len(conditionals) == 2
    worst_rows = re.compile(rf"\w+\[{tokens * top_k},\d{{3,}}")
    inside = set()
    for gives, fallback, fits in conditionals:
        assert any(worst_rows.search(ln) for ln in fallback)
        assert _grouped_products(fallback)
        assert not [ln for ln in fits if worst_rows.search(ln)]
        assert not re.search(rf"\[(?:{cap}|{tokens * top_k}),\d{{3,}}\]",
                             gives)
        inside.update(fallback, fits)
    # the compact path's forward is straight-line code outside them:
    # every gather, mask and product there is [cap, *]; at the worst
    # case's rows there are the sort's int32 columns (the order, the keys
    # against the held experts' numbers), nothing a hundred wide
    outside = [ln for lines in computations.values() for ln in lines
               if ln not in inside]
    wide = [ln for ln in outside if worst_rows.search(ln)]
    assert not wide, wide[:3]
    assert any(f"[{cap},{HIDDEN}]" in ln for ln in outside)
    assert _grouped_products(outside) >= 3
    # what the compact path keeps for its way back (its rows and grouped
    # products) beside the inputs. With the compact path under the
    # conditional too (PR 42's form) the layer took 2.58, 1.28 and 1.62
    # GB (PERF.md section 6, PR 44)
    assert 0.9 * temp < compiled.memory_analysis().temp_size_in_bytes \
        < 1.1 * temp


@dataclasses.dataclass(frozen=True)
class _Share:
    """What ``SparseExperts`` reads of a configuration."""

    routing: token_model.Routing


class _ExpertBlock(nn.Module):
    """An expert layer as a block wraps it: behind a norm, on the
    residual path, the block's last step."""

    share: _Share

    @nn.compact
    def __call__(self, x):
        normed = token_model.RMSNorm(1e-5, jnp.bfloat16)(x)
        out, sizes, compact = token_model.SparseExperts(
            self.share, jnp.bfloat16)(normed)
        return x + out, sizes, compact


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_a_rematerialised_expert_block_keeps_its_class_on_the_chip(
        one_chip, cell):
    """The gradient of one rematerialised expert block at the cell's
    widths with the expert layer's class kept and with nothing kept:
    kept, the block's re-run makes neither the gather nor a grouped
    product again, the step holds no more for it than
    ``expert_residuals`` reckons, and no conditional gives anything but
    the fallback's part of the result and the cotangents: nothing of
    ``[tokens x k, *]`` and nothing of the compact buffer."""
    tokens, top_k, experts, held, width, cap, _ = EXPERT_CELLS[cell]
    share = _Share(token_model.Routing(
        experts, (0, held), top_k, True, 1e-6, 1.0, True, width))
    what, names, size = token_model.expert_residuals(
        share.routing, tokens, HIDDEN, 1, jnp.bfloat16)
    assert size == cap * 2 * (HIDDEN + width) * 2 + tokens * top_k * 4
    x = _shape((1, tokens, HIDDEN), jnp.bfloat16, one_chip)

    def compiled(kept):
        block = token_model.rematerialised(_ExpertBlock, kept)(share)
        variables = jax.tree_util.tree_map(
            lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip),
            jax.eval_shape(block.init, jax.random.PRNGKey(0), x))
        params = variables.pop("params")

        def loss(params, buffers, x):
            out, *load = block.apply({"params": params, **buffers}, x)
            return jnp.sum(out.astype(jnp.float32)), load

        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 2), has_aux=True)).lower(
                params, variables, x).compile()

    made_again = compiled(token_model.Kept())
    held_back = compiled(token_model.Kept((what,), names, size))
    rows = re.compile(rf"\w+\[(?:{tokens * top_k}|{cap}),(\d+)\]")
    compact_path = {}
    for program in (made_again, held_back):
        computations = _computations(program.as_text())
        conditionals = _conditionals(computations)
        # the first pass's (the fallback's part of the result) and the
        # way back's: the re-run, whose result this block does not read,
        # has none
        assert len(conditionals) == 2
        inside = set()
        for gives, fallback, fits in conditionals:
            assert not [w for w in rows.findall(gives) if int(w) >= 100], \
                gives
            inside.update(fallback, fits)
        outside = [ln for lines in computations.values() for ln in lines
                   if ln not in inside]
        compact_path[program] = (
            _grouped_products(outside),
            sum(" gather(" in ln and f"[{cap},{HIDDEN}]" in ln
                for ln in outside))
    # the compact path's forward products and its gather of the rows:
    # made in the first pass and again in the re-run, or once
    (products, gathers), (kept_products, kept_gathers) = (
        compact_path[made_again], compact_path[held_back])
    assert products == 2 * kept_products >= 6, (products, kept_products)
    assert kept_gathers == gathers - 1 >= 1, (gathers, kept_gathers)
    # what the step holds more for it: under the class's bytes at LFM2's
    # shape (+335 MB for 503: part of what is kept the re-run held at the
    # same point of the step). One block alone says little more: its
    # peak moves with the schedule (-2 MB for 201 at Trinity's shape,
    # +200 for 46 at JoyAI's); the whole steps, where a kept byte costs
    # 0.75 bytes, are in PERF.md section 6, PR 44
    grew = held_back.memory_analysis().temp_size_in_bytes \
        - made_again.memory_analysis().temp_size_in_bytes
    assert grew < 1.1 * size + 0.25e9, (grew, size)


def _attention_calls(text: str):
    """The forward and the backward kernel's custom calls in a compiled
    program's text."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return ([c for c in calls if "%causal_attention_forward" in c],
            [c for c in calls if "%causal_attention_backward" in c])


def test_blockwise_attention_compiles_for_the_chip_at_the_cells_shape(
        one_chip):
    q = _shape((2, 8192, 32, 64), jnp.bfloat16, one_chip)
    kv = _shape((2, 8192, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=0.125)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    # one kernel each way where the scan had two loops, and every large
    # operand with the tokens along the lanes: a head size of 64 is no
    # padding up to 128 in HBM
    forward, backward = _attention_calls(text)
    assert len(forward) == len(backward) == 1 and " while(" not in text
    assert "bf16[2,8,64,32768]" in forward[0] + backward[0]
    assert "bf16[2,8,32768,64]" not in forward[0] + backward[0]
    # the scan's program took 201.6 MB here (its float32 carries); the
    # kernels' takes 201.9: q, d_out and dq in both layouts
    assert compiled.memory_analysis().temp_size_in_bytes < 0.21e9


def test_attention_with_two_head_sizes_compiles_for_the_chip_at_the_cells_shape(
        one_chip):
    """Latent attention at the size of the JoyAI cell: one row of 8,192
    tokens, 32 heads, queries and keys of 192, values of 128."""
    q = _shape((1, 8192, 32, 192), jnp.bfloat16, one_chip)
    v = _shape((1, 8192, 32, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=192 ** -0.5)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile()
    text = compiled.as_text()
    forward, backward = _attention_calls(text)
    assert len(forward) == len(backward) == 1 and " while(" not in text
    # the output at the values' head size, dq and dk at the queries',
    # and none of the scan's float32 carries in HBM
    assert "bf16[1,32,128,8192]" in forward[0]
    assert "bf16[1,32,16,192,512]" in backward[0]  # dq, a row block a slab
    assert "f32[1,32,8192,128]" not in text and "f32[1,32,8192,192]" \
        not in text
    # half the scan's 537 MB: its dq, dk and dv carries were float32
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("window,tiles", [(None, 136), (2048, 70)])
def test_eight_heads_of_128_a_key_value_head_compile_for_the_chip(
        one_chip, window, tiles):
    """Trinity-Mini's calls at the cell's shape: one row of 8,192 tokens,
    32 query heads over 4 key/value heads of 128, a window of 2,048 and
    none. The backward kernel holds the float32 ``dq`` of all 8 query
    heads of a key/value head (67 MB of the 84 the rule allows): the
    largest call the kernels have taken."""
    from dptpu.ops import attention

    assert attention._vmem_bytes(8 * 8192, 128, 128, 2, 512, 512) \
        == 75_759_616 < attention.KERNEL_VMEM_BYTES
    q = _shape((1, 8192, 32, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 8192, 4, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, scale=128 ** -0.5,
                                        window=window).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    text = compiled.as_text()
    forward, backward = _attention_calls(text)
    assert len(forward) == len(backward) == 1 and " while(" not in text
    # the pairs a kernel walks are its prefetched scalars: 128 row blocks
    # of 512 (8 heads x 16) over the causal triangle's key blocks, or
    # over the band's
    pairs = f"s32[{8 * tiles}]"
    assert pairs in forward[0] and pairs in backward[0]
    assert attention.tiles_walked(8192, window) == tiles
    # dq, a row block a slab, all 8 heads of a key/value head together
    assert "bf16[1,4,128,128,512]" in backward[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.15e9


def test_a_rematerialised_attention_block_keeps_out_and_lse_on_the_chip(
        one_chip):
    """An attention layer at the cell's shape as the model wraps it (a
    dense feed-forward behind it: the experts have their own case
    above), ``out`` and ``lse`` kept through the rematerialisation: the
    forward kernel is in the program once, not twice."""
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
        out, *load = block.apply({"params": params, **buffers}, x)
        return jnp.sum(out.astype(jnp.float32)), load

    params = variables.pop("params")
    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, variables, x).compile()
    text = compiled.as_text()
    forward, backward = _attention_calls(text)
    # forward and backward, no forward again; no scan left
    assert len(forward) == len(backward) == 1, (forward, backward)
    assert not [line for line in text.splitlines()
                if " while(" in line and "f32[2,8,32768,64]" in line]
    # what is held between the passes is what ``residual_bytes`` reckons:
    # the kernel's own ``out``, 64 wide with the tokens along the lanes
    # (67.1 MB, not a [.., 64] array padded up to 128 lanes), and one
    # float32 a query
    assert "(bf16[2,8,64,32768]{" in forward[0]
    assert "f32[2,8,1,32768]{" in forward[0]
    # one layer's activations and its weights' gradients: 1.062 GB with
    # the scan and with the kernels (1.50 GB with nothing kept)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1e9


def test_a_state_space_and_an_attention_layer_step_compiles_for_the_chip(
        one_chip):
    """The whole train step (``make_train_step``, AdamW, O2) of
    granite-4.0-h-micro's published layers 4 and 5, a Mamba-2 layer and
    the attention layer, at the cell's row of 8,192 tokens and its
    vocabulary rows: the chunked scan's groups fit beside the block being
    run again, and the one attention call is the two kernels."""
    from dptpu.models import create_model
    from dptpu.ops import optimizers, ssd
    from dptpu.train.state import create_train_state
    from dptpu.train.step import make_train_step

    length = 8192
    model = create_model("granite_4_0_h_micro", dtype=jnp.bfloat16,
                         layers="4:2", vocab="0:12544",
                         sequence_length=length)
    assert [kind for _, kind in model.config.types_here] == [
        "mamba", "attention"]
    tx = optimizers.adamw(0.9, 0.95, 1e-8, 0.1)
    placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda leaf: _shape(leaf.shape, leaf.dtype, one_chip), tree)
    state = placed(jax.eval_shape(lambda: create_train_state(
        jax.random.PRNGKey(0), model, tx, input_shape=(1, length),
        input_dtype=jnp.int32)))
    batch = placed({"tokens": jax.ShapeDtypeStruct((1, length), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((1, length), jnp.int32),
                    "mask": jax.ShapeDtypeStruct((1, length), jnp.bool_)})
    compiled = make_train_step(None, jnp.bfloat16, task="tokens").lower(
        state, batch).compile()
    text = compiled.as_text()
    forward, backward = _attention_calls(text)
    # nothing is kept at a budget of 0: the forward kernel runs again
    assert (len(forward), len(backward)) == (2, 1), (forward, backward)
    # a row is walked in groups of 8 chunks (128 MB of decay matrices a
    # group): no array of all 32 chunks' [64, 256, 256] float32 is made
    assert ssd._group_size(1, ssd.chunks_of(length), 64, 256) == 8
    assert "f32[8,64,256,256]" in text
    assert "f32[32,64,256,256]" not in text
    # 1.40 GB: the scan's group and the feed-forward's 8,192 x 16,384
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9
