"""What the token-sequence models share (``lfm2.py``, ``joyai.py``,
``granite.py``).

Each is built from a configuration object, cut to one chip's share of
an expert-parallel, vocabulary-parallel, pipelined deployment, trained by
``fit()`` on ``tokens:<N>`` (``task = "tokens"``) with rematerialised
blocks that keep named residuals within a budget. This module holds the
pieces that are the same mathematics in more than one, each once:

* ``RMSNorm``, ``SwiGLU``, the half-split ``rotary``;
* the expert layer: ``route`` (the top k of score + bias, weighted by
  the scores themselves), ``held_expert_outputs`` (the part of the
  result the experts held here give: a compact buffer for the slots
  they hold, the worst-case buffer where a step's routing passes it, no
  capacity, no dropped token) and ``SparseExperts``, which reads what
  it needs from a ``Routing``;
* ``Kept`` / ``keep_within``: which classes of named residuals a step
  keeps through the rematerialisation within a budget of bytes;
* ``TokenModel``: the module both models extend (``example_input``,
  ``kept``, ``fitted_to``), ``rematerialised`` (a block under the policy
  that keeps), ``with_counters`` and ``with_scan_counters`` (what a step
  counts beside the loss), and ``factory``, the registry's way to a
  chip's share.

A model says what is its own: its blocks, its ``residual_classes`` (the
order of keeping is milliseconds saved per byte, measured on the chip for
that model) and its ``step_headroom_bytes`` (the room its fully
rematerialised step takes on the device beside state and residuals).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dptpu.ops import attention as attention_op

dense_init = nn.initializers.normal(0.02)


def dense(features: int, name: str, dtype):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=dense_init, name=name)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, the
    statistics in float32."""

    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def rotary(x, theta: float):
    """Rotary positions over the whole last axis of ``x`` ``[B, S, H, D]``
    (the half-split convention: ``x * cos + rotate_half(x) * sin``),
    in float32."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


class SwiGLU(nn.Module):
    """``W2 (silu(W1 x) * W3 x)`` at ``width``, under the trace scope
    ``trace_scope``; ``keep`` names the two products for a rematerialisation
    that keeps them (None: they carry no name)."""

    width: int
    dtype: Any = jnp.float32
    trace_scope: str = "dense_ffn"
    keep: Optional[Tuple[str, str]] = ("ffn_gate", "ffn_up")

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(self.trace_scope):
            gate = dense(self.width, "w1", self.dtype)(x)
            up = dense(self.width, "w3", self.dtype)(x)
            if self.keep:
                gate, up = map(checkpoint_name, (gate, up), self.keep)
            return dense(x.shape[-1], "w2", self.dtype)(nn.silu(gate) * up)


# ------------------------------------------------------- the expert layer --


@dataclasses.dataclass(frozen=True)
class Routing:
    """What the expert layer reads of a configuration: the router's
    width (``experts``, all of them, held or not), the ``(first, count)``
    held here, the experts a token takes, how their weights are
    normalised (``norm_eps`` is the family's: added to the sum) and
    scaled, whether a selection bias exists, an expert's width."""

    experts: int
    held: Tuple[int, int]
    top_k: int
    norm_topk: bool
    norm_eps: float
    scaling: float
    use_bias: bool
    width: int

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and 0 < count and first + count <= self.experts):
            raise ValueError(
                f"experts held {first}:{count} are not among the "
                f"{self.experts} experts")


class _Expert(nn.Module):
    """One expert's three matrices, under its published index."""

    hidden: int
    width: int

    @nn.compact
    def __call__(self):
        return (self.param("w1", dense_init, (self.hidden, self.width)),
                self.param("w3", dense_init, (self.hidden, self.width)),
                self.param("w2", dense_init, (self.width, self.hidden)))


def route(scores, bias, k: int, norm_topk: bool, scaling: float, *,
          eps: float):
    """The experts of each token and their weights: the top ``k`` of
    ``scores + bias``, weighted by ``scores`` itself at those k (over
    their sum + ``eps`` under ``norm_topk``)."""
    _, chosen = lax.top_k(scores + bias, k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + eps)
    return chosen, weights * scaling


# A held-expert buffer's rows over what uniform routing sends the held
# experts (``tokens x k x held / experts``). A performance constant only:
# a layer whose held slots pass the buffer in some step takes the
# worst-case path for that step and loses nothing, so it trades rows that
# the gathers, masks and products move every step against how often a
# step pays the worst case. Measured on the chip (PERF.md section 6, PR
# 42): at 8 of 32 experts held (LFM2's cell) the held total stays within
# 3% of the uniform share and a layer costs 0.2 ms for each 1,024 rows of
# buffer; at 8 of 256 (JoyAI's) the total of one layer in one step is
# 0.4 to 1.5 times the share with a long tail: 1.5 let one layer step in
# fourteen overflow and 2 one in eighty, and an overflow costs ten
# compact layers. 2 is half and a sixteenth of the worst case's rows.
CAP_OVER_UNIFORM = 2.0
# the buffer is whole row tiles of the grouped product (the chip's kernel
# walks the rows 512 at a time: ``ragged_dot_tiling="512,..."`` in the
# program compiled for a v5e)
ROW_TILE = 512
# what the compact path keeps for its way back beside its inputs: the
# gathered rows and the three grouped products. Everything else between
# them is elementwise over ``[cap, *]`` and cheaper to compute again than
# to write out and read back. A block's rematerialisation keeps the same
# four where its budget holds them (``expert_residuals``), and with them
# the routing they were made under: a block's re-run makes the router's
# input again, a rounding apart from the first pass's where the compiler
# fused it otherwise, and a token whose top k flips between the passes
# would shift every kept row behind it against the order the way back
# sorts by
COMPACT_RESIDUALS = ("expert_rows", "expert_gate", "expert_up", "expert_out")
ROUTING_RESIDUAL = "expert_chosen"


def held_row_cap(tokens: int, k: int, count: int, experts: int) -> int:
    """The rows of the held experts' buffer: ``CAP_OVER_UNIFORM`` times
    their share of the ``tokens x k`` slots under uniform routing, in
    whole ``ROW_TILE``s, and never more than the worst case (every slot
    on a held expert), which is what a layer that holds all its experts
    gets."""
    worst = tokens * k
    share = CAP_OVER_UNIFORM * worst * count / experts
    return min(worst, math.ceil(share / ROW_TILE) * ROW_TILE)


def sorted_slots(chosen, first: int, count: int):
    """The ``tokens x k`` slots of ``chosen`` sorted by expert (stable),
    those of the experts held (``first .. first + count``) in one run
    each before all others: the order, and the runs' sizes (the tokens
    each held expert got)."""
    local = chosen - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return jnp.argsort(key, stable=True), sizes


def _gate_and_up(rows, w1, w3, sizes):
    """The first two grouped products of ``_grouped_swiglu``."""
    return lax.ragged_dot(rows, w1, sizes), lax.ragged_dot(rows, w3, sizes)


def _down(gate, up, w2, sizes, in_a_run):
    """Its third, of ``silu(gate) * up`` zeroed behind the last run."""
    return lax.ragged_dot(
        jnp.where(in_a_run, nn.silu(gate) * up, 0), w2, sizes)


def _grouped_swiglu(rows, in_a_run, w1, w3, w2, sizes, named=True):
    """``W2 (silu(W1 x) * W3 x)`` of each run of ``rows`` under its own
    expert's matrices: three grouped products (``named``, with the rows
    that go in, for a rematerialisation that keeps them:
    ``COMPACT_RESIDUALS``); the result and the four. Rows behind the last
    run are in no group: the grouped product on the chip leaves what it
    does not compute as it finds it (whatever the memory held), in the
    result and in the cotangents alike, so those rows are zeroed going in
    and coming out, which zeroes their cotangents too (a token's slot on
    an absent expert adds nothing to the token's gradient)."""
    rows = jnp.where(in_a_run, rows, 0)
    gate, up = _gate_and_up(rows, w1, w3, sizes)
    kept = (rows, gate, up, _down(gate, up, w2, sizes, in_a_run))
    if named:
        kept = tuple(map(checkpoint_name, kept, COMPACT_RESIDUALS))
    return jnp.where(in_a_run, kept[-1], 0), kept


def worst_case_outputs(x, weights, w1, w3, w2, order, sizes, named=True):
    """The held experts' part of the result over a buffer of all
    ``tokens x k`` slots (``order``: the slots sorted by expert, the
    held ones' runs first; ``sizes``: the runs): no capacity bounds a
    run, and rows behind the last run cost the grouped product nothing,
    but every gather, mask and product beside it moves all the rows. The
    sorted rows go back to their tokens by the inverse permutation and
    are summed by their weights. ``named``: whether the rows and products
    carry ``COMPACT_RESIDUALS``' names (a layer whose only buffer this
    is) or none (the fallback beside a compact buffer)."""
    tokens, k = weights.shape
    in_a_run = jnp.arange(tokens * k)[:, None] < jnp.sum(sizes)
    out, _ = _grouped_swiglu(x[order // k], in_a_run, w1, w3, w2, sizes,
                             named)
    back = jnp.argsort(order)
    out = out[back].reshape(tokens, k, -1)
    mixed = jnp.einsum("tkh,tk->th", out, weights.astype(out.dtype),
                       preferred_element_type=jnp.float32)
    return mixed.astype(x.dtype)


def _compact_slots(order, sizes, cap: int, k: int):
    """The compact buffer's slots, their tokens, and which of its rows
    lie in a run."""
    slot = order[:cap]
    return slot, slot // k, jnp.arange(cap)[:, None] < jnp.sum(sizes)


def _added_back(out, weights, slot, token, in_a_run, dtype):
    """Each row of ``out`` (zeroed behind the last run) times its slot's
    weight, added into its token: float32 sums, as the worst case's."""
    out = jnp.where(in_a_run, out, 0)
    weight = weights.reshape(-1)[slot].astype(out.dtype)
    mixed = jnp.zeros((weights.shape[0], out.shape[-1]), jnp.float32)
    return mixed.at[token].add(
        out.astype(jnp.float32) * weight.astype(jnp.float32)[:, None]
    ).astype(dtype)


def _compact_forward(x, weights, w1, w3, w2, order, sizes, cap: int):
    """``compact_outputs`` and what its way back reads beside its inputs
    (``COMPACT_RESIDUALS``, named)."""
    slot, token, in_a_run = _compact_slots(order, sizes, cap,
                                           weights.shape[1])
    _, kept = _grouped_swiglu(x[token], in_a_run, w1, w3, w2, sizes)
    return _added_back(kept[-1], weights, slot, token, in_a_run,
                       x.dtype), kept


def compact_outputs(x, weights, w1, w3, w2, order, sizes, cap: int):
    """The same over the first ``cap`` sorted slots only, which is all
    of the held ones' runs where ``sum(sizes) <= cap``: every array is
    ``[cap, *]``. On the way back each row times its slot's weight is
    added into its token (float32 sums, as the worst case's), so neither
    the inverse permutation nor a ``[tokens x k, hidden]`` gather is
    made; its transpose is a gather of ``cap`` rows, and ``x``'s
    cotangent a scatter-add of ``cap`` rows."""
    return _compact_forward(x, weights, w1, w3, w2, order, sizes, cap)[0]


def _compact_way_back(cap: int, kept, x, weights, w1, w3, w2, order, sizes,
                      ct):
    """``_compact_forward``'s cotangents for ``x``, the weights and the
    three matrices from what it kept: its four steps transposed one
    after another, last first. Everything elementwise between the kept
    arrays is made again (``jax.vjp`` also traces each step's product
    forward; nothing reads it and the compiler drops it)."""
    rows, gate, up, out = kept
    slot, token, in_a_run = _compact_slots(order, sizes, cap,
                                           weights.shape[1])
    d_out, d_weights = jax.vjp(
        lambda out, weights: _added_back(
            out, weights, slot, token, in_a_run, x.dtype),
        out, weights)[1](ct)
    d_gate, d_up, d_w2 = jax.vjp(
        lambda gate, up, w2: _down(gate, up, w2, sizes, in_a_run),
        gate, up, w2)[1](d_out)
    d_rows, d_w1, d_w3 = jax.vjp(
        lambda rows, w1, w3: _gate_and_up(rows, w1, w3, sizes),
        rows, w1, w3)[1]((d_gate, d_up))
    d_x, = jax.vjp(
        lambda x: jnp.where(in_a_run, x[token], 0), x)[1](d_rows)
    return d_x, d_weights, d_w1, d_w3, d_w2


def _worst_case_way_back(kept, x, weights, w1, w3, w2, order, sizes, ct):
    """The fallback's cotangents: its forward made again from its inputs
    (it kept nothing)."""
    del kept
    return jax.vjp(
        lambda *differentiated: worst_case_outputs(
            *differentiated, order, sizes, named=False),
        x, weights, w1, w3, w2)[1](ct)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_outputs(cap: int, x, weights, w1, w3, w2, order, sizes):
    return _held_forward(cap, x, weights, w1, w3, w2, order, sizes)[0]


def _held_forward(cap: int, *operands):
    """The compact path as straight-line code (its runs emptied in a
    step that overflows: its rows are then in no group and give zeros)
    plus the worst case's part under a ``cond`` whose other branch gives
    zeros; the residuals are the compact path's four and the inputs."""
    x, sizes = operands[0], operands[-1]
    fits = jnp.sum(sizes) <= cap
    out, kept = _compact_forward(
        *operands[:-1], jnp.where(fits, sizes, 0), cap)
    rest = lax.cond(
        fits, lambda *_: jnp.zeros_like(x),
        functools.partial(worst_case_outputs, named=False), *operands)
    return out + rest, (kept, *operands)


def _held_way_back(cap: int, residuals, ct):
    """One ``cond``: the compact path's way back from what it kept, or
    the fallback's from its inputs (the compact path gave zeros then, and
    has no gradient to give)."""
    sizes = residuals[-1]
    grads = lax.cond(
        jnp.sum(sizes) <= cap, functools.partial(_compact_way_back, cap),
        _worst_case_way_back, *residuals, ct)
    return (*grads, None, None)  # the order and the sizes are integers


_held_outputs.defvjp(_held_forward, _held_way_back)


def held_expert_outputs(x, chosen, weights, w1, w3, w2, first: int,
                        experts: int):
    """What the experts held here (``first .. first + len(w1)`` of
    ``experts``) give for the tokens routed to them: ``[tokens, hidden]``
    in ``x``'s dtype, the tokens each of them got, and whether the
    compact buffer held them (an int32 0 or 1).

    The ``tokens x k`` slots are sorted by expert, the slots of absent
    experts behind all others; the held ones form one run per expert, and
    three grouped matrix products (``lax.ragged_dot``) go over the runs.
    The rows that go through them are a buffer of ``held_row_cap`` rows,
    a static function of the shapes (``compact_outputs``). A step whose
    routing puts more slots than that on the held experts takes
    ``worst_case_outputs`` instead: every slot on a held expert fits
    there, so no capacity bounds a run and no token is dropped on either
    path.

    Forward, the two parts are ADDED (``_held_forward``): the compact
    path is straight-line code, and the conditional holds the fallback
    alone. Backward there is one ``cond`` that takes the compact path's
    four arrays (``COMPACT_RESIDUALS``) and the inputs as operands and
    gives the cotangents (``_held_way_back``): the layer is one
    ``jax.custom_vjp``. So no ``[cap, *]`` array is ever a conditional's
    RESULT: results of conditionals are buffers nothing else reuses, and
    under reverse mode a ``cond``'s residuals (the union of both
    branches') are copied through every conditional between its forward
    and its way back; with the compact path under the conditional each
    further layer's kept arrays cost a step twice their bytes (PERF.md
    section 6, PR 44). The fallback keeps nothing and names nothing: only
    a step that overflows pays its forward again, whatever policy a block
    around this is rematerialised under. The compact path keeps its four
    arrays beside its inputs and makes the elementwise steps between them
    again; they carry names, and a block's rematerialisation that keeps
    those names (``expert_residuals``) finds them where the first pass
    left them: its re-run makes neither the gather nor a grouped product
    again, and no conditional. A layer that holds all its experts has no
    smaller buffer than the worst case and no ``cond``: its one buffer
    carries the names.
    """
    tokens, k = chosen.shape
    count = w1.shape[0]
    order, sizes = sorted_slots(chosen, first, count)
    cap = held_row_cap(tokens, k, count, experts)
    operands = (x, weights, w1, w3, w2, order, sizes)
    if cap == tokens * k:
        return worst_case_outputs(*operands), sizes, jnp.ones((), jnp.int32)
    fits = jnp.sum(sizes) <= cap
    return _held_outputs(cap, *operands), sizes, fits.astype(jnp.int32)


class SparseExperts(nn.Module):
    """The expert layer: routes over all experts, computes the held
    ones' part. ``config`` is a model's configuration; the layer reads
    its ``routing`` (a ``Routing``). Gives the part, the tokens each
    held expert got and whether the compact buffer held them
    (``held_expert_outputs``)."""

    config: Any
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        r = self.config.routing
        first, count = r.held
        batch, length, hidden = x.shape
        flat = x.reshape(batch * length, hidden)
        with jax.named_scope("router"):
            gate = self.param("gate", dense_init, (hidden, r.experts))
            scores = jax.nn.sigmoid(jnp.matmul(
                flat.astype(jnp.float32), gate,
                precision=lax.Precision.HIGHEST))
            bias = 0.0
            if r.use_bias:
                bias = self.variable(
                    "batch_stats", "expert_bias", jnp.zeros,
                    (r.experts,), jnp.float32).value
            chosen, weights = route(scores, bias, r.top_k, r.norm_topk,
                                    r.scaling, eps=r.norm_eps)
            chosen = checkpoint_name(chosen, ROUTING_RESIDUAL)
        with jax.named_scope("experts"):
            w1, w3, w2 = (
                jnp.stack(ws).astype(self.dtype) for ws in zip(*(
                    _Expert(hidden, r.width, name=f"experts_{first + e}")()
                    for e in range(count))))
            out, sizes, compact = held_expert_outputs(
                flat, chosen, weights, w1, w3, w2, first, r.experts)
        return out.reshape(x.shape), sizes, compact


# --------------------------------- residuals kept through rematerialisation --


@dataclasses.dataclass(frozen=True)
class Kept:
    """What the blocks of one step keep through the rematerialisation."""

    classes: Tuple[str, ...] = ()
    names: Tuple[str, ...] = ()
    bytes: int = 0

    @property
    def megabytes(self) -> int:
        return round(self.bytes / 1e6)

    def notice(self, budget: int) -> str:
        kept = ", ".join(self.classes) or "nothing (every block's forward " \
            "is run again on the way back)"
        return (f"=> residuals kept through the rematerialisation: {kept} "
                f"({self.megabytes:,} MB a step of a budget of "
                f"{round(budget / 1e6):,} MB)")


def keep_within(classes, budget: int) -> Kept:
    """The ``classes`` (``(what it is, the names it keeps, the bytes
    they hold over all layers)``, in the order of keeping) that fit
    ``budget`` bytes: whole classes (all layers or none), up to the first
    that no longer fits. A class the share has no layer for holds nothing
    and is not listed."""
    kept, names, total = [], [], 0
    for what, class_names, size in classes:
        if total + size > budget:
            break
        if size:
            kept.append(what)
            names.extend(class_names)
            total += size
    return Kept(tuple(kept), tuple(names), total)


def expert_residuals(routing: Routing, tokens: int, hidden: int, layers: int,
                     dtype):
    """``keep_within``'s class for ``layers`` expert layers of a step on
    ``tokens`` tokens: what an expert layer's buffer makes that its way
    back reads (``COMPACT_RESIDUALS``: the gathered rows and the third
    product at ``hidden``, the first two at the expert's width), at the
    buffer's own rows (``held_row_cap``: the compact buffer's where there
    is one, every slot where the layer holds all its experts), and the
    experts each token chose (``ROUTING_RESIDUAL``, int32): the sorted
    order the kept rows stand in is made from it. Kept through a block's
    rematerialisation, the block's re-run makes neither the gather nor a
    grouped product again; the fallback beside a compact buffer names
    nothing and keeps nothing (``held_expert_outputs``)."""
    _, count = routing.held
    cap = held_row_cap(tokens, routing.top_k, count, routing.experts)
    return ("expert rows and products",
            (*COMPACT_RESIDUALS, ROUTING_RESIDUAL),
            layers * (cap * 2 * (hidden + routing.width)
                      * jnp.dtype(dtype).itemsize
                      + tokens * routing.top_k * 4))


class TokenModel(nn.Module):
    """What ``fit()`` and the step builder ask of a token-sequence model.

    ``__call__(tokens)`` gives float32 logits ``[B, S, vocab held]``;
    with ``labels`` and ``mask`` it gives sums, not logits (the loss goes
    over row blocks of the head, ``dptpu.ops.loss``):

    ``loss_sum, count, correct1, correct5`` (float32 scalars; ``loss_sum``
    is the whole objective); ``moe_counts`` ``[expert layers, experts
    held]`` int32, the tokens each held expert got; ``moe_slots``, the
    slots routed in all (tokens x k x expert layers, held or not);
    ``moe_dropped``, the tokens dropped: a constant 0, there for the day
    a capacity scheme moves it (the compact buffer of
    ``held_expert_outputs`` is none: a layer whose held slots pass it
    takes the worst-case buffer for that step, which holds every slot);
    ``moe_compact``, the expert layers whose held slots fitted the compact
    buffer in this step, of ``moe_layers`` expert layers (int32; the
    first moves with the routing, the second is a constant);
    ``kept_residual_mb``, the megabytes this
    step's blocks keep through the rematerialisation: a constant of the
    traced program; ``attention_calls`` and ``attention_kernel_calls``,
    the calls of ``dptpu.ops.attention`` in one forward pass and those of
    them that take its kernels in the program being lowered (all on a
    TPU where the shapes tile, none elsewhere), ``attention_window_calls``,
    those with a window shorter than the row, and ``attention_tiles`` of
    ``attention_tiles_causal``, the key tiles they walk of what the
    causal triangle holds (``with_counters``): constants of the lowered
    program. A model with state-space layers adds ``ssd_calls``,
    ``ssd_kernel_calls`` and ``ssd_chunks`` (``with_scan_counters``); a
    model without experts carries none of the ``moe_`` sums.

    ``residual_budget``: the bytes the blocks may keep (``keep_within``
    over the model's ``residual_classes``); 0 keeps nothing. It is 0
    unless someone who knows the device's memory sets it (``fit()`` does:
    ``fitted_to``).
    """

    config: Any
    dtype: Any = jnp.float32
    residual_budget: int = 0

    task = "tokens"
    # What a step takes on the device beside the train state and the
    # kept residuals; a model measures its own (``memory_analysis`` of
    # its fully rematerialised step on the chip, and room for the
    # allocator)
    step_headroom_bytes = 0

    @staticmethod
    def torch_key_map(variables):
        """``{torch_key: (collection, path, kind)}`` for every leaf of
        ``variables``: the model's own checkpoint names, which
        ``dptpu.models.pretrained`` asks the registry for."""
        raise NotImplementedError

    def residual_classes(self, shape):
        """``keep_within``'s classes for a step on token rows of ``shape``
        ``(rows, length)``."""
        raise NotImplementedError

    def example_input(self):
        """One row as ``init`` takes it."""
        return jnp.zeros((1, self.config.sequence_length), jnp.int32)

    def kept_on(self, shape) -> Kept:
        return keep_within(self.residual_classes(shape), self.residual_budget)

    def kept(self, rows: int) -> Kept:
        """What a step on ``rows`` rows keeps."""
        return self.kept_on((rows, self.config.sequence_length))

    def fitted_to(self, device_bytes: int, state_bytes: int):
        """This model with the budget a device of ``device_bytes`` leaves
        once the train state (``state_bytes``) and the step's own room
        are taken; 0 where the device reports no size."""
        budget = device_bytes - state_bytes - self.step_headroom_bytes
        return self.clone(
            residual_budget=max(budget, 0) if device_bytes else 0)


def rematerialised(block, kept: Kept):
    """``block`` (a module class) rematerialised on the way back but for
    the names ``kept`` keeps."""
    policy = jax.checkpoint_policies.save_only_these_names(
        *kept.names) if kept.names else None
    return nn.remat(block, policy=policy)


def no_experts():
    """What a block without an expert layer gives where ``SparseExperts``
    gives its load: no sizes, no buffer."""
    return jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)


def with_counters(sums: dict, loads, slots: int, kept: Kept, windows,
                  length: int, on_kernel) -> dict:
    """``sums`` with the expert layers' load (``loads``: what
    ``SparseExperts`` gave beside its output, one ``(sizes, compact)``
    per expert layer; ``slots``: the slots routed in all), the megabytes
    kept and the attention's calls in one forward pass on rows of
    ``length`` tokens. ``windows`` has one entry a call: its window, None
    for a causal one. A model's calls are of one shape and may be of two
    KINDS: a windowed call walks a band of the tiles a causal one walks
    (``attention.tiles_walked``), so the tiles are counted call by call
    (``attention_tiles``, of one row under one key/value head, beside
    what the causal triangle would walk, ``attention_tiles_causal``: a
    model without windows reports the two equal); which calls take the
    kernels is a rule on the shapes alone (``attention.kernel_blocks``
    does not read the window), so ``on_kernel``,
    ``attention.kernel_calls`` of one of them, answers for all. A rule
    that read the window would be asked once a kind."""
    if loads:
        counts, compact = zip(*loads)
        sums["moe_counts"] = jnp.stack(counts)
        sums["moe_slots"] = jnp.asarray(slots, jnp.int32)
        sums["moe_dropped"] = jnp.zeros((), jnp.int32)
        sums["moe_compact"] = sum(compact)
        sums["moe_layers"] = jnp.asarray(len(loads), jnp.int32)
    sums["kept_residual_mb"] = jnp.asarray(kept.megabytes, jnp.int32)
    calls = len(windows)
    sums["attention_calls"] = jnp.asarray(calls, jnp.int32)
    sums["attention_kernel_calls"] = calls * on_kernel
    sums["attention_window_calls"] = jnp.asarray(
        sum(attention_op.band(w, length) is not None for w in windows),
        jnp.int32)
    sums["attention_tiles"] = jnp.asarray(
        sum(attention_op.tiles_walked(length, w) for w in windows),
        jnp.int32)
    sums["attention_tiles_causal"] = jnp.asarray(
        calls * attention_op.tiles_walked(length), jnp.int32)
    return sums


def with_scan_counters(sums: dict, calls: int, on_kernel, chunks: int) -> dict:
    """``sums`` with the state-space scan's calls in one forward pass
    (``dptpu.ops.ssd``), those of them that run as a fused kernel in the
    program being lowered (``on_kernel``: ``ssd.kernel_calls`` of one of
    them, all being of one shape) and the chunks a row is walked in:
    constants of the lowered program. A model without the scan does not
    call this and its step carries none of the three."""
    sums["ssd_calls"] = jnp.asarray(calls, jnp.int32)
    sums["ssd_kernel_calls"] = jnp.asarray(calls * on_kernel, jnp.int32)
    sums["ssd_chunks"] = jnp.asarray(chunks, jnp.int32)
    return sums


def _pair(text: str, what: str) -> Tuple[int, int]:
    try:
        first, count = (int(part) for part in str(text).split(":"))
    except ValueError:
        raise ValueError(
            f"{what} {text!r} must be FIRST:COUNT, two whole numbers "
            f"(0:8 holds the first eight)") from None
    return first, count


def held_range(given: Tuple[int, int], total: int, what: str):
    """``given`` ``(first, count)`` if it lies among ``total``."""
    first, count = given
    if not (0 <= first and 0 < count and first + count <= total):
        raise ValueError(
            f"{what} {first}:{count} are not among the {total} {what}")
    return first, count


def factory(model, name: str, published):
    """A registry factory for the ``model`` class at the configuration
    ``published``, whole or a chip's share of it: ``layers``, ``experts``
    and ``vocab`` are ``"first:count"`` (the trainer's ``--layers``,
    ``--experts``, ``--vocab-rows``), ``sequence_length`` its
    ``--seq-len``; the configuration's ``held`` makes the cut. ``fit()``
    reads ``task`` off the factory before it builds anything: the data
    source, the step's loss and the arguments a factory is handed follow
    from it, and ``torch_key_map``, the model's own checkpoint names,
    when it converts weights."""

    def make(dtype=jnp.float32, layers=None, experts=None, vocab=None,
             sequence_length=None):
        return model(published.held(
            layers=_pair(layers, "--layers") if layers else None,
            experts=_pair(experts, "--experts") if experts else None,
            vocab=_pair(vocab, "--vocab-rows") if vocab else None,
            sequence_length=sequence_length), dtype=dtype)

    make.__name__ = name
    make.task = model.task
    make.torch_key_map = model.torch_key_map
    return make
