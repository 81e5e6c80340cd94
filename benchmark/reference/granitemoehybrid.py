"""Plain reference for the ``granitemoehybrid`` family (IBM
granite-4.0-h-micro,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).

The published equations in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, the checkpoint's leaf names, nothing of ``dptpu``:

* ``x = embed_tokens[ids] * embedding_multiplier``; every block ``x = x +
  residual_multiplier * mixer(rms(x, input_layernorm))``, then ``x = x +
  residual_multiplier * shared_mlp(rms(x, post_attention_layernorm))``;
  after the last block ``rms(x, model.norm)``, then the tied head
  ``x @ embed_tokens.T / logits_scaling``.
* ``shared_mlp``: ``output_linear(silu(g) * u)``, ``[g | u] =
  input_linear x`` (one matrix, the gate's half first). No experts.
* ``mamba`` mixer (Mamba-2): ``[z | xBC | dt] = in_proj u``; ``xBC =
  silu(conv1d(xBC) + bias)``, depthwise, causal, ``mamba_d_conv`` taps
  (torch ``[channels, 1, taps]``); ``[x | B | C] = xBC``; ``Δ =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; THE RECURRENCE ITSELF,
  one token at a time from a zero state:

      h_t = exp(Δ_t A) h_{t-1} + Δ_t x_t ⊗ B_t,    y_t = h_t C_t + D x_t

  (a head's ``h`` is ``mamba_d_head x mamba_d_state``; ``B``, ``C`` are
  shared by all heads: one group), NOT the chunked form the program
  computes: the two sides agree only if the chunked algebra is right.
  Then ``rms(y * silu(z), norm)`` over all of ``d_inner`` and
  ``out_proj``.
* ``attention`` mixer: q/k/v projections, NO positions
  (``position_embedding_type`` ``nope``), scores times
  ``attention_multiplier``, causal softmax, ``o_proj``. PLAIN attention:
  one head's whole ``[S, S]`` scores at a time, the heads one after
  another under ``jax.checkpoint``.

The loss is on a block of rows, each row's mean cross-entropy over the
tokens its mask keeps, averaged over the block's rows.

**What is compiled, and what is held.** A run of the benchmark has 360 s
and this float32 program compiles for most of a minute, so the layers of
one kind that follow one another (published 0-4 and 6-9: the same
program on other weights) are ONE ``lax.scan`` over their leaves stacked
by name: the compiled loss holds three blocks, not ten. A stack is a copy
of its layers' weights (1.5 GB for five Mamba-2 layers) and its gradient
comes back as a stack too, beside the parameters, their gradient and the
seeded copy the driver keeps (9.3 GB of a 16.9 GB chip), so each run of
layers is rematerialised as a whole (``jax.checkpoint`` round stack and
scan: only one run's stack and its gradient are alive at a time), every
block inside it again, and the recurrence a segment of ``SEGMENT`` tokens
at a time (8,192 states of all 64 heads would be 17 GB; 64 segment
boundaries are 134 MB).

**Modes** (``common.MODES``): in ``bf16`` and ``fp8`` the recurrence's
three operands ``Δ x``, ``B`` and ``C`` are rounded as a product's
operands are, the decays and the state stay float32 as the configuration
states. A mode with ``+scan`` appended (``f32+scan``, ``bf16+scan``)
rounds the decays and the carried state to bfloat16 as well: the control
for those two. In the low-precision modes a block's feed-forward is
walked ``MLP_ROWS`` tokens at a time (``_block`` says why: the chip's
memory; float32 takes the row whole).

Departures from the published model, all in the configuration's
``assumed``: the seeded draws of ``A_log`` and ``dt_bias``, the leaf
names from memory.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common

_P = "model."
SEGMENT = 64  # tokens of the recurrence rematerialised at a time
MLP_ROWS = 4096  # tokens of a feed-forward at a time in the witness's modes


def _numbered(model):
    """``(published number, type)`` of each layer held."""
    first = model["layers_first"]
    return [(first + i, kind) for i, kind in enumerate(model["layer_types"])]


def _widths(model):
    inner = model["mamba_expand"] * model["hidden_size"]
    conv = inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    return inner, conv


def _block_spec(model, i: int, kind: str):
    h, width = model["hidden_size"], model["shared_intermediate_size"]
    p = f"{_P}layers.{i}."
    spec = [(p + "input_layernorm.weight", (h,), "const", 1.0)]
    if kind == "mamba":
        inner, conv = _widths(model)
        heads, taps = model["mamba_n_heads"], model["mamba_d_conv"]
        m = p + "mamba."
        spec += [
            (m + "in_proj.weight", (inner + conv + heads, h), "normal", 0.02),
            # torch's Conv1d default, kernel and bias alike
            (m + "conv1d.weight", (conv, 1, taps), "uniform",
             1.0 / math.sqrt(taps)),
            (m + "conv1d.bias", (conv,), "uniform", 1.0 / math.sqrt(taps)),
            # Δ = softplus(dt - 4.6) is 0.004-0.025 and |A| = exp(A_log)
            # 1/16 .. 16: a head forgets in three tokens or in three
            # thousand (the configuration's ``assumed`` says why these)
            (m + "dt_bias", (heads,), "const", -4.6),
            (m + "A_log", (heads,), "uniform", math.log(16.0)),
            (m + "D", (heads,), "const", 1.0),
            (m + "norm.weight", (inner,), "const", 1.0),
            (m + "out_proj.weight", (h, inner), "normal", 0.02),
        ]
    else:
        heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
        d = model["head_dim"]
        a = p + "self_attn."
        spec += [(a + "q_proj.weight", (heads * d, h), "normal", 0.02),
                 (a + "k_proj.weight", (kv * d, h), "normal", 0.02),
                 (a + "v_proj.weight", (kv * d, h), "normal", 0.02),
                 (a + "o_proj.weight", (h, heads * d), "normal", 0.02)]
    f = p + "shared_mlp."
    return spec + [
        (p + "post_attention_layernorm.weight", (h,), "const", 1.0),
        (f + "input_linear.weight", (2 * width, h), "normal", 0.02),
        (f + "output_linear.weight", (h, width), "normal", 0.02),
    ]


def weight_spec(model):
    """Every leaf under its checkpoint name: matrices N(0, 0.02), norms'
    weights and ``D`` 1, the convolution U(+-1/sqrt(taps)), ``dt_bias``
    -4.6, ``A_log`` U(+-ln 16)."""
    spec = [(_P + "embed_tokens.weight",
             (model["vocab_size"], model["hidden_size"]), "normal", 0.02)]
    for i, kind in _numbered(model):
        spec += _block_spec(model, i, kind)
    spec.append((_P + "norm.weight", (model["hidden_size"],), "const", 1.0))
    return spec


def trainable(model):
    """Every leaf: the family has no buffer."""
    return [name for name, *_ in weight_spec(model)]


def _rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _linear(x, weight, mode):
    """torch ``nn.Linear`` without bias: ``weight`` is (out, in)."""
    return common.matmul(x, weight.T, mode)


def recurrence(x, dt, a, b, c, d, mode: str = "f32", low_scan: bool = False):
    """``y`` ``[S, H x P]`` of one row: ``x`` ``[S, H x P]`` (``H`` heads of
    ``P`` side by side, as the convolution leaves them), ``dt`` ``[S, H]``
    (after the softplus), ``a``, ``d`` ``[H]``, ``b``, ``c`` ``[S, N]``. One
    token at a time; ``SEGMENT`` tokens are rematerialised at a time on
    the way back. ``low_scan`` rounds each decay and the carried state to
    bfloat16 (the control for the float32 the configuration states).

    Whole rows stay ``[S, H x P]`` and only a token's slice is cut into
    heads: an array whose last axis is a head of 64 is padded to the
    chip's 128 lanes and takes twice its bytes."""
    length, heads = dt.shape
    width = x.shape[1] // heads
    pad = -length % SEGMENT
    low = (lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)) \
        if low_scan else (lambda v: v)
    per_head = lambda v: jnp.repeat(v, width, axis=-1)  # noqa: E731
    xd = common.operand(per_head(dt) * x, mode).astype(jnp.float32)
    b, c = (common.operand(v, mode).astype(jnp.float32) for v in (b, c))
    decay = low(jnp.exp(dt * a))

    def token(h, at):
        decay_t, xd_t, b_t, c_t = at
        h = low(decay_t[:, None, None] * h
                + xd_t.reshape(heads, width, 1) * b_t)
        return h, jnp.sum(h * c_t, axis=-1).reshape(-1)

    @jax.checkpoint
    def segment(h, tokens):
        return lax.scan(token, h, tokens)

    # padded tokens decay by 1 and add nothing: the state stays
    inputs = tuple(
        jnp.pad(v, ((0, pad), (0, 0)), constant_values=fill).reshape(
            -1, SEGMENT, v.shape[1])
        for v, fill in ((decay, 1.0), (xd, 0.0), (b, 0.0), (c, 0.0)))
    h0 = jnp.zeros((heads, width, b.shape[-1]), jnp.float32)
    _, y = lax.scan(segment, h0, inputs)
    return y.reshape(-1, x.shape[1])[:length] + per_head(d) * x


def _mamba(model, w, m, u, mode, low_scan):
    inner, conv = _widths(model)
    state, taps, length = (model["mamba_d_state"], model["mamba_d_conv"],
                           u.shape[0])
    z, xbc, dt = jnp.split(_linear(u, w[m + "in_proj.weight"], mode),
                           [inner, inner + conv], axis=-1)
    padded = jnp.pad(common.stored(xbc, mode), ((taps - 1, 0), (0, 0)))
    kernel = w[m + "conv1d.weight"][:, 0, :]  # [channels, taps]
    # torch's conv1d is a cross-correlation: tap k weighs the input
    # taps-1-k steps back
    xbc = common.stored(jax.nn.silu(
        sum(padded[k:k + length] * kernel[:, k] for k in range(taps))
        + w[m + "conv1d.bias"]), mode)
    x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    y = common.stored(recurrence(
        x, jax.nn.softplus(dt + w[m + "dt_bias"]), -jnp.exp(w[m + "A_log"]),
        b, c, w[m + "D"], mode, low_scan), mode)
    gated = y * jax.nn.silu(common.stored(z, mode))
    return _linear(
        common.stored(_rms(gated, w[m + "norm.weight"],
                           model["rms_norm_eps"]), mode),
        w[m + "out_proj.weight"], mode)


def _attention(model, w, a, x, mode):
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    length = x.shape[0]
    # the three matrices side by side in ONE product (a float32 HIGHEST
    # product costs seconds to compile; the sums are the same)
    sizes = [heads * d, kv * d, kv * d]
    qkv = common.stored(_linear(x, jnp.concatenate(
        [w[f"{a}{which}_proj.weight"] for which in "qkv"]), mode), mode)
    q, k, v = (part.reshape(length, n, d) for part, n in zip(
        jnp.split(qkv, [sizes[0], sizes[0] + sizes[1]], axis=-1),
        (heads, kv, kv)))
    causal = jnp.tril(jnp.ones((length, length), bool))

    @jax.checkpoint
    def one_head(q_h, k_h, v_h):
        scores = common.matmul(q_h, k_h.T, mode) \
            * model["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return common.matmul(probs, v_h, mode)

    group = heads // kv
    out = lax.map(
        lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group]),
        jnp.arange(heads))  # [heads, S, D]
    out = common.stored(out, mode).transpose(1, 0, 2).reshape(length,
                                                              heads * d)
    return _linear(out, w[a + "o_proj.weight"], mode)


def _mlp(w, f, x, mode):
    gate, up = jnp.split(_linear(x, w[f + "input_linear.weight"], mode), 2,
                         axis=-1)
    return _linear(common.stored(jax.nn.silu(gate) * up, mode),
                   w[f + "output_linear.weight"], mode)


def _block(model, kind, w, x, mode, low_scan=False):
    """One block whose leaves ``w`` holds under bare names (no
    ``model.layers.N.``). Its two halves are rematerialised one at a
    time on the way back: what the mixer keeps for its gradient and what
    the feed-forward keeps never lie on the chip together."""
    eps, scale = model["rms_norm_eps"], model["residual_multiplier"]

    @jax.checkpoint
    def mixer_half(x, w):
        normed = common.stored(_rms(x, w["input_layernorm.weight"], eps),
                               mode)
        if kind == "mamba":
            mixed = _mamba(model, w, "mamba.", normed, mode, low_scan)
        else:
            mixed = _attention(model, w, "self_attn.", normed, mode)
        return common.stored(x + scale * common.stored(mixed, mode), mode)

    @jax.checkpoint
    def mlp_rows(x, w):
        normed = common.stored(
            _rms(x, w["post_attention_layernorm.weight"], eps), mode)
        return common.stored(
            x + scale * common.stored(_mlp(w, "shared_mlp.", normed, mode),
                                      mode), mode)

    def mlp_half(x, w):
        # a token's feed-forward reads no other token. In the
        # low-precision modes the chip's compiler lays this program out
        # 2.5 GB larger than in float32 (7.77 GB of temporaries beside
        # 9.27 GB of weights, parameters and gradients: over the chip),
        # and MLP_ROWS tokens at a time it fits (6.7 GB); in float32 the
        # pieces make it larger (5.2 -> 7.3 GB), so the reference proper
        # takes the row whole. The same sums a token either way; a
        # weight's gradient adds up over the pieces.
        pieces = x.shape[0] // MLP_ROWS
        if mode == "f32" or pieces < 2 or x.shape[0] % MLP_ROWS:
            return mlp_rows(x, w)
        return lax.map(lambda rows: mlp_rows(rows, w),
                       x.reshape(pieces, MLP_ROWS, -1)).reshape(x.shape)

    return mlp_half(mixer_half(x, w), w)


def _runs(model):
    """The held layers as runs of one kind that follow one another:
    ``[(kind, [published numbers]), ...]``."""
    runs = []
    for i, kind in _numbered(model):
        if runs and runs[-1][0] == kind:
            runs[-1][1].append(i)
        else:
            runs.append((kind, [i]))
    return runs


def scan_blocks(block, x, leaves):
    """``x`` through ``block(x, {name: leaf})`` once for every layer of
    ``leaves`` (``{name: [one array a layer]}``), as ONE loop whose body
    is compiled once (``block`` rematerialises itself).

    A ``lax.scan`` over the leaves stacked by name is the usual way, and
    its stack is a copy of the layers' weights that lives beside them
    from the forward pass to the end of the backward pass, and its
    gradient another (the chip's compiler held four and a half times the
    run's weights that way: 8.0 GB for five Mamba-2 layers). So the loop
    counts layers and PICKS the layer's leaves by its number
    (``lax.switch``: a copy of one layer's at a time), and the rule for
    the way back is spelled out (``jax.custom_vjp``) and does only what
    autodiff would: the forward pass keeps each block's input, the
    backward pass walks the layers in reverse, each block differentiated
    by ``jax.vjp`` of the same ``block``; the layers' gradients leave the
    loop as one stack, which is cut back into leaves. The benchmark's
    tests hold it to the plain loop over layers."""
    count = len(next(iter(leaves.values())))
    numbers = jnp.arange(count)

    def layer(i, leaves):
        return lax.switch(i, [
            lambda k=k: {n: v[k] for n, v in leaves.items()}
            for k in range(count)])

    @jax.custom_vjp
    def run(x, leaves):
        return lax.fori_loop(
            0, count, lambda i, x: block(x, layer(i, leaves)), x)

    def run_fwd(x, leaves):
        out, inputs = lax.scan(
            lambda x, i: (block(x, layer(i, leaves)), x), x, numbers)
        return out, (inputs, leaves)

    def run_bwd(kept, d_out):
        inputs, leaves = kept

        def back(d_x, at):
            i, x_in = at
            return jax.vjp(block, x_in, layer(i, leaves))[1](d_x)

        d_x, d_stacked = lax.scan(back, d_out, (numbers, inputs),
                                  reverse=True)
        return d_x, {n: list(d_stacked[n]) for n in leaves}

    run.defvjp(run_fwd, run_bwd)
    return run(x, leaves)


def _run(model, w, kind, numbers, x, mode, low_scan):
    """``x`` through the blocks ``numbers``, all of ``kind``."""
    first = f"{_P}layers.{numbers[0]}."
    names = [name[len(first):]
             for name, *_ in _block_spec(model, numbers[0], kind)]

    def block(x, here):
        return _block(model, kind, here, x, mode, low_scan)

    if len(numbers) == 1:
        # nothing to share: no copy of its leaves for a stack of one
        return block(x, {n: w[first + n] for n in names})
    return scan_blocks(block, x, {
        n: [w[f"{_P}layers.{i}.{n}"] for i in numbers] for n in names})


def forward(model, w, tokens, mode: str = "f32"):
    """Float32 logits ``[S, vocabulary held]`` of ONE row of ids."""
    mode, _, variant = mode.partition("+")
    embed = w[_P + "embed_tokens.weight"]
    x = common.stored(
        common.stored(embed[tokens], mode) * model["embedding_multiplier"],
        mode)
    for kind, numbers in _runs(model):
        x = _run(model, w, kind, numbers, x, mode, variant == "scan")
    x = common.stored(
        common.stored(_rms(x, w[_P + "norm.weight"], model["rms_norm_eps"]),
                      mode) / model["logits_scaling"], mode)
    return common.matmul(x, embed.T, mode)


def loss(model, w, batch, mode: str = "f32"):
    """Mean over the block's rows of the row's mean cross-entropy over
    its kept tokens."""
    rows = []
    for tokens, labels, mask in zip(batch["tokens"], batch["labels"],
                                    batch["mask"]):
        logits = forward(model, w, tokens, mode)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        kept = mask.astype(jnp.float32)
        rows.append(jnp.sum(nll * kept) / jnp.sum(kept))
    return jnp.mean(jnp.stack(rows))


def example_input(model):
    """One row of ids, for the shapes of the program's ``model.init``."""
    return jnp.zeros((1, model["sequence_length"]), jnp.int32)


def scan_flops_per_token(model) -> float:
    """Multiply-adds x 2 of the state-space scan for one token of one
    layer, in the chunked form at the published chunk ``Q``: ``C . B``
    and the decayed product with ``Δ x`` over the causal half of a chunk
    (``Q / 2`` keys a token, as the attention's count takes half the
    row), a chunk's own state (``P x N`` a head) and the read of the
    entering state (``N x P`` a head). The token-by-token form needs
    about the same (three operations an entry of ``h`` and two to read
    it)."""
    heads, p, n = (model["mamba_n_heads"], model["mamba_d_head"],
                   model["mamba_d_state"])
    half = model["mamba_chunk_size"] / 2.0
    return 2.0 * half * n + 2.0 * half * p * heads + 4.0 * p * n * heads


def forward_flops_per_token(model) -> float:
    """Multiply-adds x 2 of one token's forward pass, the MXU's share:
    the projections, the scan's products (``scan_flops_per_token``), the
    attention's two products over the causal half of the row, the
    feed-forward, the head."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    inner, conv = _widths(model)
    total = 0.0
    for _, kind in _numbered(model):
        if kind == "mamba":
            total += 2.0 * h * (inner + conv + model["mamba_n_heads"]) \
                + 2.0 * inner * h + scan_flops_per_token(model)
        else:
            total += 2.0 * h * (2 * heads * d + 2 * kv * d)
            total += 4.0 * (model["sequence_length"] / 2.0) * heads * d
        total += 6.0 * h * model["shared_intermediate_size"]
    return total + 2.0 * h * model["vocab_size"]


def train_flops(model, rows: int) -> float:
    """Operations of one step of ``rows`` rows: forward and backward
    (x 3), no recomputation."""
    return float(rows) * model["sequence_length"] * 3.0 \
        * forward_flops_per_token(model)
