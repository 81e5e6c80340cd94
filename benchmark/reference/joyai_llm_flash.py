"""Plain reference for the ``joyai_llm_flash`` family (JoyAI-LLM-Flash,
https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json;
the keys and the layers are the DeepSeek-V3 family's).

The published equations in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, the checkpoint's leaf names, nothing of ``dptpu``:

* block: ``x = x + mla(rms(x, input_layernorm))``;
  ``x = x + ffn(rms(x, post_attention_layernorm))``; after the last layer
  ``rms(x, model.norm)``, then ``x @ lm_head.T`` (untied).
* multi-head latent attention: ``c_q = rms(q_a_proj x)``; ``q_b_proj
  c_q`` per head ``[q_nope | q_rope]``; ``[c_kv | k_rope] =
  kv_a_proj_with_mqa x``; ``kv_b_proj rms(c_kv)`` per head
  ``[k_nope | v]``; rotary positions on every head's ``q_rope`` and on
  the one ``k_rope`` all heads share, the pairs INTERLEAVED (``(x0, x1),
  (x2, x3), ...``) and rotated where they lie; ``k = [k_nope | k_rope]``,
  scores ``q.k / sqrt(qk head)``, causal softmax, ``P v`` at the values'
  head size, ``o_proj``. PLAIN attention: one head's whole ``[S, S]``
  scores at a time, the heads one after another under ``jax.checkpoint``.
* feed-forward: SwiGLU ``down(silu(gate x) * up x)`` at
  ``intermediate_size`` in the first ``first_k_dense_replace`` layers; in
  the others ``shared_experts(x) + routed(x)``: the router ``s =
  sigmoid(gate x)`` (float32 in every mode), the top k of ``s +
  e_score_correction_bias``, weights ``s`` at those k over their sum +
  1e-20, times ``routed_scaling_factor``, and PLAINLY every held expert
  over every token, weighted by what the router gave it (0 where it was
  not chosen). The share: only the experts ``experts_first .. +
  experts_held`` exist here; what the others would add is left out.
* multi-token prediction (the checkpoint's layer ``mtp_layer``): from the
  last held layer's output ``h`` (before ``model.norm``) and the
  embedding of the NEXT token, ``eh_proj [rms(emb, enorm) | rms(h,
  hnorm)]``, one block with experts, ``rms(., shared_head.norm)``, the
  same ``lm_head``; its target is the token after next.

The loss is on a block of rows: each row's ``main + mtp_loss_weight x
mtp``, averaged over the block's rows. ``main`` is the row's mean
cross-entropy over the tokens its mask keeps. In a row of the feed the
next token of position ``i`` is ``labels[i]`` and the one after it
``labels[i + 1]``: ``mtp`` sums the second head's cross-entropy against
``labels[i + 1]`` over the positions whose NEXT position the mask keeps,
over the same count as ``main`` (the last position has no target).

**What is compiled.** The float32 ``Precision.HIGHEST`` program of one
row compiles for over a minute on the chip, and a run of the benchmark
has 360 s (``lib/drive.py``), so the equations above are written the way
that gives the compiler the fewest distinct pieces, without changing a
sum: matrices that multiply the same input lie side by side in ONE
product (``q_a_proj`` beside ``kv_a_proj_with_mqa``, ``gate_proj`` beside
``up_proj``, the two heads' inputs one under the other before
``lm_head``); a head's scores are the one product ``[q_nope | q_rope] .
[k_nope | k_rope]``; the expert layers AND the module's block are one
``lax.scan`` over their leaves stacked by name (one compiled block, a
third of the cache entry); the held experts are a scan too. 83 products
in the lowered program where the layer-by-layer form had 176, 0.68 of
its compile (PERF.md, PR 36). Memory sets the other limit: beside the
parameters, their gradient and the seeded copy the driver keeps, the
program's temporaries have to stay near 10 GB of the chip's 16.9, hence
the three levels of ``jax.checkpoint`` (a block, a head, an expert) and
no stack for a kind of block held once.

Departures from the published model, all in the configuration's
``assumed``: the weight of the second loss, the order of the two halves
under ``eh_proj``, the bias a buffer that nothing trains (``trainable``
says how the driver is handed it), no auxiliary loss, the leaf names from
memory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common

_P = "model."
_ROUTE_NORM_EPS = 1e-20


def _layers(model):
    first = model["layers_first"]
    return range(first, first + model["layers_held"])


def _held(model):
    first = model["experts_first"]
    return range(first, first + model["experts_held"])


def _is_dense(model, i: int) -> bool:
    return i < model["first_k_dense_replace"]


def _block_spec(model, i: int, dense: bool):
    h = model["hidden_size"]
    heads = model["num_attention_heads"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    p = f"{_P}layers.{i}."
    a = p + "self_attn."
    spec = [
        (p + "input_layernorm.weight", (h,), "const", 1.0),
        (a + "q_a_proj.weight", (q_rank, h), "normal", 0.02),
        (a + "q_a_layernorm.weight", (q_rank,), "const", 1.0),
        (a + "q_b_proj.weight", (heads * (nope + rope), q_rank), "normal",
         0.02),
        (a + "kv_a_proj_with_mqa.weight", (kv_rank + rope, h), "normal",
         0.02),
        (a + "kv_a_layernorm.weight", (kv_rank,), "const", 1.0),
        (a + "kv_b_proj.weight", (heads * (nope + v), kv_rank), "normal",
         0.02),
        (a + "o_proj.weight", (h, heads * v), "normal", 0.02),
        (p + "post_attention_layernorm.weight", (h,), "const", 1.0),
    ]
    f = p + "mlp."

    def swiglu(prefix, width):
        return [(prefix + "gate_proj.weight", (width, h), "normal", 0.02),
                (prefix + "up_proj.weight", (width, h), "normal", 0.02),
                (prefix + "down_proj.weight", (h, width), "normal", 0.02)]

    if dense:
        return spec + swiglu(f, model["intermediate_size"])
    width, routed = model["moe_intermediate_size"], model["router_experts"]
    spec += [(f + "gate.weight", (routed, h), "normal", 0.02),
             (f + "gate.e_score_correction_bias", (routed,), "normal",
              0.003)]
    for e in _held(model):
        spec += swiglu(f"{f}experts.{e}.", width)
    return spec + swiglu(f + "shared_experts.",
                         width * model["n_shared_experts"])


def weight_spec(model):
    """Every leaf under its checkpoint name: matrices N(0, 0.02), norms'
    weights 1, ``e_score_correction_bias`` N(0, 0.003): small beside the
    scores' spread, so that it decides a few tokens' experts and not the
    load."""
    h = model["hidden_size"]
    spec = [(_P + "embed_tokens.weight", (model["vocab_size"], h),
             "normal", 0.02)]
    for i in _layers(model):
        spec += _block_spec(model, i, _is_dense(model, i))
    spec += [(_P + "norm.weight", (h,), "const", 1.0),
             ("lm_head.weight", (model["vocab_size"], h), "normal", 0.02)]
    if model["num_nextn_predict_layers"]:
        p = f"{_P}layers.{model['mtp_layer']}."
        spec += [(p + "enorm.weight", (h,), "const", 1.0),
                 (p + "hnorm.weight", (h,), "const", 1.0),
                 (p + "eh_proj.weight", (h, 2 * h), "normal", 0.02),
                 *_block_spec(model, model["mtp_layer"], dense=False),
                 (p + "shared_head.norm.weight", (h,), "const", 1.0)]
    return spec


def trainable(model):
    """Every leaf, ``e_score_correction_bias`` among them, although
    nothing trains it: the training driver closes over what is not listed
    here, so a seeded buffer would be a constant of the compiled loss and
    every seed another program. Listed, it is an argument. It enters only
    the CHOICE of experts, which has no derivative: its gradient is
    identically zero, AdamW's step on a zero gradient is zero and decays
    no vector, so it stays where it was seeded, bit for bit, and the
    program keeps it as a buffer (``batch_stats``), off the compared
    trees on both sides."""
    return [name for name, *_ in weight_spec(model)]


def _rms(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * weight


def _linear(x, weight, mode):
    """torch ``nn.Linear`` without bias: ``weight`` is (out, in)."""
    return common.matmul(x, weight.T, mode)


def rotary_interleaved(x, theta):
    """``x`` is ``[S, ..., D]``: the pairs ``(x[2j], x[2j + 1])`` rotated
    by ``position x theta^(-2j / D)``, each where it lies."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    angles = angles.reshape(x.shape[0], *([1] * (x.ndim - 2)), d // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(model, w, a, x, mode):
    """``a`` prefixes the attention's leaves in ``w``. Products that share
    their left operand are written as ONE product over the matrices laid
    side by side (``q_a_proj`` beside ``kv_a_proj_with_mqa``), and a
    head's scores as the one product ``[q_nope | q_rope] . [k_nope |
    k_rope]`` of the published equation: the same sums, fewer products
    for the compiler (module docstring, "What is compiled")."""
    heads = model["num_attention_heads"]
    nope, rope, v_dim = (model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    length = x.shape[0]
    down = _linear(x, jnp.concatenate([w[a + "q_a_proj.weight"],
                                       w[a + "kv_a_proj_with_mqa.weight"]]),
                   mode)
    q_a, kv_a = down[:, :q_rank], down[:, q_rank:]
    c_q = common.stored(_rms(q_a, w[a + "q_a_layernorm.weight"], eps), mode)
    q = _linear(c_q, w[a + "q_b_proj.weight"], mode).reshape(
        length, heads, nope + rope)
    c_kv = common.stored(_rms(kv_a[:, :kv_rank],
                              w[a + "kv_a_layernorm.weight"], eps), mode)
    kv = _linear(c_kv, w[a + "kv_b_proj.weight"], mode).reshape(
        length, heads, nope + v_dim)
    if not model["rope_interleave"]:
        raise NotImplementedError("the reference rotates interleaved pairs")
    q_rope = common.stored(rotary_interleaved(q[..., nope:], theta), mode)
    k_rope = common.stored(rotary_interleaved(kv_a[:, kv_rank:], theta),
                           mode)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((length, length), bool))
    scale = (nope + rope) ** -0.5

    @jax.checkpoint
    def one_head(q_h, kn, v_h):
        # the rotary key is the one all heads share
        k_h = jnp.concatenate([kn, k_rope], axis=-1)
        scores = common.matmul(q_h, k_h.T, mode) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return common.matmul(probs, v_h, mode)

    out = lax.map(lambda h: one_head(q[:, h], k_nope[:, h], v[:, h]),
                  jnp.arange(heads))  # [heads, S, v_dim]
    out = common.stored(out, mode).transpose(1, 0, 2).reshape(
        length, heads * v_dim)
    return _linear(out, w[a + "o_proj.weight"], mode)


def _swiglu(x, w, prefix, mode):
    """``down(silu(gate x) * up x)``; ``gate_proj`` beside ``up_proj`` in
    one product."""
    gate = w[prefix + "gate_proj.weight"]
    both = _linear(x, jnp.concatenate([gate, w[prefix + "up_proj.weight"]]),
                   mode)
    width = gate.shape[0]
    hidden = common.stored(jax.nn.silu(both[:, :width]) * both[:, width:],
                           mode)
    return _linear(hidden, w[prefix + "down_proj.weight"], mode)


def route(model, scores, bias):
    """``(chosen [S, k], weights [S, k])``: the top k of ``scores + bias``
    (one group: nothing limits the choice) weighted by ``scores``
    itself."""
    _, chosen = lax.top_k(scores + bias, model["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if model["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + _ROUTE_NORM_EPS)
    return chosen, weights * model["routed_scaling_factor"]


def routed_experts(model, w, f, x, mode, experts=None):
    """The part of the routed result that ``experts`` (default: the ones
    held) give, each over every token, weighted by the router."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x, w[f + "gate.weight"].T, precision=lax.Precision.HIGHEST))
    chosen, weights = route(model, scores,
                            w[f + "gate.e_score_correction_bias"])
    held = list(_held(model) if experts is None else experts)
    names = ("gate_proj", "up_proj", "down_proj")
    stacked = [jnp.stack([w[f"{f}experts.{e}.{name}.weight"] for e in held])
               for name in names]

    @jax.checkpoint
    def add_expert(out, one):
        e, *matrices = one
        share = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        one_w = {f"{name}.weight": m for name, m in zip(names, matrices)}
        return out + share[:, None] * _swiglu(x, one_w, "", mode), None

    # one expert after another (a scan, so that the compiler sees one
    # expert's program and not one a held expert a layer)
    out, _ = lax.scan(add_expert, jnp.zeros_like(x),
                      (jnp.asarray(held), *stacked))
    return out


def shared_expert(model, w, f, x, mode):
    """What every token takes, on every chip alike."""
    return _swiglu(x, w, f + "shared_experts.", mode)


def _block(model, w, p, dense, x, mode):
    """One block whose leaves ``p`` prefixes in ``w``."""
    eps = model["rms_norm_eps"]
    normed = common.stored(
        _rms(x, w[p + "input_layernorm.weight"], eps), mode)
    x = common.stored(
        x + _attention(model, w, p + "self_attn.", normed, mode), mode)
    normed = common.stored(
        _rms(x, w[p + "post_attention_layernorm.weight"], eps), mode)
    f = p + "mlp."
    if dense:
        ffn = _swiglu(normed, w, f, mode)
    else:
        ffn = common.stored(
            shared_expert(model, w, f, normed, mode)
            + common.stored(routed_experts(model, w, f, normed, mode), mode),
            mode)
    return common.stored(x + ffn, mode)


def _blocks(model, w, numbers, dense, x, mode, merge=None):
    """``x`` through the blocks ``numbers`` (published layer numbers of
    ONE kind, ``dense`` or with experts; the multi-token-prediction
    module's last if it is among them), one after another, each
    rematerialised on the way back; returns every block's output,
    ``[blocks, S, hidden]``. ``merge(x)`` is what the module does to its
    input before its block.

    The blocks are one program applied to other weights, and written as
    that: a ``lax.scan`` over the blocks' leaves stacked by name, the
    module's block among the expert layers behind a ``lax.cond`` on its
    input. So the compiled program holds one block of a kind and not one
    a block held (module docstring, "What is compiled")."""
    numbers = list(numbers)
    first = f"{_P}layers.{numbers[0]}."
    if len(numbers) == 1 and merge is None:
        # nothing to share: no copy of its leaves for a stack of one
        bare = {k[len(first):]: v for k, v in w.items()
                if k.startswith(first)}
        return jax.checkpoint(lambda x, here: _block(
            model, here, "", dense, x, mode))(x, bare)[None]
    names = [name[len(first):]
             for name, *_ in _block_spec(model, numbers[0], dense)]
    stacked = {n: jnp.stack([w[f"{_P}layers.{i}.{n}"] for i in numbers])
               for n in names}
    is_module = jnp.asarray([i == model["mtp_layer"] for i in numbers])

    @jax.checkpoint
    def one(x, block):
        here, module_here = block  # this block's leaves under bare names
        if merge is not None:
            x = lax.cond(module_here, merge, lambda x: x, x)
        x = _block(model, here, "", dense, x, mode)
        return x, x

    return lax.scan(one, x, (stacked, is_module))[1]


def forward(model, w, tokens, following=None, mode: str = "f32"):
    """Float32 logits of ONE row of ids: ``(main [S, vocabulary held],
    second head's or None)``. ``following`` are the ids one position on
    (the row's labels); without them the second head is not computed.
    Every block is rematerialised on the way back (``jax.checkpoint``)."""
    embed = w[_P + "embed_tokens.weight"]
    eps = model["rms_norm_eps"]
    x = common.stored(embed[tokens], mode)
    numbers = list(_layers(model))
    module = following is not None and model["num_nextn_predict_layers"]
    merge = None
    if module:
        numbers.append(model["mtp_layer"])
        p = f"{_P}layers.{model['mtp_layer']}."
        next_embedding = common.stored(
            _rms(common.stored(embed[following], mode),
                 w[p + "enorm.weight"], eps), mode)

        def merge(x):
            merged = jnp.concatenate([
                next_embedding,
                common.stored(_rms(x, w[p + "hnorm.weight"], eps), mode)],
                axis=-1)
            return common.stored(
                _linear(merged, w[p + "eh_proj.weight"], mode), mode)

    outputs = []
    for dense in (True, False):  # the dense layers are the first ones
        run = [i for i in numbers if _is_dense(model, i) == dense]
        if run:
            outputs.append(_blocks(model, w, run, dense, x, mode,
                                   None if dense else merge))
            x = outputs[-1][-1]
    outputs = jnp.concatenate(outputs)
    head = w["lm_head.weight"]
    last = common.stored(
        _rms(outputs[model["layers_held"] - 1], w[_P + "norm.weight"], eps),
        mode)
    if not module:
        return common.matmul(last, head.T, mode), None
    # the same head on both: one product, the module's rows under the main
    last_of_module = common.stored(
        _rms(outputs[-1], w[p + "shared_head.norm.weight"], eps), mode)
    logits = common.matmul(jnp.concatenate([last, last_of_module]), head.T,
                           mode)
    return logits[:len(tokens)], logits[len(tokens):]


def _nll(logits, targets):
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def row_losses(model, w, tokens, labels, mask, mode: str = "f32"):
    """``(main, mtp)`` of one row, each over the row's count of kept
    tokens; ``mtp`` is 0.0 for a model without the module."""
    main_logits, next_logits = forward(model, w, tokens, labels, mode)
    kept = mask.astype(jnp.float32)
    count = jnp.sum(kept)
    main = jnp.sum(_nll(main_logits, labels) * kept) / count
    if next_logits is None:
        return main, jnp.zeros(())
    # position i predicts labels[i + 1], with that position's weight
    mtp = jnp.sum(_nll(next_logits[:-1], labels[1:]) * kept[1:]) / count
    return main, mtp


def loss(model, w, batch, mode: str = "f32"):
    """Mean over the block's rows of ``main + mtp_loss_weight x mtp``."""
    rows = []
    for tokens, labels, mask in zip(batch["tokens"], batch["labels"],
                                    batch["mask"]):
        main, mtp = row_losses(model, w, tokens, labels, mask, mode)
        rows.append(main + model["mtp_loss_weight"] * mtp)
    return jnp.mean(jnp.stack(rows))


def example_input(model):
    """One row of ids, for the shapes of the program's ``model.init``."""
    return jnp.zeros((1, model["sequence_length"]), jnp.int32)


def forward_flops_per_token(model) -> float:
    """Multiply-adds x 2 of one token's forward pass, the MXU's share:
    the latent attention's five projections and its two products over the
    causal half of the row (at the two head sizes), the dense
    feed-forward, the router, the shared expert, the held experts at the
    MEAN load (k x held / routed experts a token a layer: what uniform
    routing gives; ``expert_local_slot_share`` says how far that holds),
    the head, and the multi-token-prediction module: its merge, its
    block, the head's second product."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    q_rank, kv_rank = model["q_lora_rank"], model["kv_lora_rank"]
    length = model["sequence_length"]
    attention = 2.0 * (h * q_rank + q_rank * heads * (nope + rope)
                       + h * (kv_rank + rope)
                       + kv_rank * heads * (nope + v) + heads * v * h) \
        + 2.0 * (length / 2.0) * heads * ((nope + rope) + v)
    mean_experts = model["num_experts_per_tok"] * model["experts_held"] \
        / model["router_experts"]
    sparse = 2.0 * h * model["router_experts"] \
        + 6.0 * h * model["moe_intermediate_size"] \
        * (model["n_shared_experts"] + mean_experts)
    head = 2.0 * h * model["vocab_size"]
    total = head
    for i in _layers(model):
        total += attention + (6.0 * h * model["intermediate_size"]
                              if _is_dense(model, i) else sparse)
    if model["num_nextn_predict_layers"]:
        total += 2.0 * (2 * h) * h + attention + sparse + head
    return total


def train_flops(model, rows: int) -> float:
    """Operations of one step of ``rows`` rows: forward and backward
    (x 3), no recomputation."""
    return float(rows) * model["sequence_length"] * 3.0 \
        * forward_flops_per_token(model)
