"""Classification loss.

TPU-native replacement for ``nn.CrossEntropyLoss()`` (reference
imagenet_ddp.py:131, default mean reduction): softmax cross-entropy with
integer labels, computed in float32 regardless of the compute dtype so that
the bf16 policy (the Apex-AMP replacement) never loses precision in the
log-sum-exp — the same role Apex's fp32 loss kept in its O1/O2 modes.

Label smoothing (``--label-smoothing``, a dptpu extension) is part of the
large-batch recipe every ImageNet-in-minutes paper ships (e.g.
arXiv:1711.04325 trains with smoothing 0.1): targets become
``(1-s)·onehot + s/K``. Training-path only — validation loss stays the
reference's unsmoothed CE so accuracy/loss numbers compare across recipes.
"""

import jax
import jax.numpy as jnp
import optax


def cross_entropy_loss(logits, labels, label_smoothing: float = 0.0):
    """Mean softmax cross-entropy, optionally label-smoothed.

    Args:
      logits: ``[batch, num_classes]`` array (any float dtype; upcast to f32).
      labels: ``[batch]`` integer class ids.
      label_smoothing: static smoothing mass ``s`` in [0, 1); 0 is the
        reference's exact hard-target CE.

    Returns:
      Scalar f32 mean loss (``nn.CrossEntropyLoss`` default reduction).
    """
    logits = logits.astype(jnp.float32)
    if label_smoothing:
        targets = optax.smooth_labels(
            jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32),
            label_smoothing,
        )
        return optax.softmax_cross_entropy(logits, targets).mean()
    per_example = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return per_example.mean()


TOKEN_LOSS_BLOCK = 2048


def token_cross_entropy_sums(hidden, embedding, labels, mask,
                             block: int = TOKEN_LOSS_BLOCK):
    """Per-token softmax cross-entropy against a tied head, in blocks.

    A language model's head multiplies ``[tokens, hidden]`` states by the
    ``[vocab, hidden]`` embedding: at 16,384 tokens over 16,384 rows of
    the vocabulary that is 1 GB of float32 logits, and as much again for
    their gradient. Here the logits exist ``block`` tokens at a time: a
    ``lax.scan`` over row blocks computes ``logsumexp - picked`` in
    float32 and the block is rematerialised on the way back
    (``jax.checkpoint``), so neither the logits of the whole batch nor
    their gradient are ever alive at once.

    Args:
      hidden: ``[tokens, hidden]`` final states (any float dtype; the
        product accumulates in float32).
      embedding: ``[vocab, hidden]``, the tied embedding (the rows held).
      labels: ``[tokens]`` next-token ids below ``vocab``.
      mask: ``[tokens]`` bool or float, 1 = the token counts.

    Returns ``{"loss_sum", "count", "correct1", "correct5"}``: float32
    sums over the kept tokens. A token is in the top k when fewer than k
    logits are strictly larger than its label's (``lax.top_k`` over the
    vocabulary for every token would sort what only has to be counted).
    """
    tokens = hidden.shape[0]
    block = min(block, tokens)
    padded = -(-tokens // block) * block
    mask = mask.astype(jnp.float32)
    if padded != tokens:
        pad = padded - tokens
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels, mask = jnp.pad(labels, (0, pad)), jnp.pad(mask, (0, pad))
    weight = embedding.astype(hidden.dtype)

    @jax.checkpoint
    def block_sums(h, y, m):
        logits = jnp.einsum("th,vh->tv", h, weight,
                            preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        above = jnp.sum(logits > picked[:, None], axis=-1)
        return jnp.stack([jnp.sum(nll * m), jnp.sum(m),
                          jnp.sum((above < 1) * m),
                          jnp.sum((above < 5) * m)])

    def step(total, xs):
        return total + block_sums(*xs), None

    n = padded // block
    total, _ = jax.lax.scan(
        step, jnp.zeros((4,), jnp.float32),
        (hidden.reshape(n, block, -1), labels.reshape(n, block),
         mask.reshape(n, block)))
    return {"loss_sum": total[0], "count": total[1],
            "correct1": total[2], "correct5": total[3]}
