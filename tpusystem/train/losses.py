"""Loss criteria as registered entities.

The reference registers ``torch.nn.CrossEntropyLoss`` so the criterion
participates in experiment identity (``examples/tinysys/main.py:27-32``).
These are their pure-functional equivalents: hashable hyperparameter
recipes whose ``__call__`` is jit-traceable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpusystem.registry import register


@register
class CrossEntropyLoss:
    """Softmax cross-entropy over integer labels, with optional smoothing."""

    def __init__(self, label_smoothing: float = 0.0):
        self.label_smoothing = label_smoothing

    def __call__(self, logits, targets):
        if self.label_smoothing:
            classes = logits.shape[-1]
            onehot = optax.smooth_labels(
                jnp.eye(classes, dtype=logits.dtype)[targets], self.label_smoothing)
            losses = optax.softmax_cross_entropy(logits, onehot)
        else:
            losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        return jnp.mean(losses)


@register
class MSELoss:
    def __init__(self):
        ...

    def __call__(self, predictions, targets):
        return jnp.mean((predictions - targets) ** 2)


@register
class BCEWithLogitsLoss:
    """Binary cross-entropy on raw logits — the recommender workload's
    click objective (:class:`tpusystem.models.DLRM` emits one logit per
    example). Per-example mean, so gradient accumulation is exact
    without a ``weight`` seam; targets are 0/1 floats (or bools)."""

    def __init__(self):
        ...

    def __call__(self, logits, targets):
        losses = optax.sigmoid_binary_cross_entropy(
            logits.astype(jnp.float32),
            jnp.asarray(targets, jnp.float32))
        return jnp.mean(losses)


@register
class WithAuxLoss:
    """Wrap a criterion for models whose outputs are ``(predictions, aux)``
    — e.g. MoE models returning router load-balance losses
    (:mod:`tpusystem.ops.moe`). The aux term (already scaled by the model's
    coefficients) adds to the base loss; ``coef`` rescales it globally.

    Under gradient accumulation the aux term is approximate either way:
    load balance is nonlinear in batch composition, so per-microbatch aux
    values cannot reproduce the full-batch value exactly. The inner
    criterion's ``weight`` (unmasked-token count) is forwarded because
    routing pressure is per token — the base-loss term stays exact and the
    aux term is token-weighted rather than microbatch-weighted."""

    def __init__(self, criterion, coef: float = 1.0):
        self.criterion = criterion
        self.coef = coef
        if hasattr(criterion, 'weight'):  # forward the accumulation weight
            self.weight = criterion.weight

    def __call__(self, outputs, targets):
        predictions, aux = outputs
        return self.criterion(predictions, targets) + self.coef * aux


@register
class ChunkedNextTokenLoss:
    """Causal LM loss fused with the LM head, chunked over rows.

    Consumes ``(features, table)`` from a model built with
    ``return_features=True`` (:class:`tpusystem.models.GPT2` /
    :class:`~tpusystem.models.Llama`) instead of materialized logits. The
    ``[batch*seq, vocab]`` float32 logits tensor — several GB at LM scale,
    and the usual OOM driver — is never formed: rows are processed in
    ``chunks`` sequential slices, each computing its logits tile at MXU
    rate (bf16 operands, f32 accumulation), reducing to its loss
    contribution, and being rematerialized in the backward pass
    (``jax.checkpoint``), so peak memory drops by ~``chunks``x while FLOPs
    stay within 2x on the head only.

    Same semantics as :class:`NextTokenLoss`: logits[:, :-1] vs
    tokens[:, 1:], pad ids < 0 masked out, optional z-loss. ``table`` may
    be ``[vocab, dim]`` (tied embedding) or ``[dim, vocab]`` (untied head
    kernel).
    """

    def __init__(self, chunks: int = 16, z_loss: float = 0.0,
                 tied: bool | None = None):
        self.chunks = chunks
        self.z_loss = z_loss
        # table orientation; None infers from shapes and refuses the
        # ambiguous square case (vocab == dim) — pass explicitly there
        self.tied = tied

    def __call__(self, outputs, tokens):
        with jax.named_scope('loss_head'):
            return self._chunked(outputs, tokens)

    def _chunked(self, outputs, tokens):
        from tpusystem.ops.precision import head_logits

        features, table = outputs
        dim = features.shape[-1]
        rows = features[:, :-1].reshape(-1, dim)
        labels = tokens[:, 1:].reshape(-1)
        padding = -rows.shape[0] % self.chunks
        if padding:
            rows = jnp.pad(rows, ((0, padding), (0, 0)))
            labels = jnp.pad(labels, (0, padding), constant_values=-1)
        rows = rows.reshape(self.chunks, -1, dim)
        labels = labels.reshape(self.chunks, -1)

        @jax.checkpoint
        def chunk(rows_chunk, labels_chunk):
            logits = head_logits(rows_chunk, table, tied=self.tied)
            logsumexp = jax.nn.logsumexp(logits, axis=-1)
            mask = (labels_chunk >= 0).astype(jnp.float32)
            safe = jnp.maximum(labels_chunk, 0)
            true_logit = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
            return (jnp.sum((logsumexp - true_logit) * mask),
                    jnp.sum(jnp.square(logsumexp) * mask), jnp.sum(mask))

        losses, z_terms, counts = jax.lax.map(
            lambda slices: chunk(*slices), (rows, labels))
        total = jnp.maximum(jnp.sum(counts), 1.0)
        loss = jnp.sum(losses) / total
        if self.z_loss:
            loss = loss + self.z_loss * jnp.sum(z_terms) / total
        return loss

    def weight(self, tokens):
        """Unmasked-token count — the accumulation weight that makes
        microbatched means equal the full-batch mean under padding (see
        ``build_train_step(accumulate=...)``)."""
        return jnp.sum((tokens[:, 1:] >= 0).astype(jnp.float32))


@register
class NextTokenLoss:
    """Causal LM loss: cross-entropy of logits[:, :-1] vs tokens[:, 1:],
    with padding mask support (pad id < 0 excluded)."""

    def __init__(self, z_loss: float = 0.0):
        self.z_loss = z_loss

    def __call__(self, logits, tokens):
        shifted_logits = logits[:, :-1]
        shifted_targets = tokens[:, 1:]
        mask = (shifted_targets >= 0).astype(jnp.float32)
        safe_targets = jnp.maximum(shifted_targets, 0)
        losses = optax.softmax_cross_entropy_with_integer_labels(
            shifted_logits.astype(jnp.float32), safe_targets)
        loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        if self.z_loss:
            logsumexp = jax.nn.logsumexp(shifted_logits.astype(jnp.float32), axis=-1)
            loss = loss + self.z_loss * jnp.sum((logsumexp ** 2) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return loss

    def weight(self, tokens):
        """Unmasked-token count — the accumulation weight that makes
        microbatched means equal the full-batch mean under padding (see
        ``build_train_step(accumulate=...)``)."""
        return jnp.sum((tokens[:, 1:] >= 0).astype(jnp.float32))
