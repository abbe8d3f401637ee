"""Optimizers as registered entities.

The reference registers ``torch.optim.Adam`` with ``excluded_args=[0]`` so
the parameter iterator stays out of the identity hash
(``examples/tinysys/main.py:27-32``). The TPU-native design is cleaner:
optimizers are *pure gradient transforms* (optax) that never hold parameter
references, so the wrapper classes below capture exactly their
hyperparameters — their registry hash identifies the optimization recipe and
participates in checkpoint identity.

Each wrapper exposes ``transform()`` returning the underlying
``optax.GradientTransformation``; slot variables live in
``TrainState.opt_state`` and shard with the same policy as the parameters
(ZeRO-style optimizer-state sharding falls out of GSPMD for free).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpusystem.registry import register


def masked_update(transform, grads, opt_state, params, ok, *, scale=None):
    """One optimizer update, suppressed in-graph when ``ok`` is False.

    The ``optax.apply_if_finite`` idea generalized to an arbitrary traced
    health verdict (finiteness AND the guard's spike z-score): the update
    and the new slot variables are computed unconditionally — one fused
    program, no host sync, no control flow — and a per-leaf ``where``
    selects between the advanced and the untouched (params, opt_state).
    A NaN/Inf gradient therefore never reaches the weights *or* the
    optimizer moments, which is what makes a skipped batch free to retry
    or discard (PaLM-style) instead of poisoning every step after it.

    ``scale`` (float32 scalar, typically ``HealthStats.lr_scale``)
    multiplies the updates before application — for optax's SGD/Adam/AdamW
    (where weight decay is folded into the update at the learning rate)
    scaling the update is exactly scaling the learning rate, so a host-side
    backoff needs no recompilation.

    Returns ``(params, opt_state)``.
    """
    updates, new_opt_state = transform.update(grads, opt_state, params)
    if scale is not None:
        updates = jax.tree.map(lambda u: u * scale.astype(u.dtype), updates)
    new_params = optax.apply_updates(params, updates)
    keep = lambda new, old: jnp.where(ok, new, old)
    return (jax.tree.map(keep, new_params, params),
            jax.tree.map(keep, new_opt_state, opt_state))


def _scoped(name: str, inner: optax.GradientTransformation
            ) -> optax.GradientTransformation:
    """``inner`` with its update under ``jax.named_scope(name)``: the same
    state and arithmetic, named in a device trace."""
    def update(updates, state, params=None):
        with jax.named_scope(name):
            return inner.update(updates, state, params)
    return optax.GradientTransformation(inner.init, update)


class Optimizer:
    """Base: a named, hashable recipe producing an optax transform."""

    def transform(self) -> optax.GradientTransformation:
        raise NotImplementedError

    def init(self, params):
        return self.transform().init(params)

    def update(self, grads, opt_state, params=None):
        return self.transform().update(grads, opt_state, params)


@register
class SGD(Optimizer):
    def __init__(self, lr: float = 0.01, momentum: float = 0.0, nesterov: bool = False):
        self.lr, self.momentum, self.nesterov = lr, momentum, nesterov

    def transform(self) -> optax.GradientTransformation:
        return optax.sgd(self.lr, momentum=self.momentum or None, nesterov=self.nesterov)


@register
class Adam(Optimizer):
    def __init__(self, lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

    def transform(self) -> optax.GradientTransformation:
        return optax.adam(self.lr, b1=self.b1, b2=self.b2, eps=self.eps)


@register
class AdamW(Optimizer):
    def __init__(self, lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.01,
                 grad_clip: float = 0.0, warmup_steps: int = 0,
                 decay_steps: int = 0, min_lr_ratio: float = 0.1):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.weight_decay = weight_decay
        self.grad_clip = grad_clip
        self.warmup_steps = warmup_steps
        self.decay_steps = decay_steps
        self.min_lr_ratio = min_lr_ratio

    def schedule(self):
        if not self.warmup_steps and not self.decay_steps:
            return self.lr
        if self.warmup_steps and not self.decay_steps:
            # warmup-then-constant: no cosine leg
            return optax.join_schedules(
                [optax.linear_schedule(0.0, self.lr, self.warmup_steps),
                 optax.constant_schedule(self.lr)],
                [self.warmup_steps])
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=self.lr,
            warmup_steps=max(self.warmup_steps, 1),
            decay_steps=max(self.decay_steps, self.warmup_steps + 1),
            end_value=self.lr * self.min_lr_ratio)

    def transform(self) -> optax.GradientTransformation:
        chain = []
        if self.grad_clip:
            chain.append(_scoped('clip',
                                 optax.clip_by_global_norm(self.grad_clip)))
        chain.append(optax.adamw(self.schedule(), b1=self.b1, b2=self.b2,
                                 eps=self.eps, weight_decay=self.weight_decay))
        return optax.chain(*chain)
