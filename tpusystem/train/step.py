"""Jitted step builders — the TPU hot path.

The reference's per-step work is eager autograd driven from a Python batch
loop (``examples/tinysys/tinysys/classifier.py:29-35``:
zero_grad -> forward -> loss -> backward -> step). Here the whole step is a
single pure function lowered once through ``jax.jit``:

* forward + loss via ``jax.value_and_grad`` (autograd seam),
* optimizer update fused into the same XLA program,
* the :class:`~tpusystem.train.state.TrainState` argument is **donated**, so
  parameters and optimizer slots update in place in HBM (no copy),
* gradient all-reduce over the mesh data axis is inserted by GSPMD when the
  batch is sharded — the step body is identical on 1 chip and on a pod.

Metrics consumed by the event bus must read only the returned loss/outputs
*after* the phase completes (one device->host sync per phase, never per
batch) — the cadence the reference models with ``metrics.compute()``
(``examples/tinysys/tinysys/metrics.py:19-23``).
"""

from __future__ import annotations

from collections.abc import Callable
from inspect import signature
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpusystem.train.optim import masked_update
from tpusystem.train.state import TrainState

# apply_fn contract: (params, inputs, rng, train) -> outputs
ApplyFn = Callable[[Any, Any, jax.Array | None, bool], Any]
# criterion contract: (outputs, targets) -> scalar loss
Criterion = Callable[[Any, Any], jax.Array]


def flax_apply(module) -> ApplyFn:
    """Adapt a flax linen module to the step-builder apply contract.

    Passes ``train=`` and dropout RNGs only when the module's ``__call__``
    accepts them, so simple modules stay simple.
    """
    parameters = signature(module.__call__).parameters
    accepts_train = 'train' in parameters

    def apply(params, inputs, rng=None, train=False):
        kwargs = {'train': train} if accepts_train else {}
        rngs = {'dropout': rng} if rng is not None else None
        return module.apply({'params': params}, inputs, rngs=rngs, **kwargs)

    return apply


def build_train_step(apply_fn: ApplyFn, criterion: Criterion, optimizer,
                     *, accumulate: int = 1, jit: bool = True,
                     guard=None, fault=None):
    """Build ``step(state, inputs, targets) -> (state, (outputs, loss))``.

    ``optimizer`` is a :class:`tpusystem.train.optim.Optimizer` or a raw
    ``optax.GradientTransformation``. The returned step donates ``state``:
    callers must treat the passed-in state as consumed.

    ``guard=`` (a :class:`tpusystem.train.sentinel.Guard`) compiles anomaly
    detection into the same XLA program: loss/global-grad-norm finiteness
    plus an EMA grad-norm spike z-score, with the optimizer update
    suppressed in-graph on a bad step
    (:func:`tpusystem.train.optim.masked_update`) — no extra dispatch, no
    host sync. The statistics ride ``state.health``
    (:class:`~tpusystem.train.state.HealthStats`); arm the state with
    ``guard.arm(state)`` before the first step. The step counter still
    advances on a suppressed step (the batch was consumed — PaLM-style
    skip), while the optimizer's own count does not (schedules see only
    applied updates).

    ``fault=`` is the chaos-drill seam: a traced callable
    ``(step, grads, loss) -> (grads, loss)`` applied right after the
    gradient computation (``step`` is the 1-based index of the step being
    computed). Production code leaves it None; the chaos harness injects
    :class:`tpusystem.parallel.chaos.CorruptGrads` here to drill the
    guard's escalation ladder end-to-end.

    ``accumulate=N`` splits the leading batch dimension into N sequential
    microbatches inside the step (``lax.scan``), averaging gradients
    before the single optimizer update — the activation-memory lever when
    the target global batch does not fit (grads add one params-sized
    buffer; activations shrink by N). When the criterion exposes
    ``weight(targets)`` (the masked LM losses return their unmasked-token
    count), microbatch losses and grads are weighted by it, so the result
    equals the full-batch step even when padding gives microbatches
    different token counts; criteria without ``weight`` are averaged
    equally (exact for per-example-mean losses). With accumulation, the
    returned ``outputs`` are the final microbatch's and ``loss`` is the
    weighted mean over microbatches.

    For activation rematerialisation use per-layer checkpointing at the
    model level (e.g. ``GPT2(remat=True)``) — whole-forward checkpointing
    here would double FLOPs without reducing backward peak memory.
    """
    transform = optimizer.transform() if hasattr(optimizer, 'transform') else optimizer

    # jax.named_scope names a device trace is read by: `model`, `loss`,
    # `optimizer` (the chunked LM head adds `loss_head`, the clipping norm
    # `clip`). flax's own module scopes stay innermost inside `model`, so a
    # Pallas kernel keeps the trace name its module gives it (`attn`)
    def objective(params, inputs, targets, dropout_rng):
        with jax.named_scope('model'):
            outputs = apply_fn(params, inputs, dropout_rng, True)
        with jax.named_scope('loss'):
            loss = criterion(outputs, targets)
        return loss, outputs

    def step(state: TrainState, inputs, targets):
        state, dropout_rng = state.next_rng()
        if accumulate == 1:
            (loss, outputs), grads = jax.value_and_grad(
                objective, has_aux=True)(state.params, inputs, targets,
                                         dropout_rng)
        else:
            batch = jax.tree.leaves(inputs)[0].shape[0]
            assert batch % accumulate == 0, (
                f'batch {batch} not divisible by accumulate={accumulate}')
            split = lambda leaf: leaf.reshape(
                (accumulate, batch // accumulate) + leaf.shape[1:])
            micro = (jax.tree.map(split, inputs), jax.tree.map(split, targets),
                     jax.random.split(dropout_rng, accumulate))
            params = state.params

            weight_fn = getattr(criterion, 'weight', None)

            def one(carry, xs):
                grads_acc, loss_acc, weight_acc, _ = carry
                micro_inputs, micro_targets, rng = xs
                (loss, outputs), grads = jax.value_and_grad(
                    objective, has_aux=True)(params, micro_inputs,
                                             micro_targets, rng)
                weight = (jnp.float32(weight_fn(micro_targets)) if weight_fn
                          else jnp.float32(1.0))
                # outputs ride the CARRY (last microbatch wins): stacking
                # them as scan ys would materialize the full-batch outputs
                # buffer this feature exists to avoid
                return (jax.tree.map(
                            lambda acc, g: acc + g.astype(jnp.float32) * weight,
                            grads_acc, grads),
                        loss_acc + loss * weight, weight_acc + weight,
                        outputs), None

            first = jax.tree.map(lambda leaf: leaf[0], micro)
            output_shapes = jax.eval_shape(
                lambda *xs: objective(params, *xs)[1], *first[:2], first[2])
            empty = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), output_shapes)
            # grads accumulate in float32 regardless of param dtype (exact
            # token-count weights + stable sums; standard practice), cast
            # back to the param dtype for the optimizer
            zeros = jax.tree.map(
                lambda leaf: jnp.zeros(leaf.shape, jnp.float32), params)
            (grads, loss_sum, weight_sum, outputs), _ = jax.lax.scan(
                one, (zeros, jnp.float32(0), jnp.float32(0), empty), micro)
            weight_sum = jnp.maximum(weight_sum, 1e-8)  # all-pad batch guard
            grads = jax.tree.map(
                lambda g, p: (g / weight_sum).astype(p.dtype), grads, params)
            loss = loss_sum / weight_sum
        current = state.step + 1
        if fault is not None:
            grads, loss = fault(current, grads, loss)
        if guard is not None:
            assert state.health is not None, (
                'guard= needs health stats on the TrainState: arm it with '
                'Guard.arm(state) before the first step')
            health, ok = guard.judge(state.health, loss, grads)
            with jax.named_scope('optimizer'):
                params, opt_state = masked_update(
                    transform, grads, state.opt_state, state.params, ok,
                    scale=health.lr_scale)
            state = state.replace(params=params, opt_state=opt_state,
                                  step=current, health=health)
            return state, (outputs, loss)
        with jax.named_scope('optimizer'):
            updates, opt_state = transform.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
        state = state.replace(params=params, opt_state=opt_state, step=current)
        return state, (outputs, loss)

    return jax.jit(step, donate_argnums=0) if jit else step


def build_multi_step(step, *, jit: bool = True, outputs_fn=None,
                     guard: bool = False):
    """Wrap an (unjitted) train step into N steps per host dispatch.

    ``multi(state, inputs, targets) -> (state, losses)`` where inputs and
    targets carry a leading ``steps`` dimension (``[N, batch, ...]``) and
    ``losses`` is the per-step ``[N]`` float32 vector. One ``lax.scan``
    runs the N steps in a single compiled program, so per-dispatch host
    overhead (one Python round trip) is paid once per N batches instead
    of per batch. Each distinct ``N`` compiles its own program (a
    :func:`grouped_batches` tail group shorter than ``size`` costs one
    extra compile, cached thereafter). Per-phase metrics stay exact: feed
    the whole loss vector
    to the accumulator (``Mean``/``Perplexity`` accept arrays), and keep
    events at phase cadence as before.

    ``step`` must be built with ``jit=False`` (it is traced into the scan).
    Per-step ``outputs`` are dropped by default — stacking N output pytrees
    would materialize exactly the buffers the fused-loss path avoids. Pass
    ``outputs_fn`` (e.g. ``lambda o: jnp.argmax(o, -1)`` for classifier
    predictions) to stack a *reduced* output per step instead; the return
    becomes ``(state, (stacked_reduced_outputs, losses))``.

    ``guard=True`` (for a ``step`` built with ``guard=``) additionally
    stacks each step's health row (``state.health.last``,
    :data:`tpusystem.train.sentinel.HEALTH_COLUMNS`), so the host-side
    :class:`~tpusystem.train.sentinel.Sentinel` reviews every step of the
    dispatch at the same single phase-cadence sync: the return becomes
    ``(state, (losses, health[N, 4]))`` (health last when ``outputs_fn``
    is also given).
    """
    def multi(state: TrainState, inputs, targets):
        if guard:
            assert state.health is not None, (
                'guard=True needs a guarded step and an armed state '
                '(Guard.arm) — see build_train_step(guard=...)')
        def body(state, xs):
            micro_inputs, micro_targets = xs
            state, (outputs, loss) = step(state, micro_inputs, micro_targets)
            loss = jnp.asarray(loss, jnp.float32)
            ys = (loss,) if outputs_fn is None else (outputs_fn(outputs), loss)
            if guard:
                ys = ys + (state.health.last,)
            return state, ys[0] if len(ys) == 1 else ys
        return jax.lax.scan(body, state, (inputs, targets))
    return jax.jit(multi, donate_argnums=0) if jit else multi


def build_multi_eval_step(step, *, jit: bool = True, outputs_fn=None):
    """Eval counterpart of :func:`build_multi_step`:
    ``multi(state, inputs, targets) -> losses[N]`` (or
    ``(stacked_reduced_outputs, losses)`` with ``outputs_fn``) over stacked
    batches (``step`` from ``build_eval_step(..., jit=False)``)."""
    def multi(state: TrainState, inputs, targets):
        def body(carry, xs):
            outputs, loss = step(state, xs[0], xs[1])
            loss = jnp.asarray(loss, jnp.float32)
            if outputs_fn is None:
                return carry, loss
            return carry, (outputs_fn(outputs), loss)
        _, ys = jax.lax.scan(body, jnp.int32(0), (inputs, targets))
        return ys
    return jax.jit(multi) if jit else multi


def grouped_batches(loader, size: int):
    """Yield tuples of ``[n, batch, ...]`` stacks of up to ``size``
    consecutive batches — the host-side feeder for
    :func:`build_multi_step`. Accepts loaders yielding tuples (``(inputs,
    targets)``) or bare arrays; the tail stack is shorter when the loader
    length doesn't divide ``size``. A shorter tail is a *distinct shape*
    to the jitted scan in ``build_multi_step`` — it compiles once per
    distinct group length, so a non-dividing loader pays one extra
    compile for the remainder group (cached across epochs of the same
    length; pick ``size`` dividing the epoch, or feed the remainder to
    the per-batch step, if that compile matters).

    Device-resident batches stack with ``jnp.stack`` (stays on device —
    ``np.stack`` would round-trip every batch through the host); host
    arrays stack with ``np.stack``."""
    group: list = []

    def flush():
        return tuple(
            jnp.stack(parts) if isinstance(parts[0], jax.Array)
            else np.stack(parts)
            for parts in zip(*group))

    for batch in loader:
        group.append(batch if isinstance(batch, tuple) else (batch,))
        if len(group) == size:
            yield flush()
            group = []
    if group:
        yield flush()


def build_eval_step(apply_fn: ApplyFn, criterion: Criterion, *, jit: bool = True):
    """Build ``step(state, inputs, targets) -> (outputs, loss)`` (no grads,
    deterministic forward) — the ``inference_mode`` analogue."""

    def step(state: TrainState, inputs, targets):
        with jax.named_scope('model'):
            outputs = apply_fn(state.params, inputs, None, False)
        with jax.named_scope('loss'):
            return outputs, criterion(outputs, targets)

    return jax.jit(step) if jit else step


def build_1f1b_train_step(model, criterion: Criterion, optimizer,
                          *, jit: bool = True):
    """1F1B-scheduled train step for pipelined models (``GPT2Pipelined``).

    Same ``step(state, inputs, targets) -> (state, (outputs, loss))``
    contract as :func:`build_train_step` (``outputs`` is None — microbatch
    outputs never exist whole under 1F1B), but the forward/backward runs
    through :func:`tpusystem.parallel.pipeline.pipeline_train`: backwards
    interleave with forwards so the per-stage activation stash is bounded
    by the stage count instead of the microbatch count. Use when
    activation memory, not step time, binds (see ``pipeline_train``'s
    bubble-FLOPs tradeoff).

    The model supplies the decomposition: ``_embed`` (head), ``_block_fn``
    (stage body), ``_head`` (tail, composed with ``criterion``); its tied
    embedding appears in both head and tail and both gradient
    contributions are summed inside ``pipeline_train``.
    """
    from tpusystem.parallel.pipeline import pipeline_train

    if getattr(model, 'moe_experts', 0):
        raise ValueError(
            'build_1f1b_train_step does not support MoE spans (the router '
            'aux channel rides the GPipe path only) — use build_train_step')

    transform = optimizer.transform() if hasattr(optimizer, 'transform') else optimizer

    def tail_fn(replicated, activations, micro_targets):
        return criterion(model._head(replicated, activations), micro_targets)

    train = pipeline_train(model._embed, model._block_fn(), tail_fn,
                           model.mesh, microbatches=model.microbatches,
                           weight_fn=getattr(criterion, 'weight', None),
                           interleave=getattr(model, 'interleave', 1))

    stacked_key = getattr(model, 'stacked_key', 'h')

    def step(state: TrainState, inputs, targets):
        replicated = {key: value for key, value in state.params.items()
                      if key != stacked_key}
        loss, (d_replicated, d_stacked) = train(
            replicated, state.params[stacked_key], inputs, targets)
        grads = dict(d_replicated, **{stacked_key: d_stacked})
        updates, opt_state = transform.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        state = state.replace(params=params, opt_state=opt_state,
                              step=state.step + 1)
        return state, (None, loss)

    return jax.jit(step, donate_argnums=0) if jit else step


def init_state(module, optimizer, sample_inputs, *, rng: int | jax.Array = 0,
               param_dtype=None) -> TrainState:
    """Initialize a :class:`TrainState` for a flax module.

    Runs ``module.init`` on the sample batch shape, initializes optimizer
    slots, and seeds the carried RNG stream.
    """
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    init_rng, carry_rng = jax.random.split(rng)
    parameters = signature(module.__call__).parameters
    kwargs = {'train': False} if 'train' in parameters else {}
    variables = module.init(init_rng, sample_inputs, **kwargs)
    params = variables['params']
    if param_dtype is not None:
        params = jax.tree.map(lambda leaf: leaf.astype(param_dtype), params)
    transform = optimizer.transform() if hasattr(optimizer, 'transform') else optimizer
    return TrainState.create(params, transform.init(params), carry_rng)
