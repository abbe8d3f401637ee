"""Mamba-2 state-space mixer: the chunked scan, the one-token update, and
the flax module that keeps a **per-row** state in the ``cache`` collection.

The layer (Dao & Gu, "Transformers are SSMs", 2024; the ``nemotron_h``
family's modelling code), for an input ``u_t``::

    [z_t ; xBC_t ; dt_t] = W_in u_t
    xBC_t  <- silu(b_c + sum_j w_c[:, j] * xBC_{t-K+1+j})      causal, depthwise
    x_t [H, P], B_t [G, N], C_t [G, N] = split(xBC_t)          head h reads group h // (H/G)
    D_t = softplus(dt_t + dt_bias),  a_t = exp(D_t * A),  A = -exp(A_log)
    S_t[h] = a_t[h] * S_{t-1}[h] + D_t[h] * x_t[h] (x) B_t[g]   S in R^{P x N}
    y_t[h] = S_t[h] . C_t[g] + D[h] * x_t[h]
    y_t <- groupnorm_rms(y_t * silu(z_t)) * w_n                 within each of the G groups
    out = W_out y_t

Two forms of the same recurrence:

* :func:`ssm_scan` — the chunked form for many positions at once (prefill):
  within a chunk the decay-masked ``C B^T`` product, across chunks the
  carried state. Positions whose ``dt`` is 0 leave the state as it was
  (decay 1, input 0): how a right-padded prompt's junk is kept out of it.
* :func:`ssm_update` — one recurrence step a row (decode).

:class:`Mamba2` runs them over one set of cache leaves, on
:func:`tpusystem.ops.attention.cached_attention`'s conventions: ``state``
(float32 ``[batch, H, P, N]``) and ``conv`` (``[batch, K - 1, C]``, the
compute type: the convolution's last inputs) beside the ``index`` cursor.
Both are addressed **by row**: no blocks, no table, nothing a cursor masks.
The call that creates the cache is the prefill; it takes the true ``length``
of each row so that a prompt padded to a bucket leaves the state, and the
convolution's tail, as they stood at ``length``.

The state, ``dt`` and the decays are float32 whatever the compute type (a
recurrent accumulator rounded every token compounds over a thousand steps);
matrix products take ``dtype`` operands and accumulate in float32. Scopes
(``jax.named_scope``): ``ssm_proj`` (the two projections, the gated norm),
``ssm_conv``, ``ssm_scan``, ``ssm_update``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn


def ssm_update(state, x, dt, A, B, C):
    """One step of the recurrence for every row: ``state [b, H, P, N]``
    float32, ``x [b, H, P]``, ``dt [b, H]`` (after the softplus), ``A [H]``
    (negative), ``B``/``C [b, G, N]``. Returns ``(y [b, H, P], new state)``,
    float32, ``y`` without the ``D x`` term."""
    heads, groups = x.shape[1], B.shape[1]
    spread = lambda grouped: jnp.repeat(grouped.astype(jnp.float32),
                                        heads // groups, axis=1)
    decay = jnp.exp(dt * A)                                       # [b, H]
    pushed = (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * spread(B)[:, :, None, :]
    new = state * decay[..., None, None] + pushed
    return jnp.sum(new * spread(C)[:, :, None, :], axis=-1), new


def ssm_scan(x, dt, A, B, C, *, chunk: int, initial=None):
    """The recurrence over ``L`` positions in chunks of ``chunk``.

    ``x [b, L, H, P]``, ``dt [b, L, H]`` float32 (after the softplus; 0 at a
    position that must leave the state alone), ``A [H]`` negative, ``B``/``C
    [b, L, G, N]``, ``initial [b, H, P, N]`` (None: zeros). Returns ``(y [b,
    L, H, P], state [b, H, P, N])`` in float32: ``y`` without the ``D x``
    term, ``state`` the one after the last position. The matrix products
    take their operands in ``x``'s type and accumulate in float32; the
    decays are float32 throughout. ``L`` is padded to whole chunks with
    ``dt = 0`` positions."""
    batch, length, heads, width = x.shape
    groups, size = B.shape[2:]
    per_group = heads // groups
    operand = x.dtype
    chunk = min(chunk, length)
    pad = -length % chunk
    if pad:
        grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = grow(x), grow(dt), grow(B), grow(C)
    count = (length + pad) // chunk
    x = x.reshape(batch, count, chunk, groups, per_group, width)
    B = B.reshape(batch, count, chunk, groups, size)
    C = C.reshape(batch, count, chunk, groups, size)
    dt = dt.reshape(batch, count, chunk, groups, per_group)
    product = lambda spec, left, right: jnp.einsum(
        spec, left.astype(operand), right.astype(operand),
        preferred_element_type=jnp.float32)

    # log-decay from the chunk's start up to and including each position
    run = jnp.cumsum(dt * A.reshape(groups, per_group), axis=2)
    total = run[:, :, -1]                                     # [b, c, G, R]

    # within a chunk: y_i = sum_{j<=i} exp(run_i - run_j) dt_j (C_i.B_j) x_j
    gap = run[:, :, :, None] - run[:, :, None, :]             # [b,c,i,j,G,R]
    causal = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None, None], gap,
                              -jnp.inf))
    scores = product('bcign,bcjgn->bcijg', C, B)
    weights = scores[..., None] * decay * dt[:, :, None]
    within = product('bcijgr,bcjgrp->bcigrp', weights, x)

    # what each chunk adds to the state by its end, and the carried state
    left = jnp.exp(total[:, :, None] - run) * dt              # [b,c,j,G,R]
    added = product('bcjgrp,bcjgn->bcgrpn',
                    x.astype(jnp.float32) * left[..., None], B)

    def carry(state, step):
        shrink, gain = step
        return state * shrink[..., None, None] + gain, state

    start = (jnp.zeros((batch, groups, per_group, width, size), jnp.float32)
             if initial is None else initial.astype(jnp.float32).reshape(
                 batch, groups, per_group, width, size))
    last, before = jax.lax.scan(
        carry, start, (jnp.moveaxis(jnp.exp(total), 1, 0),
                       jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                   # [b,c,G,R,P,N]
    across = product('bcign,bcgrpn->bcigrp', C, before) \
        * jnp.exp(run)[..., None]
    y = (within + across).reshape(batch, count * chunk, heads, width)
    return y[:, :length], last.reshape(batch, heads, width, size)


def _dt_bias_init(low: float, high: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform over
    ``[low, high]`` (the family's initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (math.log(high) - math.log(low)) + math.log(low))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -exp(A_log)`` uniform over ``[-16, -1]``."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


class Mamba2(nn.Module):
    """The Mamba-2 mixer (module docstring). ``heads x head_dim`` is the
    inner width (not a multiple of the model's width by any rule),
    ``groups`` the number of ``B``/``C`` groups, ``state`` the state size
    ``N``. In ``decode`` mode the layer keeps ``state``, ``conv`` and
    ``index`` in the ``cache`` collection; ``length`` (``[batch]`` or a
    scalar; None: every position) is how many of the call's positions are
    real — the rest are right-padding that must not reach the state."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int = 4
    chunk: int = 128
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    decode: bool = False

    @nn.compact
    def __call__(self, hidden, length=None):
        batch, positions, dim = hidden.shape
        heads, width, groups, size = (self.heads, self.head_dim, self.groups,
                                      self.state)
        inner, taps = heads * width, self.conv_kernel
        channels = inner + 2 * groups * size
        compute = jnp.dtype(self.dtype)
        init = nn.initializers.lecun_normal()
        w_in = self.param('in_proj', init, (dim, inner + channels + heads),
                          jnp.float32)
        w_conv = self.param('conv_weight', init, (channels, taps), jnp.float32)
        b_conv = self.param('conv_bias', nn.initializers.zeros, (channels,),
                            jnp.float32)
        a_log = self.param('A_log', _a_log_init, (heads,), jnp.float32)
        skip = self.param('D', nn.initializers.ones, (heads,), jnp.float32)
        dt_bias = self.param('dt_bias', _dt_bias_init(0.001, 0.1), (heads,),
                             jnp.float32)
        w_norm = self.param('norm_scale', nn.initializers.ones, (inner,),
                            jnp.float32)
        w_out = self.param('out_proj', init, (inner, dim), jnp.float32)
        project = lambda value, matrix: jnp.dot(
            value.astype(compute), matrix.astype(compute),
            preferred_element_type=jnp.float32)

        with jax.named_scope('ssm_proj'):
            projected = project(hidden, w_in)
            gate = projected[..., :inner]
            mixed = projected[..., inner:inner + channels].astype(compute)
            step = projected[..., inner + channels:]

        created = not self.has_variable('cache', 'state')
        if self.decode:
            carried = self.variable('cache', 'state', jnp.zeros,
                                    (batch, heads, width, size), jnp.float32)
            tail = self.variable('cache', 'conv', jnp.zeros,
                                 (batch, taps - 1, channels), compute)
            index = self.variable('cache', 'index',
                                  lambda: jnp.zeros((batch,), jnp.int32))
            before, context = carried.value, tail.value
        else:
            before = None
            context = jnp.zeros((batch, taps - 1, channels), compute)

        with jax.named_scope('ssm_conv'):
            window = jnp.concatenate([context, mixed], axis=1)
            mixed = b_conv.astype(jnp.float32) + sum(
                window[:, tap:tap + positions].astype(jnp.float32)
                * w_conv[:, tap].astype(jnp.float32) for tap in range(taps))
            mixed = nn.silu(mixed).astype(compute)
            if length is None:
                kept = window[:, positions:]
            else:       # the last inputs before each row's own length
                kept = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
                    row, at, taps - 1, axis=0))(
                        window, jnp.broadcast_to(length, (batch,)))
        x = mixed[..., :inner].reshape(batch, positions, heads, width)
        B = mixed[..., inner:inner + groups * size].reshape(
            batch, positions, groups, size)
        C = mixed[..., inner + groups * size:].reshape(
            batch, positions, groups, size)
        dt = nn.softplus(step + dt_bias.astype(jnp.float32))
        if length is not None:
            real = jnp.arange(positions)[None, :] < jnp.broadcast_to(
                length, (batch,))[:, None]
            dt = jnp.where(real[..., None], dt, 0.0)
        A = -jnp.exp(a_log.astype(jnp.float32))

        if self.decode and not created and positions == 1:
            with jax.named_scope('ssm_update'):
                y, after = ssm_update(before, x[:, 0], dt[:, 0], A, B[:, 0],
                                      C[:, 0])
                y = y[:, None]
        else:
            with jax.named_scope('ssm_scan'):
                y, after = ssm_scan(x, dt, A, B, C, chunk=self.chunk,
                                    initial=before)
        if self.decode and not self.is_initializing():
            carried.value, tail.value = after, kept
            index.value = index.value + positions

        with jax.named_scope('ssm_proj'):
            y = y + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
            y = y.reshape(batch, positions, inner) * nn.silu(gate)
            grouped = y.reshape(batch, positions, groups, inner // groups)
            grouped = grouped * jax.lax.rsqrt(
                jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
                + self.eps)
            y = grouped.reshape(batch, positions, inner) \
                * w_norm.astype(jnp.float32)
            return project(y, w_out)
