"""Plain Ouro: the looped forward pass in ``jax.numpy``, float32, every
matrix product under ``jax.default_matmul_precision('highest')``.

The yardstick ``correct`` is decided against for the ``ouro`` family. No
kernels, no cache, no batching; it imports nothing of ``tpusystem/`` and is
handed only leaves the benchmark made from the seed (the bfloat16 values,
widened). It follows the published model
(https://huggingface.co/ByteDance/Ouro-2.6B: ``config.json``; Zhu et al.
2025, *Scaling Latent Reasoning via Looped Language Models*). For a
sequence ``x``::

    h_0 = E[x]
    for t in 1 .. total_ut_steps:
        u = h_{t-1}
        for l in 1 .. num_hidden_layers:      (the same leaves every pass)
            a = u + N2in_l(Attn_l(N1in_l(u)))
            u = a + N2post_l(W_down silu(W_gate z) * W_up z),  z = N1post_l(a)
        h_t = N_final(u)
    logits = h_T W_head

``Attn_l``: ``q, k, v = y W_q,k,v``, rotary at ``rope_theta`` over the
whole head in the rotate-half convention (the first half of a head against
the second, frequencies ``theta^(-2i/head_dim)``), causal softmax at
``head_dim^-1/2`` over the pass's own keys (each pass runs over the whole
sequence, so its keys are its own), then ``W_o``. RMSNorm at
``rms_norm_eps``. The exit gate does not reach the logits at
``early_exit_threshold`` 1 and is not computed.

The leaves of a layer are asked for whenever a pass needs them
(``leaves_of``), used for every sequence and freed before the next, so
that at most one layer's float32 leaves live beside the program's state.
``precision`` and ``bits`` are the control's levers, as
``chipbench/reference/deepseek_v2.py`` has them (whose plain helpers this
file shares: the rounded product, the norm, and the narrowing of every
matrix of a layer to the control's bits, one scale per output channel).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.deepseek_v2 import _product, narrow, rms_norm, silu

QUERY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the arithmetic needs, under the published names."""
    num_hidden_layers: int
    total_ut_steps: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float


def rotate_half(x, positions, model: Model):
    """``x [seq, heads, head_dim]`` turned by ``positions [seq]``."""
    half = model.head_dim // 2
    inv_freq = model.rope_theta ** (
        -jnp.arange(0, model.head_dim, 2, dtype=jnp.float32) / model.head_dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], axis=-1)


def attention(h, p, model: Model, precision: str):
    """Full causal multi-head attention over one sequence ``[seq, d]``
    (key/value head ``i // (heads / kv)`` for query head ``i``)."""
    seq = h.shape[0]
    heads, kv, size = (model.num_attention_heads, model.num_key_value_heads,
                       model.head_dim)
    positions = jnp.arange(seq)
    q = rotate_half(_product('sd,de->se', h, p['q'], precision).reshape(
        seq, heads, size), positions, model).reshape(
        seq, kv, heads // kv, size)
    k = rotate_half(_product('sd,de->se', h, p['k'], precision).reshape(
        seq, kv, size), positions, model)
    v = _product('sd,de->se', h, p['v'], precision).reshape(seq, kv, size)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(start):
        queries = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = _product('qgrd,kgd->grqk', queries, k, precision) \
            * size ** -0.5
        causal = (start + jnp.arange(block))[:, None] >= positions[None, :]
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _product('grqk,kgd->qgrd', weights, v, precision)

    mixed = jax.lax.map(attend, jnp.arange(0, seq, block))
    return _product('se,ed->sd', mixed.reshape(seq, heads * size), p['o'],
                    precision)


@functools.partial(jax.jit, static_argnames=('model', 'precision'))
def layer(u, p, *, model: Model, precision: str = 'float32'):
    """One sandwich-normalised layer on one sequence ``[seq, d]``."""
    eps = model.rms_norm_eps
    a = u + rms_norm(attention(rms_norm(u, p['input_norm'], eps), p, model,
                               precision), p['input_norm_2'], eps)
    z = rms_norm(a, p['post_attention_norm'], eps)
    mlp = _product('sw,wd->sd',
                   silu(_product('sd,dw->sw', z, p['gate'], precision))
                   * _product('sd,dw->sw', z, p['up'], precision),
                   p['down'], precision)
    return a + rms_norm(mlp, p['post_attention_norm_2'], eps)


def forward(sequences: list, leaves_of, model: Model, *,
            precision: str = 'float32', bits: int | None = None):
    """``h_T`` for every sequence of ``sequences`` (``[seq]`` int32 each).
    ``leaves_of('top')`` gives ``{'embedding', 'final_norm', 'lm_head'}``,
    ``leaves_of(i)`` layer ``i``'s leaves, asked for in every pass."""
    top = leaves_of('top')
    final = top['final_norm']
    hidden = [top['embedding'][tokens] for tokens in sequences]
    del top
    for _ in range(model.total_ut_steps):
        for index in range(model.num_hidden_layers):
            leaves = leaves_of(index)
            if bits:
                leaves = narrow(leaves, bits)
            hidden = [layer(u, leaves, model=model, precision=precision)
                      for u in hidden]
            del leaves
        hidden = [rms_norm(u, final, model.rms_norm_eps) for u in hidden]
    return hidden


def logits(sequences: list, leaves_of, model: Model, **levers):
    """``[seq, vocabulary]`` for every sequence (tests, small sizes)."""
    hidden = forward(sequences, leaves_of, model, **levers)
    table = leaves_of('top')['lm_head']
    return [_product('sd,dv->sv', h, table,
                     levers.get('precision', 'float32')) for h in hidden]


@jax.jit
def _gaps(h, lowered, tokens, table):
    scores = _product('sd,dv->sv', h, table, 'float32')[:-1]
    if lowered is None:
        first = tokens[1:]
    else:
        first = jnp.argmax(_product('sd,dv->sv', lowered, table,
                                    'bfloat16')[:-1], axis=-1)
    chosen = jnp.take_along_axis(scores, first[:, None], axis=-1)[:, 0]
    return jnp.max(scores, axis=-1) - chosen


def served_gaps(sequences: list, leaves_of, model: Model, *,
                control_bits: int | None = None):
    """For every sequence, at each position but the last: how far the logit
    of the token that follows lies below the reference's best (``[seq -
    1]``). With ``control_bits`` the gap is the control's instead: that of
    the token which the reference with its layers' matrices at that many
    bits, under bfloat16 products, puts first."""
    hidden = forward(sequences, leaves_of, model)
    lowered = [None] * len(sequences)
    if control_bits:
        lowered = forward(sequences, leaves_of, model, precision='bfloat16',
                          bits=control_bits)
    table = leaves_of('top')['lm_head']
    return [_gaps(h, low, tokens, table)
            for h, low, tokens in zip(hidden, lowered, sequences)]
