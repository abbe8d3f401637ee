"""Ouro language model family: a looped decoder (a "LoopLM").

The whole stack of ``layers`` layers runs ``passes`` times for every token,
the same weights each pass, each pass feeding the next. For a token
sequence ``x``::

    h_0 = E[x]                                   (float32 stream)
    for t in 1 .. passes:
        u = h_{t-1}
        for l in 1 .. layers:
            a = u + N2in_l(Attn_l^(t)(N1in_l(u)))
            u = a + N2post_l(W_down_l(silu(W_gate_l z) * W_up_l z)),
                z = N1post_l(a)
        h_t = N_final(u)                         (closes every pass)
    logits = W_head h_passes

``Attn_l^(t)``: ``q, k, v = W_q,k,v y`` (no bias), rotary at θ
``rope_theta`` over the whole head in the rotate-half convention, causal
softmax at ``head_dim^-1/2`` over **pass t's own keys**, then ``W_o``.
Four RMSNorms a layer ("sandwich" normalisation: before and after the
attention and before and after the MLP), the final norm closing every pass
with its output carried into the next, an untied head.

**A cache for every pass of every layer.** Pass ``t`` of layer ``l``
attends keys and values that pass ``t`` of layer ``l`` made, so decode
keeps ``passes x layers`` caches. The weights are shared across passes;
the caches are not: each (pass, layer) has cache leaves of its own
(``layer_l/attn/pass_t/{key, value, table, index}``), all following one
block table and one cursor per row (the serving engine edits every
``table`` and ``index`` leaf alike, and each advances once a token: a
pass's cache is written once by its one application a token).

**The exit gate.** The published model follows every pass with a gate
``sigmoid(w_e . h_t + b_e)`` and exits a token at the first pass whose
cumulative exit mass reaches ``early_exit_threshold``. At the published
threshold of 1 no token exits before the last pass, so the served logits
are the last pass's and the gate reaches nothing; its two leaves are held
(the tree is the whole published model) and not computed. A threshold
below 1 is refused at construction (:func:`exit_reason`).

Each pass runs under the named scope ``loop_pass``, in prefill and in
decode. Parameters may be handed in any float type; the residual stream
and the norms are float32, matrix products take ``dtype`` operands and
accumulate in float32. The family decode conventions (``decode``,
``max_seq``, ``per_row_decode``, ``decode_pages``) hold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpusystem.models.llama import RMSNorm, rotary_embedding
from tpusystem.ops.attention import attend, cached_attention
from tpusystem.ops.precision import head_logits
from tpusystem.registry import register


def exit_reason(threshold: float) -> str | None:
    """Why a looped model with this exit threshold cannot be run here, or
    ``None`` (threshold 1: every token runs every pass)."""
    if threshold >= 1:
        return None
    return (f'early_exit_threshold={threshold} lets rows of a batch leave at '
            'different passes (a per-row exit depth the compiled step does '
            'not have), and the caches of the passes a token skipped are '
            'never written, so a later token would attend keys that do not '
            'exist; only threshold 1 (every token runs every pass) is served')


def apply_rotary_half(tensor: jax.Array, cos: jax.Array,
                      sin: jax.Array) -> jax.Array:
    """Rotate ``[batch, len, heads, head_dim]`` in the rotate-half
    convention: the first half of the head against the second. Tables are
    ``[len, head_dim/2]`` or ``[batch, len, head_dim/2]``. Runs in float32,
    returns in the input dtype."""
    dtype = tensor.dtype
    tensor = tensor.astype(jnp.float32)
    half = tensor.shape[-1] // 2
    first, second = tensor[..., :half], tensor[..., half:]
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin],
                           axis=-1).astype(dtype)


class PassCache(nn.Module):
    """One pass's keys and values of one layer: rotary at the positions this
    cache's own cursor says, then :func:`~tpusystem.ops.attention.
    cached_attention` over its own leaves (the paged pool with
    ``decode_pages``)."""

    head_dim: int
    rope_theta: float
    max_seq: int
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, query, key, value):
        batch, length = query.shape[:2]
        cursor = (self.get_variable('cache', 'index')
                  if self.has_variable('cache', 'index')
                  else jnp.zeros((batch,), jnp.int32))
        cos, sin = rotary_embedding(cursor[:, None] + jnp.arange(length),
                                    self.head_dim, self.rope_theta)
        return cached_attention(self, apply_rotary_half(query, cos, sin),
                                apply_rotary_half(key, cos, sin), value,
                                self.max_seq, per_row=self.per_row_decode,
                                pages=self.decode_pages)


class LoopedAttention(nn.Module):
    """Full multi-head attention whose projections every pass shares and
    whose cache is the pass's own (:class:`PassCache` ``pass_<t>``)."""

    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype
    rope_theta: float
    decode: bool = False
    max_seq: int = 4096
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, hidden, loop: int):
        batch, length, dim = hidden.shape
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        shape = lambda heads: (batch, length, heads, self.head_dim)
        query = dense(self.heads * self.head_dim, 'q')(hidden).reshape(
            shape(self.heads))
        key = dense(self.kv_heads * self.head_dim, 'k')(hidden).reshape(
            shape(self.kv_heads))
        value = dense(self.kv_heads * self.head_dim, 'v')(hidden).reshape(
            shape(self.kv_heads))
        if self.decode:
            context = PassCache(self.head_dim, self.rope_theta, self.max_seq,
                                self.per_row_decode, self.decode_pages,
                                name=f'pass_{loop}')(query, key, value)
        else:
            cos, sin = rotary_embedding(jnp.arange(length), self.head_dim,
                                        self.rope_theta)
            context = attend(apply_rotary_half(query, cos, sin),
                             apply_rotary_half(key, cos, sin), value,
                             kernel='xla', causal=True)
        return dense(dim, 'out')(context.reshape(
            batch, length, self.heads * self.head_dim))


class LoopedLayer(nn.Module):
    """One layer of the looped stack, sandwich-normalised; ``loop`` names
    the pass (whose cache the attention uses)."""

    heads: int
    kv_heads: int
    head_dim: int
    ffn_dim: int
    dtype: jnp.dtype
    rope_theta: float
    eps: float
    decode: bool = False
    max_seq: int = 4096
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, hidden, loop: int):
        norm = lambda name, x: RMSNorm(self.eps, name=name)(
            x.astype(jnp.float32))
        mixed = LoopedAttention(
            self.heads, self.kv_heads, self.head_dim, self.dtype,
            self.rope_theta, self.decode, self.max_seq, self.per_row_decode,
            self.decode_pages, name='attn')(norm('input_norm', hidden), loop)
        hidden = hidden + norm('input_norm_2', mixed)
        normed = norm('post_attention_norm', hidden)
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        gated = (nn.silu(dense(self.ffn_dim, 'gate')(normed)
                         .astype(jnp.float32))
                 * dense(self.ffn_dim, 'up')(normed).astype(jnp.float32))
        out = dense(hidden.shape[-1], 'down')(gated.astype(self.dtype))
        return hidden + norm('post_attention_norm_2', out)


class Ouro(nn.Module):
    """Ouro-style looped decoder. Defaults are the published widths of
    Ouro-2.6B (https://huggingface.co/ByteDance/Ouro-2.6B, its published
    configuration); use :func:`ouro_tiny` on the CPU."""

    vocab_size: int = 49_152
    layers: int = 48
    passes: int = 4                  # total_ut_steps
    dim: int = 2048
    heads: int = 16
    kv_heads: int = 16
    head_dim: int = 128
    ffn_dim: int = 5632
    rope_theta: float = 1_000_000.0
    eps: float = 1e-6
    early_exit_threshold: float = 1.0
    max_seq: int = 4096
    dtype: str = 'bfloat16'
    decode: bool = False
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    def __post_init__(self):
        reason = exit_reason(self.early_exit_threshold)
        if reason is not None:
            raise ValueError(f'Ouro cannot be served so: {reason}')
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        del train                       # no dropout anywhere
        compute = jnp.dtype(self.dtype)
        assert tokens.shape[-1] <= self.max_seq, (
            f'sequence length {tokens.shape[-1]} exceeds max_seq='
            f'{self.max_seq}')
        with jax.named_scope('embed'):
            table = self.param('embedding', nn.initializers.normal(0.02),
                               (self.vocab_size, self.dim), jnp.float32)
            hidden = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        stack = [LoopedLayer(self.heads, self.kv_heads, self.head_dim,
                             self.ffn_dim, compute, self.rope_theta, self.eps,
                             self.decode, self.max_seq, self.per_row_decode,
                             self.decode_pages, name=f'layer_{index}')
                 for index in range(self.layers)]
        final_norm = RMSNorm(self.eps, name='final_norm')
        for loop in range(self.passes):
            with jax.named_scope('loop_pass'):
                for layer in stack:
                    hidden = layer(hidden, loop)
                hidden = final_norm(hidden)
        # the exit gate: held, never computed (see the module docstring)
        self.param('exit_gate', nn.initializers.zeros, (self.dim,),
                   jnp.float32)
        self.param('exit_gate_bias', nn.initializers.zeros, (), jnp.float32)
        with jax.named_scope('head'):
            head = self.param('lm_head', nn.initializers.lecun_normal(),
                              (self.dim, self.vocab_size), jnp.float32)
            return head_logits(hidden, head.astype(compute), tied=False)


register(Ouro)


def ouro_tiny(**overrides) -> Ouro:
    """Test scale: 3 passes of 2 layers (so that two passes still loop
    after the first), 4 heads of 16, vocabulary 256, float32."""
    config = dict(vocab_size=256, layers=2, passes=3, dim=64, heads=4,
                  kv_heads=4, head_dim=16, ffn_dim=96, max_seq=128,
                  dtype='float32')
    config.update(overrides)
    return Ouro(**config)
