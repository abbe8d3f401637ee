"""dots3 language model family: learned sparse latent attention beside
sliding-window latent attention, sigmoid-routed experts with a shared one.

A pre-RMSNorm decoder built from ``layer_types``, on the family decode
conventions (``decode``, ``max_seq``, ``per_row_decode``, ``decode_pages``
— what :func:`tpusystem.train.generate._decoder` and the serving engine
clone), bias-free, untied head. ``__call__`` takes ``length``, the number
of real positions of a right-padded prompt, which the window layers need
(their cache keeps the prompt's last positions) and the serving engine's
prefill hands in. Two kinds of attention layer, each multi-head latent
attention (:class:`GatedLatentAttention`: queries down to a latent rank
and up, keys and values from one cached latent row ``[c ; k_rope]`` a
position, a sigmoid gate a head on the output):

* ``full_attention``: a **learned sparse attention** (DeepSeek-V3.2's
  indexer, :class:`Indexer`): a small second set of heads scores every
  earlier position, ``I(t, s) = sum_j w_j relu(qI_j(t) · kI_s)``, and the
  query attends only its ``index_topk`` best
  (:func:`tpusystem.ops.attention.sparse_latent_attention`). The indexer's
  keys (``index_dim`` values a position) are a second paged leaf of the
  layer, behind the same table;
* ``sliding_attention``: a window of ``window`` positions (the current one
  and those before it) over its own, wider latent, cached in a **per-row
  window cache** whose size does not grow with the context
  (:func:`tpusystem.ops.attention.window_latent_attention`).

``lora_rescale`` (the published ``apply_mla_qkv_lora_rescale``): the query
after its up-projection is multiplied by ``sqrt(dim / q_rank)`` and the
normed latent by ``sqrt(dim / kv_rank)`` before it is cached (LongCat-
Flash's ``mla_scale_q_lora`` and ``mla_scale_kv_lora``); the indexer's
query takes the unscaled query latent. Rotary angles are plain (no
scaling) at each kind's own ``theta``; a softmax scale of ``(nope +
rope)^-1/2``.

The FFN is a dense gated MLP in the first ``first_dense`` layers and
:class:`tpusystem.ops.moe.GatedExperts` after: sigmoid scores, the top
``k`` of score + learned correction (no group limit), weights
renormalised and scaled, three-matrix SwiGLU experts, one shared expert,
and ``held = (start, count)`` naming the experts whose matrices live on
this chip (one chip's share of an expert-parallel deployment).

Parameters may be handed in any float type. The residual stream, the
norms, the router's scores, the softmax and the index scores run in
float32; matrix products take ``dtype`` operands and accumulate in
float32. The vision and audio towers and the multi-token prediction module
of the published model are not here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpusystem.models.llama import RMSNorm, apply_rotary
from tpusystem.ops.attention import (banded_latent_attention, row_cache,
                                     selected_latent_attention,
                                     sparse_latent_attention,
                                     window_latent_attention,
                                     write_positions)
from tpusystem.ops.moe import GatedExperts
from tpusystem.ops.precision import head_logits
from tpusystem.registry import register

FULL, SLIDING = 'full_attention', 'sliding_attention'


def rotary_tables(positions, rope_dim: int, theta: float):
    """``(cos, sin)`` ``[..., rope_dim / 2]`` at ``positions``."""
    freqs = 1.0 / theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                            / rope_dim)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


class Indexer(nn.Module):
    """DeepSeek-V3.2's indexer for one full layer: ``heads`` queries of
    ``dim`` from the layer's query latent, one key of ``dim`` a position
    from its normed input (a LayerNorm, weight and bias), a weight a head
    from the same input scaled by ``(heads · dim)^-1/2``; rotary on the
    first ``rope_dim`` dims of each. Its cache leaf is ``key`` (a
    :func:`~tpusystem.ops.attention.row_cache`: paged behind the layer's
    table, ``dim`` lanes), written here in decode mode. Returns
    ``(queries, weights, keys, the key leaf after the write or None)``."""

    heads: int
    dim: int
    rope_dim: int
    eps: float
    dtype: jnp.dtype
    decode: bool
    max_seq: int
    per_row: bool
    pages: tuple | None

    @nn.compact
    def __call__(self, hidden, q_latent, cos, sin, positions, table):
        batch, length, _ = hidden.shape
        rope = self.rope_dim
        dense = lambda features, name, dtype: nn.Dense(
            features, use_bias=False, dtype=dtype, name=name)
        with jax.named_scope('mla_proj'):
            query = dense(self.heads * self.dim, 'wq_b', self.dtype)(
                q_latent).reshape(batch, length, self.heads, self.dim)
            key = nn.LayerNorm(self.eps, dtype=jnp.float32, name='k_norm')(
                dense(self.dim, 'wk', self.dtype)(hidden).astype(jnp.float32))
            weights = dense(self.heads, 'weights_proj', jnp.float32)(
                hidden.astype(jnp.float32)) * (self.heads * self.dim) ** -0.5
        query = jnp.concatenate([apply_rotary(query[..., :rope], cos, sin),
                                 query[..., rope:]], axis=-1)
        key = jnp.concatenate(
            [apply_rotary(key[:, :, None, :rope], cos, sin)[:, :, 0],
             key[..., rope:]], axis=-1).astype(self.dtype)
        query = query.astype(self.dtype)
        if not self.decode:
            return query, weights, key, None
        pool = row_cache(self, 'key', self.dim, self.dtype, batch,
                         max_seq=self.max_seq, pages=self.pages)
        if not self.is_initializing():
            with jax.named_scope('kv_write'):
                write_positions(pool, key, positions, table, pages=self.pages,
                                per_row=self.per_row)
        return query, weights, key, pool.value


class GatedLatentAttention(nn.Module):
    """Multi-head latent attention of either kind (module docstring), with
    a sigmoid gate a head from the layer's normed input multiplying that
    head's output before ``out``. ``index`` is ``(heads, dim, topk, eps)``
    for a full layer, ``None`` for a window layer (``window`` positions)."""

    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    theta: float
    lora_rescale: bool
    index: tuple | None
    window: int
    dtype: jnp.dtype
    eps: float
    decode: bool = False
    max_seq: int = 4096
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, hidden, length=None):
        batch, span, dim = hidden.shape
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        heads, nope, rope = self.heads, self.nope_dim, self.rope_dim
        with jax.named_scope('mla_proj'):
            q_latent = RMSNorm(self.eps, name='q_norm')(
                dense(self.q_rank, 'q_a')(hidden))
            query = dense(heads * (nope + rope), 'q_b')(q_latent)
            down = dense(self.kv_rank + rope, 'kv_a')(hidden)
            content = RMSNorm(self.eps, name='kv_norm')(
                down[..., :self.kv_rank])
            if self.lora_rescale:
                query = query * math.sqrt(dim / self.q_rank)
                content = content * math.sqrt(dim / self.kv_rank)
            query = query.reshape(batch, span, heads, nope + rope)
            up = self.param('kv_b', nn.initializers.lecun_normal(),
                            (self.kv_rank, heads * (nope + self.v_dim)),
                            jnp.float32)
            up = up.astype(self.dtype).reshape(self.kv_rank, heads,
                                               nope + self.v_dim)
            gate = jax.nn.sigmoid(dense(heads, 'gate')(hidden).astype(
                jnp.float32))

        cursor = (self.get_variable('cache', 'index')
                  if self.decode and self.has_variable('cache', 'index')
                  else jnp.zeros((batch,), jnp.int32))
        positions = cursor[:, None] + jnp.arange(span)[None, :]
        cos, sin = rotary_tables(positions, rope, self.theta)
        q_rope = apply_rotary(query[..., nope:], cos, sin)
        k_rope = apply_rotary(down[..., None, self.kv_rank:], cos,
                              sin)[:, :, 0]
        latent = jnp.concatenate([content, k_rope], axis=-1)
        scale = (nope + rope) ** -0.5
        weights = dict(scale=scale, max_seq=self.max_seq,
                       per_row=self.per_row_decode)
        if self.index is not None:
            index_heads, index_dim, topk, index_eps = self.index
            table = (self.get_variable('cache', 'table')
                     if self.decode_pages is not None
                     and self.has_variable('cache', 'table') else None)
            index_query, index_weights, index_keys, index_pool = Indexer(
                index_heads, index_dim, rope, index_eps, self.dtype,
                self.decode, self.max_seq, self.per_row_decode,
                self.decode_pages, name='indexer')(
                    hidden, q_latent, cos, sin, positions, table)
            if self.decode:
                mixed = sparse_latent_attention(
                    self, query[..., :nope], q_rope, latent, up[..., :nope],
                    up[..., nope:], index_query, index_weights, index_keys,
                    index_pool, topk=topk, pages=self.decode_pages,
                    **weights)
            else:
                mixed = selected_latent_attention(
                    jnp.concatenate([query[..., :nope], q_rope], axis=-1),
                    latent, up[..., :nope], up[..., nope:], index_query,
                    index_weights, index_keys, scale=scale, topk=topk)
        elif self.decode:
            mixed = window_latent_attention(
                self, query[..., :nope], q_rope, latent, up[..., :nope],
                up[..., nope:], window=self.window, length=length,
                **weights)
        else:
            mixed = banded_latent_attention(
                jnp.concatenate([query[..., :nope], q_rope], axis=-1),
                latent, up[..., :nope], up[..., nope:], scale=scale,
                window=self.window)
        with jax.named_scope('mla_proj'):
            mixed = mixed * gate[..., None].astype(mixed.dtype)
            return dense(dim, 'out')(
                mixed.reshape(batch, span, heads * self.v_dim))


class Dots3Block(nn.Module):
    """``x + attention(norm(x))``, then ``+ FFN(norm(·))``: the dense gated
    MLP (``experts=None``) or the expert layer (``experts``: the keyword
    arguments of :class:`~tpusystem.ops.moe.GatedExperts`, as pairs)."""

    attention: tuple             # GatedLatentAttention's arguments, as pairs
    dense_width: int
    experts: tuple | None
    dtype: jnp.dtype
    eps: float

    @nn.compact
    def __call__(self, hidden, length=None):
        dim = hidden.shape[-1]               # hidden: the float32 stream
        hidden = hidden + GatedLatentAttention(
            dtype=self.dtype, eps=self.eps, name='attn',
            **dict(self.attention))(RMSNorm(self.eps, name='attn_norm')(
                hidden), length).astype(hidden.dtype)
        normed = RMSNorm(self.eps, name='ffn_norm')(hidden)
        if self.experts is not None:
            return hidden + GatedExperts(dtype=self.dtype, name='moe',
                                         **dict(self.experts))(normed)
        with jax.named_scope('dense_mlp'):
            dense = lambda features, name: nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name)
            return hidden + dense(dim, 'down')(
                nn.silu(dense(self.dense_width, 'gate')(normed))
                * dense(self.dense_width, 'up')(normed)).astype(hidden.dtype)


PUBLISHED_LAYERS = (FULL, FULL) + (SLIDING, SLIDING, SLIDING, FULL) * 11


class Dots3(nn.Module):
    """dots3 decoder. Defaults are the published widths
    (https://huggingface.co/dots-studio/dots3-note-prev, its published
    configuration); use :func:`dots3_tiny` on the CPU. ``held`` and
    ``vocab_size`` are where a chip's share of an expert-parallel
    deployment is stated: the router stays ``experts`` wide, the expert
    matrices are the ``held`` ones. ``heads``, ``kv_rank``, ``index_*``
    are the full layers', ``window_*`` the sliding layers'."""

    vocab_size: int = 152_064
    layer_types: tuple = PUBLISHED_LAYERS
    dim: int = 5120
    heads: int = 128
    q_rank: int = 1024
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rope_theta: float = 8e7
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    index_eps: float = 1e-6
    window_heads: int = 64
    window_q_rank: int = 1024
    window_kv_rank: int = 1024
    window_nope_dim: int = 192
    window_rope_dim: int = 64
    window_v_dim: int = 128
    window_theta: float = 5e4
    window: int = 513
    lora_rescale: bool = True
    dense_width: int = 13_824
    expert_width: int = 1536
    experts: int = 256
    experts_per_token: int = 8
    shared_experts: int = 1
    routed_scale: float = 1.0
    first_dense: int = 1
    held: tuple | None = None    # (first expert held, how many); None: all
    max_seq: int = 4096
    eps: float = 1e-5
    dtype: str = 'bfloat16'
    decode: bool = False
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    def attention_of(self, kind: str) -> tuple:
        """:class:`GatedLatentAttention`'s arguments for one kind, as pairs."""
        common = dict(lora_rescale=self.lora_rescale, window=self.window,
                      decode=self.decode, max_seq=self.max_seq,
                      per_row_decode=self.per_row_decode,
                      decode_pages=self.decode_pages)
        if kind == FULL:
            return tuple(dict(
                heads=self.heads, q_rank=self.q_rank, kv_rank=self.kv_rank,
                nope_dim=self.nope_dim, rope_dim=self.rope_dim,
                v_dim=self.v_dim, theta=self.rope_theta,
                index=(self.index_heads, self.index_dim, self.index_topk,
                       self.index_eps), **common).items())
        if kind == SLIDING:
            return tuple(dict(
                heads=self.window_heads, q_rank=self.window_q_rank,
                kv_rank=self.window_kv_rank, nope_dim=self.window_nope_dim,
                rope_dim=self.window_rope_dim, v_dim=self.window_v_dim,
                theta=self.window_theta, index=None, **common).items())
        raise ValueError(f'unknown layer type {kind!r}; expected {FULL!r} '
                         f'or {SLIDING!r}')

    @nn.compact
    def __call__(self, tokens, train: bool = False, length=None):
        del train                       # no dropout anywhere
        compute = jnp.dtype(self.dtype)
        assert tokens.shape[-1] <= self.max_seq, (
            f'sequence length {tokens.shape[-1]} exceeds max_seq='
            f'{self.max_seq}')
        with jax.named_scope('embed'):
            table = self.param('embedding', nn.initializers.normal(0.02),
                               (self.vocab_size, self.dim), jnp.float32)
            hidden = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        experts = tuple(dict(
            experts=self.experts, k=self.experts_per_token,
            width=self.expert_width, scale=self.routed_scale,
            shared_width=self.shared_experts * self.expert_width,
            held=self.held, scoring='sigmoid').items())
        for index, kind in enumerate(self.layer_types):
            hidden = Dots3Block(
                self.attention_of(kind), self.dense_width,
                experts if index >= self.first_dense else None, compute,
                self.eps, name=f'layer_{index}')(hidden, length)
        hidden = RMSNorm(self.eps, name='final_norm')(hidden)
        with jax.named_scope('head'):
            head = self.param('lm_head', nn.initializers.lecun_normal(),
                              (self.dim, self.vocab_size), jnp.float32)
            return head_logits(hidden, head.astype(compute), tied=False)


register(Dots3)


def dots3_tiny(**overrides) -> Dots3:
    """Test scale: every mechanism of the published model at widths the
    CPU compiles in seconds (4 layers: full, full, sliding, sliding; the
    first dense; 16 experts, 3 a token, 1 shared; ``index_topk`` 8 and a
    window of 5, so that at 24-40 positions both the selection and the
    window bite)."""
    config = dict(vocab_size=256, layer_types=(FULL, FULL, SLIDING, SLIDING),
                  dim=64, heads=4, q_rank=32, kv_rank=16, nope_dim=16,
                  rope_dim=8, v_dim=16, index_heads=4, index_dim=16,
                  index_topk=8, window_heads=2, window_q_rank=32,
                  window_kv_rank=24, window_nope_dim=24, window_rope_dim=8,
                  window_v_dim=16, window=5, dense_width=128,
                  expert_width=48, experts=16, experts_per_token=3,
                  max_seq=64, dtype='float32')
    config.update(overrides)
    return Dots3(**config)
