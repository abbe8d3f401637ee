"""DeepSeek-V2 language model family: multi-head latent attention and
group-limited expert layers with shared experts.

A pre-RMSNorm decoder on the family decode conventions (``decode``,
``max_seq``, ``per_row_decode``, ``decode_pages`` — what
:func:`tpusystem.train.generate._decoder` and the serving engine clone),
bias-free throughout, untied head. What sets it apart from
:mod:`tpusystem.models.llama`:

* **MLA** (:class:`LatentAttention`): queries go down to ``q_rank`` and up
  to ``heads x (nope + rope)``; keys and values go down to one latent row
  ``[c_kv (kv_rank) ; k_rope (rope)]`` a position, which is all that is
  cached (:func:`tpusystem.ops.attention.latent_attention`: expanded per
  head at prefill, absorbed into the query and the output at decode).
* **YaRN** rotary on the ``rope`` dims only (:func:`yarn_frequencies`),
  with the softmax scale raised by ``mscale²`` (:func:`yarn_softmax_scale`).
* **A per-layer FFN kind**: a dense gated MLP in the first
  ``first_dense`` layers (and wherever ``layer % moe_every`` is not 0),
  :class:`tpusystem.ops.moe.GatedExperts` after — softmax router over all
  ``experts``, group-limited top-k, scaled weights, shared experts, and
  ``held = (start, count)`` naming the experts whose matrices live on this
  chip (one chip's share of an expert-parallel deployment).

Parameters may be handed in any float type (a 10 GB bfloat16 tree is served
as it is). The residual stream, the norms, the router's scores and softmax
run in float32; matrix products take ``dtype`` operands and accumulate in
float32. (A bfloat16 residual stream rounds at every add, and a rounded
router input flips near-tied experts: a token then goes through another
expert than the float32 model's.)
The training losses of the published model (``seq_aux``, the balance
terms) are not here: the module returns logits.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpusystem.models.llama import RMSNorm, apply_rotary
from tpusystem.ops.attention import (expanded_latent_attention,
                                     latent_attention)
from tpusystem.ops.moe import GatedExperts
from tpusystem.ops.precision import head_logits
from tpusystem.registry import register


def yarn_correction_range(rope_dim: int, theta: float, original: int,
                          beta_fast: float, beta_slow: float):
    """``(low, high)``: the rotary pairs between which YaRN blends the
    published and the interpolated frequencies. A pair that turns ``r``
    times over the original context has index ``c(r) = rope_dim ·
    ln(original / (2π r)) / (2 ln theta)``; ``low = ⌊c(beta_fast)⌋``,
    ``high = ⌈c(beta_slow)⌉``, clipped to the pairs there are."""
    def pair(turns: float) -> float:
        return (rope_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), rope_dim - 1))


def yarn_frequencies(rope_dim: int, theta: float, factor: float,
                     original: int, beta_fast: float,
                     beta_slow: float) -> jax.Array:
    """``[rope_dim / 2]`` inverse frequencies: ``f_i = theta^(-2i /
    rope_dim)`` below ``low``, ``f_i / factor`` above ``high``, a linear
    blend between."""
    f = 1.0 / theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                        / rope_dim)
    low, high = yarn_correction_range(rope_dim, theta, original, beta_fast,
                                      beta_slow)
    ramp = jnp.clip((jnp.arange(rope_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_softmax_scale(head_dim: int, factor: float,
                       mscale_all_dim: float) -> float:
    """``head_dim^-1/2 · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``
    (0.11472 at the published 192, 40, 0.707)."""
    scale = head_dim ** -0.5
    if mscale_all_dim:
        scale *= yarn_mscale(factor, mscale_all_dim) ** 2
    return scale


class LatentAttention(nn.Module):
    """Multi-head latent attention (module docstring). Scope ``mla_proj``
    holds the down- and up-projections and, at decode, the two absorption
    products; ``kv_write``/``kv_read`` are
    :func:`~tpusystem.ops.attention.latent_attention`'s."""

    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dtype: jnp.dtype
    eps: float
    rope: tuple      # (theta, factor, original, beta_fast, beta_slow,
    #                   mscale, mscale_all_dim)
    decode: bool = False
    max_seq: int = 4096
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, hidden):
        batch, length, dim = hidden.shape
        theta, factor, original, fast, slow, mscale, mscale_all = self.rope
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        heads, nope, rope = self.heads, self.nope_dim, self.rope_dim
        with jax.named_scope('mla_proj'):
            query = dense(heads * (nope + rope), 'q_b')(
                RMSNorm(self.eps, name='q_norm')(
                    dense(self.q_rank, 'q_a')(hidden)))
            query = query.reshape(batch, length, heads, nope + rope)
            down = dense(self.kv_rank + rope, 'kv_a')(hidden)
            content = RMSNorm(self.eps, name='kv_norm')(
                down[..., :self.kv_rank])
            # kv_b is declared at its published shape [kv_rank, heads *
            # (nope + v)]: a head's columns are its key half, then its
            # value half
            up = self.param('kv_b', nn.initializers.lecun_normal(),
                            (self.kv_rank, heads * (nope + self.v_dim)),
                            jnp.float32)
            up = up.astype(self.dtype).reshape(self.kv_rank, heads,
                                               nope + self.v_dim)

        if self.decode:
            cursor = (self.get_variable('cache', 'index')
                      if self.has_variable('cache', 'index')
                      else jnp.zeros((batch,), jnp.int32))
            positions = cursor[:, None] + jnp.arange(length)
        else:
            positions = jnp.arange(length)
        angles = positions.astype(jnp.float32)[..., None] * yarn_frequencies(
            rope, theta, factor, original, fast, slow)
        # cos and sin carry mscale(factor, mscale) / mscale(factor,
        # mscale_all_dim): 1 at the published values
        spread = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
        cos, sin = jnp.cos(angles) * spread, jnp.sin(angles) * spread
        q_rope = apply_rotary(query[..., nope:], cos, sin)
        k_rope = apply_rotary(down[..., None, self.kv_rank:], cos, sin)[:, :, 0]
        latent = jnp.concatenate([content, k_rope], axis=-1)
        scale = yarn_softmax_scale(nope + rope, factor, mscale_all)

        if self.decode:
            mixed = latent_attention(
                self, query[..., :nope], q_rope, latent, up[..., :nope],
                up[..., nope:], scale=scale, max_seq=self.max_seq,
                per_row=self.per_row_decode, pages=self.decode_pages)
        else:
            mixed = expanded_latent_attention(
                jnp.concatenate([query[..., :nope], q_rope], axis=-1),
                k_rope, latent, up[..., :nope], up[..., nope:], scale)
        with jax.named_scope('mla_proj'):
            return dense(dim, 'out')(
                mixed.reshape(batch, length, heads * self.v_dim))


class DeepSeekBlock(nn.Module):
    """``x + MLA(norm(x))``, then ``+ FFN(norm(·))``: the dense gated MLP
    (``experts=None``) or the expert layer (``experts``: the keyword
    arguments of :class:`~tpusystem.ops.moe.GatedExperts`, as sorted
    pairs so that the module stays hashable)."""

    attention: tuple             # LatentAttention's arguments, as pairs
    dense_width: int
    experts: tuple | None
    dtype: jnp.dtype
    eps: float

    @nn.compact
    def __call__(self, hidden):
        dim = hidden.shape[-1]               # hidden: the float32 stream
        hidden = hidden + LatentAttention(
            dtype=self.dtype, eps=self.eps, name='attn',
            **dict(self.attention))(RMSNorm(self.eps, name='attn_norm')(
                hidden)).astype(hidden.dtype)
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


class DeepSeekV2(nn.Module):
    """DeepSeek-V2-style decoder. Defaults are the published widths
    (https://huggingface.co/deepseek-ai/DeepSeek-V2, its published configuration); use
    :func:`deepseek_tiny` on the CPU. ``held`` and ``vocab_size`` are where
    a chip's share of an expert-parallel deployment is stated: the router
    stays ``experts`` wide, the expert matrices are the ``held`` ones."""

    vocab_size: int = 102_400
    layers: int = 60
    dim: int = 5120
    heads: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_width: int = 12_288
    expert_width: int = 1536
    experts: int = 160
    experts_per_token: int = 6
    expert_groups: int = 8
    keep_groups: int = 3
    shared_experts: int = 2
    routed_scale: float = 16.0
    first_dense: int = 1
    moe_every: int = 1
    held: tuple | None = None    # (first expert held, how many); None: all
    max_seq: int = 4096
    eps: float = 1e-6
    rope_theta: float = 10_000.0
    rope_factor: float = 40.0
    rope_original: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    dtype: str = 'bfloat16'
    decode: bool = False
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    def expert_layer(self, index: int) -> bool:
        return index >= self.first_dense and index % self.moe_every == 0

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
        attention = tuple(dict(
            heads=self.heads, q_rank=self.q_rank, kv_rank=self.kv_rank,
            nope_dim=self.nope_dim, rope_dim=self.rope_dim, v_dim=self.v_dim,
            rope=(self.rope_theta, self.rope_factor, self.rope_original,
                  self.rope_beta_fast, self.rope_beta_slow, self.rope_mscale,
                  self.rope_mscale_all_dim),
            decode=self.decode, max_seq=self.max_seq,
            per_row_decode=self.per_row_decode,
            decode_pages=self.decode_pages).items())
        experts = tuple(dict(
            experts=self.experts, k=self.experts_per_token,
            width=self.expert_width, groups=self.expert_groups,
            keep_groups=self.keep_groups, scale=self.routed_scale,
            shared_width=self.shared_experts * self.expert_width,
            held=self.held).items())
        for index in range(self.layers):
            hidden = DeepSeekBlock(
                attention, self.dense_width,
                experts if self.expert_layer(index) else None, compute,
                self.eps, name=f'layer_{index}')(hidden)
        hidden = RMSNorm(self.eps, name='final_norm')(hidden)
        with jax.named_scope('head'):
            head = self.param('lm_head', nn.initializers.lecun_normal(),
                              (self.dim, self.vocab_size), jnp.float32)
            return head_logits(hidden, head.astype(compute), tied=False)


register(DeepSeekV2)


def deepseek_tiny(**overrides) -> DeepSeekV2:
    """Test scale: every mechanism of the published model at widths the
    CPU compiles in seconds (3 layers, the first dense; 16 experts in 4
    groups of which 2, 3 a token, 1 shared)."""
    config = dict(vocab_size=256, layers=3, dim=64, heads=4, q_rank=32,
                  kv_rank=16, nope_dim=16, rope_dim=8, v_dim=16,
                  dense_width=128, expert_width=48, experts=16,
                  experts_per_token=3, expert_groups=4, keep_groups=2,
                  shared_experts=1, max_seq=128, rope_original=32,
                  rope_factor=4.0, dtype='float32')
    config.update(overrides)
    return DeepSeekV2(**config)
