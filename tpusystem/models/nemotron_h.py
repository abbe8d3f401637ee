"""Nemotron-H language model family: a decoder built from a **pattern
string**, one mixer a layer.

``pattern[l]`` names layer ``l``'s one mixer behind its one RMSNorm —
``x <- x + mixer_l(norm_l(x))``:

* ``M`` — a Mamba-2 state-space layer (:class:`tpusystem.ops.ssm.Mamba2`):
  its cache is a per-row float32 state and the convolution's last inputs,
  not keys and values;
* ``*`` — grouped-query attention (:class:`GroupedQueryAttention`) with
  **no positional term** (position reaches the model through the
  state-space layers), cached as Llama's is: ``key``/``value`` leaves, the
  paged pool through :func:`tpusystem.ops.attention.paged_attention`;
* ``E`` — an expert layer (:class:`tpusystem.ops.moe.GatedExperts` with
  ``scoring='sigmoid'``, ``form='relu2'``): sigmoid scores, the top ``k`` of
  score + learned correction, weights renormalised over the chosen and
  scaled, two-matrix ``relu²`` experts (stored with both dimensions
  rounded up to multiples of ``expert_pad``, 2688 x 1856 -> 2816 x 2048,
  the padding read by nothing: ``GatedExperts.pad_to``), one shared expert
  of the same form, and ``held = (start, count)`` naming the experts whose matrices live on
  this chip (one chip's share of an expert-parallel deployment).

After the last layer a final RMSNorm and an untied head. No dropout, no
bias but the convolution's. The family decode conventions (``decode``,
``max_seq``, ``per_row_decode``, ``decode_pages`` — what
:func:`tpusystem.train.generate._decoder` and the serving engine clone)
hold, with one addition: ``__call__`` takes ``length``, the number of real
positions of a right-padded prompt, which the state-space layers need (a
recurrence sees its padding; attention does not) and the serving engine's
prefill hands in.

Parameters may be handed in any float type. The residual stream, the norms,
the router's scores, the softmax and the state-space layers' steps, decays
and state run in float32; matrix products take ``dtype`` operands and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpusystem.models.llama import RMSNorm
from tpusystem.ops.attention import attend, cached_attention
from tpusystem.ops.moe import GatedExperts
from tpusystem.ops.precision import head_logits
from tpusystem.ops.ssm import Mamba2
from tpusystem.registry import register

PUBLISHED_PATTERN = 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'


class GroupedQueryAttention(nn.Module):
    """Causal grouped-query attention, no rotary or other positional term:
    query head ``i`` attends key/value head ``i // (heads / kv_heads)`` at
    scale ``head_dim^-1/2``. The cache is
    :func:`~tpusystem.ops.attention.cached_attention`'s (scopes
    ``kv_write``/``kv_read`` on the paged pool); the projections run under
    ``attn_proj``."""

    heads: int
    kv_heads: int
    head_dim: int
    dtype: jnp.dtype
    decode: bool = False
    max_seq: int = 4096
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @nn.compact
    def __call__(self, hidden):
        batch, length, dim = hidden.shape
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope('attn_proj'):
            query = dense(self.heads * self.head_dim, 'q')(hidden).reshape(
                batch, length, self.heads, self.head_dim)
            key = dense(self.kv_heads * self.head_dim, 'k')(hidden).reshape(
                batch, length, self.kv_heads, self.head_dim)
            value = dense(self.kv_heads * self.head_dim, 'v')(hidden).reshape(
                batch, length, self.kv_heads, self.head_dim)
        if self.decode:
            context = cached_attention(self, query, key, value, self.max_seq,
                                       per_row=self.per_row_decode,
                                       pages=self.decode_pages)
        else:
            context = attend(query, key, value, kernel='xla', causal=True)
        with jax.named_scope('attn_proj'):
            return dense(dim, 'out')(context.reshape(
                batch, length, self.heads * self.head_dim))


class HybridLayer(nn.Module):
    """``x + mixer(norm(x))``: one RMSNorm and one mixer, whatever its kind.
    Only a state-space mixer is told the true ``length``."""

    mixer: nn.Module
    eps: float

    @nn.compact
    def __call__(self, hidden, length=None):
        normed = RMSNorm(self.eps, name='norm')(hidden)   # hidden: float32
        mixed = self.mixer(normed, length) \
            if isinstance(self.mixer, Mamba2) else self.mixer(normed)
        return hidden + mixed.astype(hidden.dtype)


class NemotronH(nn.Module):
    """Nemotron-H-style hybrid decoder. Defaults are the published widths
    of NVIDIA-Nemotron-3-Nano-30B-A3B
    (https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
    its published configuration); use :func:`nemotron_tiny` on the CPU.
    ``pattern``, ``held`` and ``vocab_size`` are where a chip's share of a
    deployment is stated: the layers kept, the experts whose matrices are
    here (the router stays ``experts`` wide), the rows of the table and the
    head."""

    vocab_size: int = 131_072
    pattern: str = PUBLISHED_PATTERN
    dim: int = 2688
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    expert_width: int = 1856
    shared_width: int = 3712
    experts: int = 128
    experts_per_token: int = 6
    routed_scale: float = 2.5
    expert_pad: int = 256        # GatedExperts.pad_to
    held: tuple | None = None    # (first expert held, how many); None: all
    max_seq: int = 4096
    eps: float = 1e-5
    dtype: str = 'bfloat16'
    decode: bool = False
    per_row_decode: bool = False
    decode_pages: tuple | None = None

    @property
    def layers(self) -> int:
        return len(self.pattern)

    def mixer(self, kind: str, compute):
        """Layer kind -> its mixer, unbound (the layer adopts it)."""
        if kind == 'M':
            return Mamba2(
                heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                groups=self.ssm_groups, state=self.ssm_state,
                conv_kernel=self.conv_kernel, chunk=self.chunk, eps=self.eps,
                dtype=compute, decode=self.decode, parent=None)
        if kind == '*':
            return GroupedQueryAttention(
                heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, dtype=compute, decode=self.decode,
                max_seq=self.max_seq, per_row_decode=self.per_row_decode,
                decode_pages=self.decode_pages, parent=None)
        if kind == 'E':
            return GatedExperts(
                experts=self.experts, k=self.experts_per_token,
                width=self.expert_width, scale=self.routed_scale,
                shared_width=self.shared_width, held=self.held,
                dtype=compute, scoring='sigmoid', form='relu2',
                pad_to=self.expert_pad, parent=None)
        raise ValueError(f'pattern {self.pattern!r} holds {kind!r}: a layer '
                         "is 'M' (Mamba-2), '*' (attention) or 'E' (experts)")

    @nn.compact
    def __call__(self, tokens, train: bool = False, length=None):
        """``length`` (a scalar or ``[batch]``; None: all of them): how many
        of ``tokens``' positions are real, the rest being right-padding."""
        del train                       # no dropout anywhere
        compute = jnp.dtype(self.dtype)
        assert tokens.shape[-1] <= self.max_seq, (
            f'sequence length {tokens.shape[-1]} exceeds max_seq='
            f'{self.max_seq}')
        with jax.named_scope('embed'):
            table = self.param('embedding', nn.initializers.normal(0.02),
                               (self.vocab_size, self.dim), jnp.float32)
            hidden = jnp.take(table, tokens, axis=0).astype(jnp.float32)
        for index, kind in enumerate(self.pattern):
            hidden = HybridLayer(self.mixer(kind, compute), self.eps,
                                 name=f'layer_{index}')(hidden, length)
        hidden = RMSNorm(self.eps, name='final_norm')(hidden)
        with jax.named_scope('head'):
            head = self.param('lm_head', nn.initializers.lecun_normal(),
                              (self.dim, self.vocab_size), jnp.float32)
            return head_logits(hidden, head.astype(compute), tied=False)


register(NemotronH)


def nemotron_tiny(**overrides) -> NemotronH:
    """Test scale: every mechanism of the published model at widths the CPU
    compiles in seconds — all three kinds of layer, 2 state-space groups of 2
    heads, 2 key/value heads under 4 query heads, 8 experts of which 3 a
    token and 4 held and stored padded (64 x 48 -> 64 x 64), a chunk (8) that
    prompts do not divide."""
    config = dict(vocab_size=256, pattern='ME*ME', dim=64, ssm_heads=4,
                  ssm_head_dim=8, ssm_groups=2, ssm_state=16, chunk=8,
                  heads=4, kv_heads=2, head_dim=16, expert_width=48,
                  shared_width=96, experts=8, experts_per_token=3,
                  expert_pad=32, held=(2, 4), max_seq=128, dtype='float32')
    config.update(overrides)
    return NemotronH(**config)
