"""Llama-3 language model family (the 8B aggregate behind a sharded
``Service.handler``).

The reference framework ships only an MNIST MLP (SURVEY.md §2.2,
``examples/tinysys/modules/mlp.py``); the 8B-scale decoder family is part of
the capability level this framework must supply (SURVEY.md §6).

TPU-first choices mirror :mod:`tpusystem.models.gpt2`: bfloat16 activations
with float32 RMSNorm/softmax/loss, float32 master weights cast per-use, and
Megatron-style partition rules shipped with the model so the
``TensorParallel``/``FullyShardedDataParallel`` policies shard it without
per-experiment configuration. Llama-specific pieces: rotary position
embeddings (no learned position table), grouped-query attention (8 KV heads
at 8B — KV broadcast happens inside
:func:`tpusystem.ops.attention.dot_product_attention`), SwiGLU FFN, RMSNorm,
no biases anywhere, untied LM head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from tpusystem.ops.attention import attend
from tpusystem.ops.precision import head_logits
from tpusystem.registry import register


def rotary_embedding(positions: jax.Array, head_dim: int,
                     theta: float = 500_000.0) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables of shape [*positions.shape, head_dim/2], float32.

    ``positions`` is ``[len]`` for training/prefill or ``[batch, len]``
    when rows decode at independent cursors (speculative decoding)."""
    frequencies = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = positions.astype(jnp.float32)[..., None] * frequencies
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(tensor: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [batch, len, heads, head_dim] pairs (x_even, x_odd) by the
    position angle. Runs in float32, returns in the input dtype. Tables
    are [len, head_dim/2] (shared across the batch) or
    [batch, len, head_dim/2] (per-row positions)."""
    dtype = tensor.dtype
    paired = tensor.astype(jnp.float32).reshape(*tensor.shape[:-1], -1, 2)
    even, odd = paired[..., 0], paired[..., 1]
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    rotated = jnp.stack(
        (even * cos - odd * sin, even * sin + odd * cos), axis=-1)
    return rotated.reshape(tensor.shape).astype(dtype)


class _HeadKernel(nn.Module):
    """Bare ``kernel`` parameter under the module's scope — what ``nn.Dense``
    would create (same path, same initializer), but retrievable so the
    fused-loss path can pass the table to the criterion."""

    dim: int
    vocab: int

    @nn.compact
    def __call__(self):
        return self.param('kernel', nn.initializers.lecun_normal(),
                          (self.dim, self.vocab))


class RMSNorm(nn.Module):
    """Root-mean-square normalization in float32 (bf16-safe)."""

    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, hidden):
        dtype = hidden.dtype
        hidden = hidden.astype(jnp.float32)
        scale = self.param('scale', nn.initializers.ones, (hidden.shape[-1],))
        variance = jnp.mean(jnp.square(hidden), axis=-1, keepdims=True)
        return (hidden * jax.lax.rsqrt(variance + self.epsilon)
                * scale).astype(dtype)


class LlamaAttention(nn.Module):
    """Causal grouped-query attention with rotary embeddings.

    ``kernel='xla'`` (default) keeps the separate KV-head count through
    :func:`dot_product_attention` (which broadcasts KV over query-head
    groups); 'flash'/'ring'/'ulysses' kernels take full-head tensors, so KV
    is repeated up front for them.
    """

    heads: int
    kv_heads: int
    dtype: jnp.dtype
    rope_theta: float = 500_000.0
    kernel: str = 'xla'
    mesh: object = None
    decode: bool = False
    max_seq: int = 8192
    per_row_decode: bool = False  # per-row cache cursors (speculative decoding)
    decode_pages: tuple | None = None  # (num_blocks, block_size): paged
    # block-pool KV cache with per-row block tables (the serving engine's
    # layout — ops.attention.paged_attention)

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        dim = hidden.shape[-1]
        head_dim = dim // self.heads
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        query = dense(self.heads * head_dim, 'q')(hidden)
        key = dense(self.kv_heads * head_dim, 'k')(hidden)
        value = dense(self.kv_heads * head_dim, 'v')(hidden)
        batch, length = hidden.shape[:2]
        query = query.reshape(batch, length, self.heads, head_dim)
        key = key.reshape(batch, length, self.kv_heads, head_dim)
        value = value.reshape(batch, length, self.kv_heads, head_dim)

        if self.decode:
            # rotary runs at absolute positions: peek at the per-row cache
            # cursor ([batch] — declared and advanced by cached_attention;
            # absent on the prefill call, where every offset is 0)
            cursor = (self.get_variable('cache', 'index')
                      if self.has_variable('cache', 'index')
                      else jnp.zeros((batch,), jnp.int32))
            positions = cursor[:, None] + jnp.arange(length)
        else:
            positions = jnp.arange(length)
        cos, sin = rotary_embedding(positions, head_dim, self.rope_theta)
        query = apply_rotary(query, cos, sin)
        key = apply_rotary(key, cos, sin)

        if self.decode:
            from tpusystem.ops.attention import cached_attention
            context = cached_attention(self, query, key, value, self.max_seq,
                                       per_row=self.per_row_decode,
                                       pages=self.decode_pages)
        else:
            context = attend(query, key, value, kernel=self.kernel,
                             mesh=self.mesh, causal=True)
        context = context.reshape(batch, length, dim)
        return dense(dim, 'out')(context)


class LlamaBlock(nn.Module):
    """Pre-RMSNorm transformer block with a SwiGLU FFN."""

    heads: int
    kv_heads: int
    ffn_dim: int
    dtype: jnp.dtype
    rope_theta: float = 500_000.0
    attention: str = 'xla'
    mesh: object = None
    decode: bool = False
    max_seq: int = 8192
    per_row_decode: bool = False
    decode_pages: tuple | None = None  # paged KV pool (see LlamaAttention)
    schedule: object = None  # parallel.OverlapSchedule composing the
    # SwiGLU TP collectives ('gspmd' | 'overlap' rings) with FSDP prefetch
    # (see gpt2.Block.schedule); None -> every axis on GSPMD

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        from tpusystem.parallel.schedule import (resolve_schedule,
                                                 schedule_applicable,
                                                 scheduled_swiglu)
        schedule = resolve_schedule(self.schedule)
        dim = hidden.shape[-1]
        normed = RMSNorm(name='attn_norm')(hidden)
        hidden = hidden + LlamaAttention(
            self.heads, self.kv_heads, self.dtype, self.rope_theta,
            kernel=self.attention, mesh=self.mesh, decode=self.decode,
            max_seq=self.max_seq, per_row_decode=self.per_row_decode,
            decode_pages=self.decode_pages,
            name='attn')(normed, train)
        normed = RMSNorm(name='ffn_norm')(hidden)
        from tpusystem.parallel.overlap import DenseParams
        # init ALWAYS takes the nn.Dense path below (see gpt2.Block: the
        # legacy threefry's draws depend on the sharding the manual
        # region imposes inside a scanned init program — nn.Dense is the
        # single init authority, the schedule a pure apply-time knob)
        if (not self.is_initializing()
                and schedule_applicable(schedule, self.mesh, normed.shape,
                                        self.ffn_dim)):
            # the scheduled SwiGLU (parallel/schedule.py): one ring
            # all-gathers the sequence rows into the fused gate|up matmul
            # and the down matmul reduce-scatters them back (decomposed
            # when schedule.tp='overlap'), and with schedule.fsdp=
            # 'prefetch' the three kernels enter still FSDP-sharded —
            # gathered at FFN entry so the transfers hide under the
            # upstream matmuls, grads reduce-scattered off the backward
            # critical path. Same param paths as nn.Dense, so the knob
            # never changes a checkpoint; non-tiling shapes fall through
            # to the GSPMD path below.
            w_gate, _ = DenseParams(self.ffn_dim, use_bias=False,
                                    name='gate')(dim)
            w_up, _ = DenseParams(self.ffn_dim, use_bias=False,
                                  name='up')(dim)
            w_down, _ = DenseParams(dim, use_bias=False,
                                    name='down')(self.ffn_dim)
            return hidden + scheduled_swiglu(
                normed, w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype), self.mesh, schedule=schedule)
        dense = lambda features, name: nn.Dense(
            features, use_bias=False, dtype=self.dtype, name=name)
        gated = nn.silu(dense(self.ffn_dim, 'gate')(normed)) \
            * dense(self.ffn_dim, 'up')(normed)
        return hidden + dense(dim, 'down')(gated)


class LlamaBlockSpan(nn.Module):
    """``span`` consecutive LlamaBlocks — the ``scan_unit``
    grouping that keeps deep scanned stacks under the TPU compiler's
    nested-loop cliff (see :class:`tpusystem.models.gpt2.BlockSpan`): an
    outer steps-loop over a layer-scan longer than ~8 iterations sends
    the AOT compile from seconds to >10 minutes, so the 32-layer 8B scans
    8 spans of 4."""

    heads: int
    kv_heads: int
    ffn_dim: int
    dtype: jnp.dtype
    rope_theta: float = 500_000.0
    span: int = 4
    attention: str = 'xla'
    mesh: object = None
    decode: bool = False
    max_seq: int = 8192
    per_row_decode: bool = False
    decode_pages: tuple | None = None  # paged KV pool (see LlamaAttention)
    schedule: object = None  # OverlapSchedule (see LlamaBlock.schedule)

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        for index in range(self.span):
            hidden = LlamaBlock(self.heads, self.kv_heads, self.ffn_dim,
                                self.dtype, self.rope_theta,
                                attention=self.attention, mesh=self.mesh,
                                decode=self.decode, max_seq=self.max_seq,
                                per_row_decode=self.per_row_decode,
                                decode_pages=self.decode_pages,
                                schedule=self.schedule,
                                name=f'd_{index}')(hidden, train)
        return hidden


class Llama(nn.Module):
    """Llama-3-style decoder-only transformer.

    Defaults are the 8B shape (vocab 128256, 32 x 4096, 32 heads / 8 KV
    heads, SwiGLU 14336, RoPE theta 5e5). Use :func:`llama3_8b` /
    :func:`llama_tiny` presets.
    """

    vocab_size: int = 128_256
    layers: int = 32
    dim: int = 4096
    heads: int = 32
    kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq: int = 8192
    rope_theta: float = 500_000.0
    dtype: str = 'bfloat16'
    attention: str = 'xla'
    mesh: object = None
    remat: bool = False
    scan_layers: bool = False  # one lax.scan over stacked block params
    # instead of 32 unrolled copies: XLA compiles ONE block body, so 8B
    # compile time stops scaling with depth; params live under 'blocks'
    # with a leading layer dim (see partition_rules)
    scan_unit: int = 1  # layers per scan step (see gpt2.GPT2.scan_unit):
    # group k blocks per LlamaBlockSpan so the scan length is layers/k —
    # keep layers/k <= 8 when the step runs inside a compiled steps-loop
    # (the TPU backend's nested-loop cliff)
    return_features: bool = False  # return (features, head kernel) for a
    # fused chunked LM loss (train.ChunkedNextTokenLoss); at 128k vocab the
    # full f32 logits tensor is the dominant memory term
    decode: bool = False  # KV-cache autoregressive decoding (see
    # tpusystem.train.generate; apply with mutable=['cache'])
    per_row_decode: bool = False  # per-row cache cursors for speculative
    # decoding (scatter writes); False = ordinary decode, shared-cursor
    # dynamic_update_slice cache writes
    decode_pages: tuple | None = None  # (num_blocks, block_size): paged
    # block-pool KV cache with per-row block tables — the serving
    # engine's layout (tpusystem.serve; ops.attention.paged_attention)
    schedule: object = None  # parallel.OverlapSchedule: ONE knob composing
    # the SwiGLU TP collectives (tp='gspmd': monolithic
    # partitioner-inserted all-gather/reduce-scatter | tp='overlap':
    # decomposed latency-hiding ring matmuls — parallel/overlap.py; needs
    # a mesh with model > 1, falls back per-shape otherwise) with FSDP
    # param-prefetch/grad-scatter hiding (see gpt2.GPT2.schedule); None
    # keeps every axis on GSPMD. The pp=/moe= arms ride the same object
    # but are inert in this family (no pipelined/MoE Llama variant yet —
    # pass the one schedule everywhere and each model consumes the arms
    # it has).
    # Param trees and checkpoints are bitwise knob-invariant

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        compute_dtype = jnp.dtype(self.dtype)
        assert tokens.shape[-1] <= self.max_seq, (
            f'sequence length {tokens.shape[-1]} exceeds max_seq={self.max_seq}')
        hidden = nn.Embed(self.vocab_size, self.dim, dtype=jnp.float32,
                          name='embed')(tokens)
        hidden = hidden.astype(compute_dtype)
        block_cls = (nn.remat(LlamaBlock, static_argnums=(2,))
                     if self.remat else LlamaBlock)
        if self.scan_layers:
            # one compiled block body + stacked params: compile time is
            # O(1) in depth. Decode scans too: the per-layer KV caches ride
            # the scan via variable_axes={'cache': 0} (each layer slice
            # owns its cache at a leading layer dim).
            if self.scan_unit > 1:
                if self.layers % self.scan_unit:
                    raise ValueError(
                        f'scan_unit={self.scan_unit} must divide layers '
                        f'({self.layers})')
                span_cls = (nn.remat(LlamaBlockSpan, static_argnums=(2,))
                            if self.remat else LlamaBlockSpan)
                template = span_cls(self.heads, self.kv_heads,
                                    self.ffn_dim, compute_dtype,
                                    self.rope_theta, span=self.scan_unit,
                                    attention=self.attention,
                                    mesh=self.mesh, decode=self.decode,
                                    max_seq=self.max_seq,
                                    per_row_decode=self.per_row_decode,
                                    decode_pages=self.decode_pages,
                                    schedule=self.schedule,
                                    name='blocks')
                length = self.layers // self.scan_unit
            else:
                template = block_cls(self.heads, self.kv_heads,
                                     self.ffn_dim, compute_dtype,
                                     self.rope_theta,
                                     attention=self.attention,
                                     mesh=self.mesh, decode=self.decode,
                                     max_seq=self.max_seq,
                                     per_row_decode=self.per_row_decode,
                                     decode_pages=self.decode_pages,
                                     schedule=self.schedule,
                                     name='blocks')
                length = self.layers
            from tpusystem.parallel.mesh import scan_carry_constraint
            constrain = scan_carry_constraint(self.mesh)
            scan = nn.scan(
                lambda block, carry, _: (block(constrain(carry), train),
                                         None),
                variable_axes={'params': 0, 'cache': 0},
                split_rngs={'params': True},
                length=length)
            hidden, _ = scan(template, hidden, None)
        else:
            for index in range(self.layers):
                hidden = block_cls(self.heads, self.kv_heads, self.ffn_dim,
                                   compute_dtype, self.rope_theta,
                                   attention=self.attention, mesh=self.mesh,
                                   decode=self.decode, max_seq=self.max_seq,
                                   per_row_decode=self.per_row_decode,
                                   decode_pages=self.decode_pages,
                                   schedule=self.schedule,
                                   name=f'layer_{index}')(hidden, train)
        hidden = RMSNorm(name='final_norm')(hidden)
        # untied head (Llama-3 convention). bf16 x bf16 operands at MXU
        # rate, f32 accumulation out for a stable softmax/loss. The kernel
        # lives in a param holder (same 'lm_head/kernel' path a Dense would
        # use) so the fused-loss path can hand it to the criterion.
        kernel = _HeadKernel(self.dim, self.vocab_size, name='lm_head')()
        table = kernel.astype(compute_dtype)
        if self.return_features:
            return hidden, table
        return head_logits(hidden, table, tied=False)

    @staticmethod
    def partition_rules():
        """Megatron-style TP rules: q/k/v/gate/up split columns on ``model``;
        out/down split rows (their all-reduce rides ICI); embedding and head
        split the vocab dimension. The ``blocks`` rules cover the
        ``scan_layers`` stacked variant (same splits shifted one dim right
        past the leading layer axis)."""
        return (
            # `blocks/.*` covers both the plain scanned stack and the
            # LlamaBlockSpan nesting (blocks/d_0/attn/...) — either way
            # one leading layer/span dim shifts the spec right
            (r'blocks/.*attn/(q|k|v)/kernel$', P(None, None, 'model')),
            (r'blocks/.*attn/out/kernel$', P(None, 'model', None)),
            (r'blocks/.*(gate|up)/kernel$', P(None, None, 'model')),
            (r'blocks/.*down/kernel$', P(None, 'model', None)),
            (r'attn/(q|k|v)/kernel$', P(None, 'model')),
            (r'attn/out/kernel$', P('model', None)),
            (r'(gate|up)/kernel$', P(None, 'model')),
            (r'down/kernel$', P('model', None)),
            (r'embed/embedding$', P('model', None)),
            (r'lm_head/kernel$', P(None, 'model')),
        )


register(Llama, excluded_kwargs={'mesh'})


def llama3_8b(**overrides) -> Llama:
    """The 8B preset (== class defaults), gradient checkpointing on."""
    config = dict(remat=True)
    config.update(overrides)
    return Llama(**config)


def llama_tiny(**overrides) -> Llama:
    """Test/dry-run scale: compiles in seconds on CPU."""
    config = dict(vocab_size=256, layers=2, dim=64, heads=4, kv_heads=2,
                  ffn_dim=128, max_seq=128)
    config.update(overrides)
    return Llama(**config)
