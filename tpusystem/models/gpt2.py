"""GPT-2 language model (the flagship: the LM aggregate under GSPMD
FSDP; both cells of ``BENCHMARK.json`` run it).

TPU-first choices: bfloat16 activations with float32 layernorm/softmax/loss,
weights kept float32 (master copies) and cast per-use; attention through
:func:`tpusystem.ops.attention.dot_product_attention`; Megatron-style tensor
partition rules shipped with the model (``GPT2.partition_rules()``) so the
``TensorParallel``/``FullyShardedDataParallel`` policies shard it without
per-experiment configuration.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from tpusystem.ops.attention import attend
from tpusystem.ops.precision import head_logits
from tpusystem.registry import register

# Megatron TP splits for one transformer block's leaf paths: qkv/fc split
# columns on `model`, out/proj split rows (their all-reduce rides ICI).
# Single source for every layout: GPT2.partition_rules uses them plain and
# shifted past the `hs` scan dim; GPT2Pipelined feeds them to
# PipelineParallel(stacked_rules=...) shifted past the stage dim(s).
BLOCK_TP_RULES = (
    (r'attn/qkv/kernel$', P(None, 'model')),
    (r'attn/out/kernel$', P('model', None)),
    (r'fc/kernel$', P(None, 'model')),
    (r'proj/kernel$', P('model', None)),
)


class SelfAttention(nn.Module):
    """Causal multi-head self-attention with a pluggable kernel.

    ``kernel='xla'`` (default) is einsum attention that GSPMD shards freely —
    required under the DP/FSDP/TP policies, since a Pallas call cannot be
    auto-partitioned. ``'flash'`` is the Pallas O(seq)-memory kernel for
    single-chip runs; ``'ring'``/``'ulysses'`` are the sequence-parallel
    variants (shard_map over the mesh's seq axis).

    ``attn_dropout=None`` (default) applies ``dropout`` to the attention
    probabilities on the 'xla' and 'flash' kernels — the torch-reference
    behavior ('flash' drops in-kernel via positional hash masks) — and 0.0
    on the sequence-parallel kernels; set it explicitly to override.
    """

    heads: int
    dropout: float
    dtype: jnp.dtype
    kernel: str = 'xla'    # 'xla' | 'flash' (Pallas) | 'ring' | 'ulysses'
    mesh: object = None    # required for 'ring'/'ulysses' (seq-sharded)
    attn_dropout: float | None = None  # None -> follow `dropout`
    decode: bool = False   # KV-cache incremental decoding (xla kernel only)
    max_seq: int = 1024    # cache capacity when decoding
    per_row_decode: bool = False  # per-row cache cursors (speculative decoding)
    decode_pages: tuple | None = None  # (num_blocks, block_size): paged
    # block-pool KV cache with per-row block tables (the serving engine's
    # layout — ops.attention.paged_attention)

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        if self.attn_dropout is None:
            attn_dropout = (self.dropout if self.kernel in ('xla', 'flash')
                            else 0.0)
        else:
            attn_dropout = self.attn_dropout
            if attn_dropout and self.kernel not in ('xla', 'flash'):
                raise ValueError(
                    "attention-probability dropout is only implemented on "
                    f"the 'xla' and 'flash' kernels, not {self.kernel!r}")
        dim = hidden.shape[-1]
        head_dim = dim // self.heads
        qkv = nn.Dense(3 * dim, dtype=self.dtype, name='qkv')(hidden)
        query, key, value = jnp.split(qkv, 3, axis=-1)
        shape = hidden.shape[:2] + (self.heads, head_dim)
        query, key, value = (t.reshape(shape) for t in (query, key, value))
        if self.decode:
            from tpusystem.ops.attention import cached_attention
            context = cached_attention(self, query, key, value, self.max_seq,
                                       per_row=self.per_row_decode,
                                       pages=self.decode_pages)
        else:
            dropout = attn_dropout if train else 0.0
            context = attend(
                query, key, value, kernel=self.kernel, mesh=self.mesh,
                causal=True, dropout=dropout,
                dropout_rng=self.make_rng('dropout') if dropout else None)
        context = context.reshape(hidden.shape)
        return nn.Dense(dim, dtype=self.dtype, name='out')(context)


class Block(nn.Module):
    """Transformer block. With ``moe_experts > 0`` the FFN is an
    expert-parallel :class:`~tpusystem.ops.moe.MoEMLP` and the block
    returns ``(hidden, aux_loss)`` instead of ``hidden``."""

    heads: int
    mlp_ratio: int
    dropout: float
    dtype: jnp.dtype
    attention: str = 'xla'
    mesh: object = None
    attn_dropout: float | None = None
    decode: bool = False
    max_seq: int = 1024
    per_row_decode: bool = False
    decode_pages: tuple | None = None  # paged KV pool (see SelfAttention)
    moe_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_exchange: str = 'quota'
    moe_sparse_impl: str = 'gather'  # single-shard row movement:
    # 'gather' | 'scatter' | 'fused' (Pallas grouped gather-matmul)
    schedule: object = None  # OverlapSchedule composing the dense-FFN TP
    # collectives ('gspmd': monolithic all-gather/reduce-scatter inserted
    # by the partitioner | 'overlap': decomposed latency-hiding ring
    # matmuls, parallel/overlap.py) with FSDP param-prefetch/grad-scatter
    # under one knob (parallel/schedule.py); None -> every axis on GSPMD

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        from tpusystem.parallel.schedule import resolve_schedule
        schedule = resolve_schedule(self.schedule)
        dim = hidden.shape[-1]
        normed = nn.LayerNorm(dtype=jnp.float32, name='ln_1')(hidden)
        attended = SelfAttention(self.heads, self.dropout, self.dtype,
                                 kernel=self.attention, mesh=self.mesh,
                                 attn_dropout=self.attn_dropout,
                                 decode=self.decode, max_seq=self.max_seq,
                                 per_row_decode=self.per_row_decode,
                                 decode_pages=self.decode_pages,
                                 name='attn')(
            normed.astype(self.dtype), train)
        attended = nn.Dropout(self.dropout, deterministic=not train)(attended)
        hidden = hidden + attended
        normed = nn.LayerNorm(dtype=jnp.float32, name='ln_2')(hidden)
        if self.moe_experts:
            from tpusystem.ops.moe import MoEMLP
            # the schedule's moe= arm reaches the expert dispatch here:
            # with moe='overlap' the sharded quota exchange pipelines its
            # all_to_all under the expert matmuls (ops/moe.py)
            # decode dispatches at FULL capacity: no drops, so a token's
            # expert mix is independent of co-batched traffic and of the
            # serving engine's pad buckets — the engine's token-exactness
            # contract (training keeps the capacity_factor economics)
            shrunk, aux = MoEMLP(self.moe_experts, k=self.moe_k,
                                 mlp_ratio=self.mlp_ratio,
                                 capacity_factor=self.moe_capacity_factor,
                                 dtype=self.dtype, mesh=self.mesh,
                                 exchange=self.moe_exchange,
                                 sparse_impl=self.moe_sparse_impl,
                                 schedule=schedule,
                                 full_capacity=self.decode,
                                 name='moe')(normed.astype(self.dtype))
        else:
            from tpusystem.parallel.overlap import DenseParams
            from tpusystem.parallel.schedule import (schedule_applicable,
                                                     scheduled_ffn)
            grown_features = self.mlp_ratio * dim
            # init ALWAYS takes the nn.Dense path below: the legacy
            # (non-partitionable) threefry generates different bits when
            # the scanned init program shards the drawn kernels through
            # the manual region's in_specs, so routing init through the
            # scheduled branch would silently change the draws on
            # composed fsdp x model meshes — nn.Dense is the single init
            # authority, the schedule a pure apply-time knob
            if (not self.is_initializing()
                    and schedule_applicable(schedule, self.mesh,
                                            normed.shape, grown_features)):
                # the scheduled FFN (parallel/schedule.py): the sequence
                # rows all-gather INTO the fc matmul and the proj matmul
                # reduce-scatters them back (decomposed rings when
                # schedule.tp='overlap'), and with schedule.fsdp=
                # 'prefetch' the kernels enter still FSDP-sharded — their
                # gathers issue at FFN entry (the proj kernel's transfer
                # hides under the fc matmul) and the grad reduce-scatter
                # is deferred off the backward critical path. Params are
                # created at nn.Dense's exact paths, so the knob never
                # changes a checkpoint; shapes that cannot tile fall
                # through to the GSPMD Dense path below.
                w_fc, b_fc = DenseParams(grown_features, name='fc')(dim)
                w_proj, b_proj = DenseParams(dim, name='proj')(grown_features)
                shrunk = scheduled_ffn(
                    normed.astype(self.dtype),
                    w_fc.astype(self.dtype), b_fc.astype(self.dtype),
                    w_proj.astype(self.dtype), b_proj.astype(self.dtype),
                    self.mesh, schedule=schedule, activation=nn.gelu)
            else:
                grown = nn.Dense(self.mlp_ratio * dim, dtype=self.dtype,
                                 name='fc')(normed.astype(self.dtype))
                grown = nn.gelu(grown)
                shrunk = nn.Dense(dim, dtype=self.dtype, name='proj')(grown)
            aux = None
        shrunk = nn.Dropout(self.dropout, deterministic=not train)(shrunk)
        hidden = hidden + shrunk
        return (hidden, aux) if self.moe_experts else hidden


class BlockSpan(nn.Module):
    """``span`` consecutive blocks; with ``moe_experts > 0`` every
    ``moe_every``-th block in the span is MoE.

    The homogeneous unit that lets heterogeneous/deep stacks ride
    ``nn.scan``: scanning over ``layers/span`` identical spans compiles
    ONE span body instead of unrolling. Two composable uses:

    * MoE-every-k: ``span`` a multiple of ``moe_every`` — block index
      ``i`` is MoE iff ``i % moe_every == moe_every - 1`` (params under
      ``moe_{i}``, dense under ``d_{i}``); returns ``(hidden, aux)`` with
      ``aux`` the mean router loss of the span's MoE blocks.
    * ``scan_unit`` grouping (``moe_experts == 0``): k dense layers per
      scan step keep the scan length under the TPU compiler's
      nested-loop cliff (an outer steps-loop over a layer-scan longer
      than ~8 iterations sends the AOT compile from seconds to >10
      minutes); returns ``hidden`` alone."""

    heads: int
    mlp_ratio: int
    dropout: float
    dtype: jnp.dtype
    span: int = 2
    attention: str = 'xla'
    mesh: object = None
    attn_dropout: float | None = None
    decode: bool = False
    max_seq: int = 1024
    per_row_decode: bool = False
    decode_pages: tuple | None = None  # paged KV pool (see SelfAttention)
    moe_experts: int = 0
    moe_every: int = 2
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_exchange: str = 'quota'
    moe_sparse_impl: str = 'gather'  # single-shard row movement:
    # 'gather' | 'scatter' | 'fused' (Pallas grouped gather-matmul)
    schedule: object = None  # OverlapSchedule (see Block.schedule)

    @nn.compact
    def __call__(self, hidden, train: bool = False):
        common = dict(attention=self.attention, mesh=self.mesh,
                      attn_dropout=self.attn_dropout, decode=self.decode,
                      max_seq=self.max_seq,
                      per_row_decode=self.per_row_decode,
                      decode_pages=self.decode_pages,
                      schedule=self.schedule)
        if self.moe_experts and self.span % self.moe_every:
            raise ValueError(f'span ({self.span}) must be a multiple of '
                             f'moe_every ({self.moe_every})')
        aux_terms = []
        for index in range(self.span):
            is_moe = (self.moe_experts > 0
                      and index % self.moe_every == self.moe_every - 1)
            if is_moe:
                hidden, aux = Block(
                    self.heads, self.mlp_ratio, self.dropout, self.dtype,
                    moe_experts=self.moe_experts, moe_k=self.moe_k,
                    moe_capacity_factor=self.moe_capacity_factor,
                    moe_exchange=self.moe_exchange,
                    moe_sparse_impl=self.moe_sparse_impl,
                    name=f'moe_{index}', **common)(hidden, train)
                aux_terms.append(aux)
            else:
                hidden = Block(self.heads, self.mlp_ratio, self.dropout,
                               self.dtype, name=f'd_{index}',
                               **common)(hidden, train)
        if not aux_terms:
            return hidden
        return hidden, jnp.mean(jnp.stack(aux_terms))


class GPT2(nn.Module):
    """Decoder-only transformer with learned positions and tied LM head.

    125M preset == defaults (vocab 50257, 12 x 768, 12 heads, seq 1024).
    """

    vocab_size: int = 50257
    layers: int = 12
    dim: int = 768
    heads: int = 12
    max_seq: int = 1024
    mlp_ratio: int = 4
    dropout: float = 0.1
    dtype: str = 'bfloat16'
    attention: str = 'xla'  # 'xla' (GSPMD-shardable) | 'flash' | 'ring' | 'ulysses'
    mesh: object = None  # mesh for ring/ulysses sequence parallelism
    attn_dropout: float | None = None  # None -> follow `dropout` on the
    # 'xla' and 'flash' kernels (flash drops in-kernel), 0 elsewhere
    remat: bool = False  # recompute each block's activations in backward
    scan_layers: bool = False  # one lax.scan over stacked block params
    # instead of `layers` unrolled copies: XLA compiles ONE block body, so
    # compile time stops scaling with depth (the 32-layer 8B unroll is the
    # compile-time cliff); params live under 'hs' with a leading layer dim
    scan_unit: int = 1  # layers per scan step (scan_layers=True): group k
    # blocks into one BlockSpan body so the scan length is layers/k — the
    # TPU backend's nested-loop optimization goes super-linear when an
    # outer steps-loop wraps a layer-scan longer than ~8 iterations, so
    # deep stacks inside compiled training loops pick k with
    # layers/k <= 8 (measured: 12-layer scan in a 90-step loop >10 min
    # AOT; 6x2 compiles in seconds at identical runtime math)
    return_features: bool = False  # return (features, wte table) for a fused
    # chunked LM loss (train.ChunkedNextTokenLoss) instead of full logits
    decode: bool = False  # KV-cache autoregressive decoding (see
    # tpusystem.train.generate; apply with mutable=['cache'])
    per_row_decode: bool = False  # per-row cache cursors: cache writes use a
    # 2D gather-index scatter so rows advance independently (speculative
    # decoding); False keeps ordinary decode on the faster
    # dynamic_update_slice at the shared cursor
    decode_pages: tuple | None = None  # (num_blocks, block_size): paged
    # block-pool KV cache with per-row block tables — the serving
    # engine's layout (tpusystem.serve; ops.attention.paged_attention).
    # Implies per-row cursors; admission/eviction are host-side table
    # edits, never a cache reshape
    moe_experts: int = 0  # >0: MoE FFN in every `moe_every`-th block
    moe_every: int = 2
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_exchange: str = 'quota'  # multi-device exchange: 'quota' | 'ragged'
    # | 'ragged-emulated' (see tpusystem.ops.moe.MoEMLP)
    moe_sparse_impl: str = 'gather'  # single-shard row movement:
    # 'gather' | 'scatter' | 'fused' (Pallas grouped gather-matmul)
    schedule: object = None  # parallel.OverlapSchedule: ONE knob composing
    # the dense-FFN TP collectives (tp='gspmd': monolithic
    # partitioner-inserted all-gather/reduce-scatter | tp='overlap':
    # decomposed latency-hiding ring matmuls — parallel/overlap.py; needs
    # a mesh with model > 1, falls back per-shape otherwise) with FSDP
    # param-prefetch/grad-scatter hiding (fsdp='prefetch') and their
    # shared ppermute chunking; None keeps every axis on GSPMD. Purely an
    # implementation schedule — param trees and checkpoints are bitwise
    # knob-invariant

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        compute_dtype = jnp.dtype(self.dtype)
        if self.decode:
            # absolute positions continue from the per-row cache cursor
            # ([batch] — speculative decoding rewinds rows independently)
            offset = self.variable(
                'cache', 'position',
                lambda: jnp.zeros((tokens.shape[0],), jnp.int32))
            positions = offset.value[:, None] + jnp.arange(tokens.shape[-1])
            if not self.is_initializing():
                offset.value = offset.value + tokens.shape[-1]
        else:
            positions = jnp.arange(tokens.shape[-1])
        token_embedding = nn.Embed(self.vocab_size, self.dim,
                                   dtype=jnp.float32, name='wte')
        hidden = token_embedding(tokens)
        hidden = hidden + nn.Embed(self.max_seq, self.dim,
                                   dtype=jnp.float32, name='wpe')(positions)
        hidden = nn.Dropout(self.dropout, deterministic=not train)(hidden)
        hidden = hidden.astype(compute_dtype)
        assert tokens.shape[-1] <= self.max_seq, (
            f'sequence length {tokens.shape[-1]} exceeds max_seq={self.max_seq}')
        block_cls = nn.remat(Block, static_argnums=(2,)) if self.remat else Block
        aux_losses = []
        if self.scan_layers:
            # one compiled block body, stacked params, lax.scan over depth —
            # compile time is O(1) in layer count instead of O(layers).
            # MoE-every-k stacks scan over homogeneous (dense*, moe) SPANS
            # (BlockSpan); decode-mode KV caches scan along with the params
            # (variable_axes carries the 'cache' collection, so each layer
            # slice owns its cache at a leading layer dim).
            common = dict(attention=self.attention, mesh=self.mesh,
                          attn_dropout=self.attn_dropout,
                          decode=self.decode, max_seq=self.max_seq,
                          per_row_decode=self.per_row_decode,
                          decode_pages=self.decode_pages,
                          schedule=self.schedule)
            from tpusystem.parallel.mesh import scan_carry_constraint
            constrain = scan_carry_constraint(self.mesh)
            if self.moe_experts:
                # span = scan_unit when set (must be a multiple of
                # moe_every — the MoE pattern repeats inside the span),
                # else one moe_every group per scan step
                span_size = (self.scan_unit if self.scan_unit > 1
                             else self.moe_every)
                if span_size % self.moe_every:
                    raise ValueError(
                        f'scan_unit ({span_size}) must be a multiple of '
                        f'moe_every ({self.moe_every}) so each scanned '
                        f'span carries whole MoE groups')
                if self.layers % span_size:
                    raise ValueError(
                        f'scan_layers with moe_experts needs layers '
                        f'({self.layers}) divisible by the span '
                        f'({span_size})')
                span_cls = (nn.remat(BlockSpan, static_argnums=(2,))
                            if self.remat else BlockSpan)
                template = span_cls(self.heads, self.mlp_ratio,
                                    self.dropout, compute_dtype,
                                    span=span_size,
                                    moe_experts=self.moe_experts,
                                    moe_every=self.moe_every,
                                    moe_k=self.moe_k,
                                    moe_capacity_factor=self.moe_capacity_factor,
                                    moe_exchange=self.moe_exchange,
                                    moe_sparse_impl=self.moe_sparse_impl,
                                    name='hs', **common)
                length = self.layers // span_size
                body = lambda block, carry, _: block(constrain(carry), train)
            elif self.scan_unit > 1:
                if self.layers % self.scan_unit:
                    raise ValueError(
                        f'scan_unit={self.scan_unit} must divide layers '
                        f'({self.layers})')
                span_cls = (nn.remat(BlockSpan, static_argnums=(2,))
                            if self.remat else BlockSpan)
                template = span_cls(self.heads, self.mlp_ratio,
                                    self.dropout, compute_dtype,
                                    span=self.scan_unit, name='hs',
                                    **common)
                length = self.layers // self.scan_unit
                body = lambda block, carry, _: (block(constrain(carry),
                                                      train), None)
            else:
                template = block_cls(self.heads, self.mlp_ratio,
                                     self.dropout, compute_dtype,
                                     name='hs', **common)
                length = self.layers
                body = lambda block, carry, _: (block(constrain(carry),
                                                      train), None)
            scan = nn.scan(
                body,
                variable_axes={'params': 0, 'cache': 0},
                split_rngs={'params': True, 'dropout': True},
                length=length)
            hidden, aux_stack = scan(template, hidden, None)
            if self.moe_experts:
                aux_losses.append(jnp.mean(aux_stack))
        else:
            for index in range(self.layers):
                is_moe = (self.moe_experts > 0
                          and index % self.moe_every == self.moe_every - 1)
                block = block_cls(self.heads, self.mlp_ratio, self.dropout,
                                  compute_dtype, attention=self.attention,
                                  mesh=self.mesh,
                                  attn_dropout=self.attn_dropout,
                                  decode=self.decode, max_seq=self.max_seq,
                                  per_row_decode=self.per_row_decode,
                                  decode_pages=self.decode_pages,
                                  moe_experts=self.moe_experts if is_moe else 0,
                                  moe_k=self.moe_k,
                                  moe_capacity_factor=self.moe_capacity_factor,
                                  moe_exchange=self.moe_exchange,
                                  moe_sparse_impl=self.moe_sparse_impl,
                                  schedule=self.schedule,
                                  name=f'h_{index}')
                result = block(hidden, train)
                if is_moe:
                    hidden, aux = result
                    aux_losses.append(aux)
                else:
                    hidden = result
        hidden = nn.LayerNorm(dtype=jnp.float32, name='ln_f')(hidden)
        # tied LM head: logits against the token embedding table. The matmul
        # runs bf16 x bf16 (MXU rate) accumulating into f32 — f32 operands
        # here would put ~30% of the model's FLOPs on the slow path — and
        # the f32 logits keep the softmax/loss numerically stable.
        table = token_embedding.embedding.astype(compute_dtype)
        # MoE aux (router balance) exists only for the training loss; in
        # decode mode every output branch is aux-free
        emit_aux = self.moe_experts and not self.decode
        if self.return_features:
            # fused-head path: the criterion owns the head matmul and never
            # materializes the [batch*seq, vocab] f32 logits tensor
            features = hidden.astype(compute_dtype)
            if emit_aux:
                aux = jnp.mean(jnp.stack(aux_losses)) if aux_losses else jnp.float32(0)
                return (features, table), aux
            return features, table
        logits = head_logits(hidden.astype(compute_dtype), table, tied=True)
        if emit_aux:
            # arity is fixed by configuration, not by which layers happened
            # to be MoE, so the WithAuxLoss pairing can't be broken by a
            # (layers, moe_every) combination that selects no layer. In
            # decode mode the aux (router-balance) term is meaningless —
            # logits only, so generation works on MoE models too. Caveat:
            # expert capacity derives from the call's token count, so a
            # decode step (batch tokens) effectively never drops, while a
            # training-shaped forward (batch*seq tokens) may — decode
            # matches it exactly only where the training forward drops
            # nothing (capacity-based MoE's standard decode asymmetry).
            aux = jnp.mean(jnp.stack(aux_losses)) if aux_losses else jnp.float32(0)
            return logits, aux
        return logits

    @staticmethod
    def partition_rules():
        """Megatron-style TP rules (combined with FSDP via policy flag).

        qkv/fc split columns on ``model``; out/proj split rows (their
        all-reduce rides ICI); embeddings split the vocab/position table.
        The ``hs`` rules cover the ``scan_layers`` stacked variant (same
        splits shifted one dim right past the leading layer axis).
        """
        from tpusystem.ops.moe import moe_partition_rules
        from tpusystem.parallel.mesh import EXPERT
        return (
            # `hs/.*` covers both the plain scanned stack (hs/attn/...)
            # and BlockSpan nesting (hs/d_0/attn/..., hs/moe_block/attn/...)
            # — either way one leading layer/span dim shifts the spec right
            *tuple((rf'hs/.*{pattern}', P(None, *spec))
                   for pattern, spec in BLOCK_TP_RULES),
            # scanned MoE expert stacks: span dim first, then experts
            (r'hs/.*moe/w1$', P(None, EXPERT, None, 'model')),
            (r'hs/.*moe/b1$', P(None, EXPERT, 'model')),
            (r'hs/.*moe/w2$', P(None, EXPERT, 'model', None)),
            (r'hs/.*moe/b2$', P(None, EXPERT, None)),
            (r'hs/.*moe/router$', P()),
            *BLOCK_TP_RULES,
            (r'wte/embedding$', P('model', None)),
            (r'wpe/embedding$', P(None, 'model')),
        ) + moe_partition_rules()


register(GPT2, excluded_kwargs={'mesh'})


class GPT2Pipelined:
    """GPT-2 with its block stack pipelined over the ``stage`` mesh axis.

    Blocks are initialized *stacked* (leading ``layers`` dimension via
    ``jax.vmap`` of ``Block.init``) and executed through
    :func:`tpusystem.parallel.pipeline.pipeline_apply`: each stage owns
    ``layers/stages`` layers, microbatch activations ride the ICI ring.
    Embeddings, final layernorm, and the tied LM head run replicated over
    ``stage`` (they are a tiny fraction of the FLOPs).

    Implements the same ``init``/``apply``/``__call__`` surface the step
    builders expect from a flax module, so ``init_state``/``flax_apply``
    work unchanged. Dropout is 0 inside the pipe (pretraining-scale
    convention); the reference never pipelines at all (SURVEY.md §2.4).

    ``schedule=OverlapSchedule(pp='overlap', ...)`` skews the GPipe loop
    so every stage-to-stage ``ppermute`` issues under a microbatch's
    compute (see :func:`tpusystem.parallel.pipeline.pipeline_apply`);
    ``moe_experts > 0`` makes every ``moe_every``-th block an MoE FFN
    (the stacked unit becomes a :class:`BlockSpan`, router aux losses
    ride the pipeline's aux channel, and ``apply`` returns
    ``(logits, aux)`` for ``WithAuxLoss`` — the GPipe path only; the
    1F1B builder rejects MoE spans).
    """

    def __init__(self, vocab_size: int = 50257, layers: int = 12,
                 dim: int = 768, heads: int = 12, max_seq: int = 1024,
                 mlp_ratio: int = 4, dtype: str = 'bfloat16',
                 microbatches: int = 4, remat: bool = True, mesh=None,
                 return_features: bool = False, interleave: int = 1,
                 schedule=None, moe_experts: int = 0, moe_every: int = 2,
                 moe_k: int = 2, moe_capacity_factor: float = 1.25):
        if mesh is None:
            raise ValueError('GPT2Pipelined needs a mesh with a stage axis')
        if layers % max(interleave, 1):
            raise ValueError(f'{layers} layers not divisible by '
                             f'interleave={interleave}')
        self.vocab_size, self.layers, self.dim = vocab_size, layers, dim
        self.heads, self.max_seq, self.mlp_ratio = heads, max_seq, mlp_ratio
        self.dtype = dtype
        self.microbatches, self.remat, self.mesh = microbatches, remat, mesh
        self.return_features = return_features
        # interleave > 1: the stacked params are stored chunk-major
        # ([interleave, layers/interleave, ...], a plain reshape of the
        # layer-major stack) so the interleaved 1F1B schedule's
        # P(None, stage) sharding places each device's v non-contiguous
        # chunks without per-step resharding
        self.interleave = interleave
        # schedule: parallel.OverlapSchedule — the pp= arm drives the
        # GPipe loop's skewed overlap ticks (pipeline_apply); tp=/fsdp=
        # stay on GSPMD inside stage bodies (the stage shard_map is
        # already the manual region, so the blocks see mesh=None and the
        # partial-manual model axis), and moe= reaches the blocks' MoEMLP
        # (single-shard inside the pipe — the exchange arms bite on the
        # non-pipelined expert meshes). Purely an implementation
        # schedule: param trees and losses are bitwise knob-invariant.
        self.schedule = schedule
        # moe_experts > 0: every `moe_every`-th block is an expert-
        # parallel MoEMLP. The stacked unit becomes a BlockSpan of
        # `moe_every` blocks (the homogeneous span nn.scan/vmap needs),
        # so the stage axis shards layers/moe_every spans; the router aux
        # losses ride pipeline_apply's aux channel (mean over every
        # (span, microbatch)) and the model returns (logits, aux) for
        # WithAuxLoss, exactly like the non-pipelined family.
        self.moe_experts = moe_experts
        self.moe_every = moe_every
        if moe_experts:
            if interleave > 1:
                raise ValueError('moe_experts with interleave > 1 is not '
                                 'supported (the aux channel rides the '
                                 'plain GPipe schedule)')
            if layers % moe_every:
                raise ValueError(f'{layers} layers not divisible by '
                                 f'moe_every ({moe_every})')
            self.block = BlockSpan(heads, mlp_ratio, 0.0, jnp.dtype(dtype),
                                   span=moe_every, moe_experts=moe_experts,
                                   moe_every=moe_every, moe_k=moe_k,
                                   moe_capacity_factor=moe_capacity_factor,
                                   schedule=schedule)
            self.stacked_units = layers // moe_every
        else:
            self.block = Block(heads, mlp_ratio, 0.0, jnp.dtype(dtype),
                               schedule=schedule)
            self.stacked_units = layers
        self.stacked_key = 'h'   # params key of the stage-sharded layer stack

    def __call__(self, tokens, train: bool = False):
        raise TypeError('bind parameters via .apply(), like a flax module')

    def init(self, rng, tokens, train: bool = False):
        units = self.stacked_units
        keys = jax.random.split(rng, units + 2)
        sample = jnp.zeros((1, 8, self.dim), jnp.dtype(self.dtype))
        stacked = jax.vmap(lambda key: self.block.init(key, sample)['params'])(
            keys[:units])
        if self.interleave > 1:
            stacked = jax.tree.map(
                lambda leaf: leaf.reshape(
                    (self.interleave, self.layers // self.interleave)
                    + leaf.shape[1:]),
                stacked)
        scale = 0.02
        wte = scale * jax.random.normal(keys[-2], (self.vocab_size, self.dim))
        wpe = scale * jax.random.normal(keys[-1], (self.max_seq, self.dim))
        return {'params': {
            'wte': {'embedding': wte}, 'wpe': {'embedding': wpe},
            'h': stacked,
            'ln_f': {'scale': jnp.ones(self.dim), 'bias': jnp.zeros(self.dim)},
        }}

    def _embed(self, params, tokens):
        length = tokens.shape[-1]
        assert length <= self.max_seq, (length, self.max_seq)
        embedding = params['wte']['embedding']
        hidden = embedding[tokens] + params['wpe']['embedding'][:length]
        return hidden.astype(jnp.dtype(self.dtype))

    def _head(self, params, hidden):
        # same ln_f the non-pipelined family uses, applied as a standalone
        # module so the two variants cannot drift numerically
        hidden = nn.LayerNorm(dtype=jnp.float32).apply(
            {'params': params['ln_f']}, hidden.astype(jnp.float32))
        table = params['wte']['embedding'].astype(jnp.dtype(self.dtype))
        if self.return_features:
            # fused-loss path (train.ChunkedNextTokenLoss): the criterion
            # owns the head matmul, logits are never materialized
            return hidden.astype(jnp.dtype(self.dtype)), table
        return head_logits(hidden, table, tied=True)

    def _block_fn(self):
        def block_fn(layer_params, activations):
            return self.block.apply({'params': layer_params}, activations)
        return block_fn

    def _flat_stack(self, stacked):
        """Layer-major view of the stacked block params (undoes the
        chunk-major interleave storage; identity when interleave == 1)."""
        if self.interleave <= 1:
            return stacked
        return jax.tree.map(
            lambda leaf: leaf.reshape((self.layers,) + leaf.shape[2:]),
            stacked)

    def apply(self, variables, tokens, rngs=None, train: bool = False):
        from tpusystem.parallel.pipeline import pipeline_apply
        params = variables['params']
        hidden = self._embed(params, tokens)
        # chunk-major stack passes straight through: pipeline_apply's
        # interleaved forward schedule shares pipeline_train's layout, so
        # the GPipe path gets the same (S-1)/v fill/drain bubble shrink.
        # schedule.pp='overlap' swaps in the skewed tick (sends under
        # compute); with MoE spans the router aux rides the aux channel.
        hidden = pipeline_apply(self._block_fn(), params['h'],
                                hidden, self.mesh,
                                microbatches=self.microbatches,
                                remat=self.remat,
                                interleave=self.interleave,
                                schedule=self.schedule,
                                has_aux=bool(self.moe_experts))
        if self.moe_experts:
            hidden, aux = hidden
            return self._head(params, hidden), aux
        return self._head(params, hidden)

    def sequential_apply(self, variables, tokens):
        """Reference forward without the pipeline (correctness harness).

        With MoE spans the aux is the mean over span units computed on
        the FULL batch — the pipelined aux averages per-microbatch span
        means instead (the balance loss is nonlinear in its token
        statistics, and expert capacity derives from the call's token
        count), so with drops or across that nonlinearity the two agree
        only approximately; schedule-on vs schedule-off pipelined runs
        agree bitwise."""
        params = variables['params']
        hidden = self._embed(params, tokens)
        block_fn = self._block_fn()

        if self.moe_experts:
            def moe_layer(carry, layer_params):
                x, aux = carry
                x, unit_aux = block_fn(layer_params, x)
                return (x, aux + unit_aux.astype(jnp.float32)), None
            (hidden, aux_sum), _ = jax.lax.scan(
                moe_layer, (hidden, jnp.float32(0)),
                self._flat_stack(params['h']))
            return (self._head(params, hidden),
                    aux_sum / self.stacked_units)

        def layer(carry, layer_params):
            return block_fn(layer_params, carry), None

        hidden, _ = jax.lax.scan(layer, hidden, self._flat_stack(params['h']))
        return self._head(params, hidden)

    @staticmethod
    def block_partition_rules():
        """Megatron TP rules for the *within-stack* block leaf paths
        (``attn/qkv/kernel`` etc. — no leading layer dim): qkv/fc split
        columns on ``model``, out/proj split rows — the same
        ``BLOCK_TP_RULES`` the non-pipelined family uses. Feed these to
        ``PipelineParallel(stacked_rules=...)``, which shifts them right
        past the stage dim(s); the pipeline's partial-manual ``shard_map``
        then runs each stage's matmuls model-partitioned (PP x TP)."""
        return BLOCK_TP_RULES

    def partition_rules(self):
        """Stage sharding for the stacked blocks, composed with the
        Megatron within-stage TP splits (inert on meshes with model=1, or
        wherever a dim doesn't divide — the policy drops non-dividing
        axes); embeddings/ln replicated (combine with ``fsdp=True`` on the
        policy to scatter them). With interleave, the chunk-major stack
        shards its *second* dim (the within-chunk layer index groups
        ``stages`` contiguous layers per device — see ``pipeline_train``'s
        layout contract)."""
        from tpusystem.parallel.pipeline import compose_stacked_rules
        return compose_stacked_rules(r'(^|/)h/', self.block_partition_rules(),
                                     self.interleave)


register(GPT2Pipelined, excluded_kwargs={'mesh'})


def gpt2_small(**overrides) -> GPT2:
    return GPT2(**overrides)


def gpt2_tiny(**overrides) -> GPT2:
    """Test/dry-run scale: compiles in seconds on CPU."""
    config = dict(vocab_size=256, layers=2, dim=64, heads=4, max_seq=128,
                  dropout=0.0)
    config.update(overrides)
    return GPT2(**config)
