"""Paged index scores — one Pallas TPU kernel that walks the block table
of a sparse-attention indexer's key pool.

A learned sparse attention (DeepSeek-V3.2's indexer: :func:`tpusystem.ops.
attention.sparse_latent_attention`) scores every position a row holds
before it attends any: ``I(s) = sum_j w_j · relu(q_j · k_s)`` over the
indexer's heads ``j``, from one small key ``k_s`` a position (128 values)
kept in a paged pool behind the row's table. :func:`paged_index_scores`
reads that pool where it lies, on the walk of
:func:`tpusystem.ops.pallas.latent_attention.paged_latent_attention`: the
grid is ``(rows,)``, the pool stays in HBM, the table and the cursors are
scalar-prefetch operands, and each row's own blocks ``0 … cursor //
block`` go by DMA, a chunk at a time, into a double-buffered window, the
next chunk (or the next row's first) in flight while the current one is
scored. A chunk's scores are one product ``Q [heads, dim] · chunk^T``,
a ReLU, and the weighted sum over the heads; they are written into the
row's ``[chunks, 1, span]`` output at the chunk's place, and positions past
the cursor read ``NEG_INF`` (chunks past it are never fetched).

``interpret=None`` auto-selects interpreter mode off-TPU;
:func:`index_plan` answers from shapes alone whether the TPU can tile them
(``None``: the caller keeps its XLA read).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.pallas import auto_interpret, streamed_from_hbm
from tpusystem.ops.pallas.latent_attention import (LANES, NEG_INF,
                                                   VMEM_BYTES, chunk_span)


def index_plan(heads: int, dim: int, block: int, max_blocks: int, dtype,
               interpret: bool) -> int | None:
    """Positions one chunk scores, or ``None`` where these shapes cannot
    run: ``max_seq`` must be whole chunks (the output is laid out chunk by
    chunk); on the TPU the key must fill whole lanes, the heads and a
    block whole sublane tiles of ``dtype``, and the double window, the
    query, the scores and the row's output must fit in VMEM."""
    span = chunk_span(block, max_blocks)
    max_seq = max_blocks * block
    if max_seq % span:
        return None
    if not interpret:
        itemsize = jnp.dtype(dtype).itemsize
        sublanes = 8 * max(1, 4 // itemsize)
        if dim % LANES or heads % sublanes or block % sublanes:
            return None
        kept = (2 * span * dim * itemsize + 2 * heads * (dim + LANES) * 4
                + heads * span * 4 + 2 * 8 * max_seq * 4)
        if kept > VMEM_BYTES:
            return None
    return span


def _kernel(table_ref, cursor_ref, q_ref, w_ref, pool_hbm, out_ref, window,
            slot_ref, sems, *, block: int, span: int, max_seq: int):
    row, rows = pl.program_id(0), pl.num_programs(0)
    chunk = span // block                      # table columns per chunk

    def depth_of(r):        # positions row r holds, this step's included
        return jnp.minimum(cursor_ref[r] + 1, max_seq)

    def move(r, index, slot, start: bool):
        first = index * chunk
        filled = jnp.clip(pl.cdiv(depth_of(r), block) - first, 0, chunk)

        def one(offset, carry):
            source = pl.multiple_of(table_ref[r, first + offset] * block,
                                    block)
            target = pl.multiple_of(offset * block, block)
            copy = pltpu.make_async_copy(
                pool_hbm.at[pl.ds(source, block)],
                window.at[slot, pl.ds(target, block)], sems.at[slot])
            copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, filled, one, None)

    @pl.when(row == 0)
    def _prime():
        window[...] = jnp.zeros_like(window)
        slot_ref[0] = 0
        move(0, 0, 0, start=True)

    query = q_ref[...]                                   # [heads, dim]
    weights = w_ref[...]                                 # [heads, 1]
    out_ref[...] = jnp.full(out_ref.shape, NEG_INF, out_ref.dtype)
    depth = depth_of(row)
    chunks = pl.cdiv(depth, span)
    transposed = (((1,), (1,)), ((), ()))

    def score(index, carry):
        slot = slot_ref[0]
        more = index + 1 < chunks       # else: the next row's first chunk

        @pl.when(more | (row + 1 < rows))
        def _prefetch():
            move(jnp.where(more, row, jnp.minimum(row + 1, rows - 1)),
                 jnp.where(more, index + 1, 0), 1 - slot, start=True)

        move(row, index, slot, start=False)
        keys = window[slot].astype(query.dtype)          # [span, dim]
        dots = jax.lax.dot_general(query, keys, transposed,
                                   preferred_element_type=jnp.float32)
        total = jnp.sum(jnp.maximum(dots, 0.0) * weights, axis=0,
                        keepdims=True)                   # [1, span]
        position = index * span + jax.lax.broadcasted_iota(
            jnp.int32, total.shape, 1)
        out_ref[index] = jnp.where(position < depth, total, NEG_INF)
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, chunks, score, 0)


def paged_index_scores(query, weights, pool, table, cursor, *, block: int,
                       interpret: bool | None = None):
    """Every held position's index score, for one new token per row.

    Args:
        query: ``[rows, heads, dim]``: the indexer's rotated queries.
        weights: ``[rows, heads]`` float32: each head's weight, the
            softmax scale folded in.
        pool: ``[slots, dim]`` the indexer's keys as stored, this step's
            written. Left in HBM; only the blocks a row holds are read.
        table: ``[rows, max_blocks]`` int32 physical block per logical
            block; ``cursor``: ``[rows]`` int32 position of this step's
            token (a row scores positions ``0 … cursor``).

    Returns ``[rows, chunks, 1, span]`` float32: position ``c · span + i``
    of a row at ``[row, c, 0, i]``, ``NEG_INF`` past its cursor (the
    layout :func:`~tpusystem.ops.pallas.latent_attention.
    paged_latent_attention` takes its ``selected`` mask in). Raises
    ``ValueError`` where :func:`index_plan` refuses the shapes."""
    interpret = auto_interpret(interpret)
    rows, heads, dim = query.shape
    max_blocks = table.shape[1]
    span = index_plan(heads, dim, block, max_blocks, pool.dtype, interpret)
    if span is None or pool.shape[1] != dim:
        raise ValueError(
            f'paged_index_scores cannot tile heads={heads} dim={dim} '
            f'block={block} pool={pool.shape} {pool.dtype}')
    max_seq = max_blocks * block
    chunks = max_seq // span
    kernel = functools.partial(_kernel, block=block, span=span,
                               max_seq=max_seq)
    positions = rows * max_seq // 2
    itemsize = jnp.dtype(pool.dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[pl.BlockSpec((None, heads, dim),
                                   lambda row, *_: (row, 0, 0)),
                      pl.BlockSpec((None, heads, 1),
                                   lambda row, *_: (row, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, chunks, 1, span),
                                   lambda row, *_: (row, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, span, dim), pool.dtype),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, chunks, 1, span), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(
            flops=2 * positions * heads * (dim + 1),
            bytes_accessed=positions * dim * itemsize
            + rows * (heads * dim * itemsize + max_seq * 4),
            transcendentals=0),
        interpret=interpret,
        name='paged_index_scores',
    )(table, cursor, query, weights[..., None].astype(jnp.float32),
      streamed_from_hbm(pool, interpret))
