"""Paged decode attention — one Pallas TPU kernel that walks the block table.

The serving engine's fused decode step attends one new token per row over
a **paged** KV pool (:func:`tpusystem.ops.attention.paged_attention` owns
the layout: ``[slots, heads * head_dim]`` pools, ``[rows, max_blocks]``
block tables, per-row cursors). :func:`paged_decode_attention` reads that
pool where it lies:

* the K and V pools stay in HBM (``memory_space=pl.ANY``); the table and
  the cursors are scalar-prefetch operands, so every address is known
  before a row starts;
* for each row the kernel walks that row's own table columns
  ``0 … cursor // block`` and DMAs those blocks — and no others — into a
  double-buffered VMEM window, a *chunk* of blocks at a time (chosen from
  ``block`` and ``max_blocks`` by :func:`paged_plan`: about one MXU tile
  of positions), the next chunk (or the next row's first) in flight while
  the current one is attended;
* flash's online softmax carries a running maximum, denominator and
  accumulator in float32 across a row's chunks. Positions past the cursor
  inside the last chunk are masked; blocks past it are never fetched (the
  window is zeroed once, so what a skipped block leaves there is finite
  and its probability is exactly zero). A parked row (cursor 0 on the
  trash block) reads one position.

Every head's scores come out of ONE product: the row's ``[heads *
head_dim]`` query is laid out block-diagonally (row ``h`` holds head
``h``'s lanes, zeros elsewhere), so ``Q_bd @ K^T`` contracts over the
stored minor dimension and the pool is never reshaped, sliced by head or
relaid. The value product runs the same way and the block diagonal of
``P @ V`` is the context. Operands stay in the stored dtype (bf16 on the
chip), scores, softmax statistics and the value accumulation are float32,
the result is rounded once to the query's dtype.

Module discipline (``decode_matmul``): ``interpret=None`` auto-selects
interpreter mode off-TPU, so tier-1 runs this body on the CPU;
:func:`paged_plan` answers from shapes alone whether the TPU can tile
them — ``fused_paged_reason`` names a refusal and ``decode_impl='auto'``
then serves the flax paged step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.attention import NEG_INF
from tpusystem.ops.pallas import auto_interpret, streamed_from_hbm

LANES = 128          # lane tile: a block's minor dim must be a multiple
CHUNK_POSITIONS = 128   # one MXU tile of keys per product


def paged_plan(heads: int, head_dim: int, block: int, max_blocks: int,
               dtype, interpret: bool) -> int | None:
    """Pure tiling decision: how many table columns one chunk walks, or
    ``None`` when the TPU cannot run these shapes — the pool's minor dim
    (``heads * head_dim``) must fill whole lanes and a block of ``block``
    positions whole sublane tiles of ``dtype`` (16 rows of bf16, 8 of
    f32), since each block is its own DMA into the window. Interpret mode
    has no tiling constraints. The chunk is as many blocks as cover
    ``CHUNK_POSITIONS`` positions, never more than the table holds."""
    if not interpret:
        sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
        if (heads * head_dim) % LANES or block % sublanes:
            return None
    return max(1, min(max_blocks, CHUNK_POSITIONS // block))


def _kernel(table_ref, cursor_ref, q_ref, k_hbm, v_hbm, out_ref,
            k_win, v_win, acc, top, denom, slot_ref, sems, *,
            head_dim: int, block: int, chunk: int, max_seq: int,
            scale: float):
    row, rows = pl.program_id(0), pl.num_programs(0)
    span = chunk * block                       # positions per chunk
    padded_heads, width = acc.shape

    def depth_of(r):        # positions row r holds, this step's included
        return jnp.minimum(cursor_ref[r] + 1, max_seq)

    def move(r, index, slot, start: bool):
        """Start (or wait for) the DMAs of chunk ``index`` of row ``r``
        into window ``slot``: one per table column the row has filled."""
        first = index * chunk
        filled = jnp.clip(pl.cdiv(depth_of(r), block) - first, 0, chunk)

        def one(offset, carry):
            source = table_ref[r, first + offset] * block
            target = pl.multiple_of(offset * block, block)
            for which, (pool, window) in enumerate(((k_hbm, k_win),
                                                    (v_hbm, v_win))):
                copy = pltpu.make_async_copy(
                    pool.at[pl.ds(source, block)],
                    window.at[slot, pl.ds(target, block)],
                    sems.at[slot, which])
                copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, filled, one, None)

    @pl.when(row == 0)
    def _prime():
        k_win[...] = jnp.zeros_like(k_win)
        v_win[...] = jnp.zeros_like(v_win)
        slot_ref[0] = 0
        move(0, 0, 0, start=True)

    # the query laid out block-diagonally: row h keeps head h's lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (padded_heads, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (padded_heads, width), 0)
    own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)
    query = jnp.where(own, q_ref[...].astype(jnp.float32), 0.0).astype(
        q_ref.dtype)       # (selected in f32: the mask is a 32-bit one)

    acc[...] = jnp.zeros_like(acc)
    top[...] = jnp.full_like(top, NEG_INF)
    denom[...] = jnp.zeros_like(denom)
    depth = depth_of(row)
    chunks = pl.cdiv(depth, span)

    def attend(index, carry):
        slot = slot_ref[0]
        more = index + 1 < chunks       # else: the next row's first chunk

        @pl.when(more | (row + 1 < rows))
        def _prefetch():
            move(jnp.where(more, row, jnp.minimum(row + 1, rows - 1)),
                 jnp.where(more, index + 1, 0), 1 - slot, start=True)

        move(row, index, slot, start=False)
        keys = k_win[slot].astype(query.dtype)
        values = v_win[slot].astype(query.dtype)
        scores = jax.lax.dot_general(
            query, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [heads, span]
        position = index * span + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(position < depth, scores, NEG_INF)
        before = top[...]
        after = jnp.maximum(before, jnp.max(scores, axis=-1, keepdims=True))
        shrink = jnp.exp(before - after)
        weights = jnp.exp(scores - after)
        denom[...] = shrink * denom[...] + jnp.sum(weights, axis=-1,
                                                   keepdims=True)
        acc[...] = shrink * acc[...] + jax.lax.dot_general(
            weights.astype(query.dtype), values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        top[...] = after
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, chunks, attend, 0)
    context = jnp.where(own, acc[...] / denom[...], 0.0)
    out_ref[...] = jnp.sum(context, axis=0, keepdims=True).astype(
        out_ref.dtype)


def paged_decode_attention(query, key_pool, value_pool, table, cursor, *,
                           block: int, interpret: bool | None = None):
    """One new token per row attended over the paged pool in place.

    Args:
        query: ``[rows, heads, head_dim]`` (the compute dtype).
        key_pool, value_pool: ``[slots, heads * head_dim]`` pools as
            stored — this step's K and V already written at each row's
            slot. Left in HBM; only the blocks a row holds are read.
        table: ``[rows, max_blocks]`` int32 physical block per logical
            block (unmapped columns point at the trash block).
        cursor: ``[rows]`` int32 position of this step's token; a row
            attends positions ``0 … cursor``.
        block: positions per block.

    Returns the ``[rows, heads, head_dim]`` context in ``query.dtype``.
    Raises ``ValueError`` where :func:`paged_plan` refuses the shapes —
    the engine asks the plan first (``fused_paged_reason``)."""
    interpret = auto_interpret(interpret)
    rows, heads, head_dim = query.shape
    width = heads * head_dim
    max_blocks = table.shape[1]
    if key_pool.shape != value_pool.shape or key_pool.shape[1:] != (width,):
        raise ValueError(f'pools {key_pool.shape} / {value_pool.shape} do '
                         f'not hold [slots, {width}]')
    chunk = paged_plan(heads, head_dim, block, max_blocks, key_pool.dtype,
                       interpret)
    if chunk is None:
        raise ValueError(
            f'paged_decode_attention cannot tile heads={heads} '
            f'head_dim={head_dim} block={block} {key_pool.dtype} on the TPU')
    span = chunk * block
    padded_heads = -(-heads // 16) * 16        # whole bf16 sublane tiles
    kernel = functools.partial(
        _kernel, head_dim=head_dim, block=block, chunk=chunk,
        max_seq=max_blocks * block, scale=head_dim ** -0.5)
    itemsize = jnp.dtype(key_pool.dtype).itemsize
    per_row = pl.BlockSpec((None, 1, width), lambda r, *_: (r, 0, 0))
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    context = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[per_row, in_place, in_place],
            out_specs=per_row,
            scratch_shapes=[
                pltpu.VMEM((2, span, width), key_pool.dtype),
                pltpu.VMEM((2, span, width), value_pool.dtype),
                pltpu.VMEM((padded_heads, width), jnp.float32),
                pltpu.VMEM((padded_heads, 1), jnp.float32),
                pltpu.VMEM((padded_heads, 1), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, 1, width), query.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * padded_heads * width * max_blocks * block,
            bytes_accessed=2 * rows * max_blocks * block * width * itemsize,
            transcendentals=rows * padded_heads * max_blocks * block),
        interpret=interpret,
        name='paged_decode_attention',
    )(table, cursor, query.reshape(rows, 1, width),
      streamed_from_hbm(key_pool, interpret),
      streamed_from_hbm(value_pool, interpret))
    return context.reshape(rows, heads, head_dim)
