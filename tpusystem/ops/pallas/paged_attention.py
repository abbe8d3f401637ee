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

Every head's scores come out of ONE product: the row's query is laid out
block-diagonally — row ``h`` of a ``[padded heads, kv_heads * head_dim]``
matrix holds head ``h``'s values in the lanes of **its key/value head**
(``h // group``), zeros elsewhere — so ``Q_bd @ K^T`` contracts over the
stored minor dimension and the pool is never reshaped, sliced by head or
relaid. The value product runs the same way, and the context of head ``h``
is the ``head_dim`` lanes of its key/value head in row ``h`` of ``P @ V``.
``group`` (query heads to a key/value head) is read off the operands'
shapes: ``query heads * head_dim / pool width``. At ``group == 1`` (GPT-2:
as many query heads as the pool holds) the query goes in as one
``[1, width]`` row, spread over the sublanes in the kernel, and the context
leaves as the sum over rows of the block diagonal; grouped queries (32 on
2 x 128: Nemotron-H, Llama) go in ``[heads, head_dim]``, are repeated
across the lanes once a key/value head, and the context leaves
``[heads, head_dim]`` through one static slice a key/value head.
Operands stay in the stored dtype (bf16 on the chip), scores, softmax
statistics and the value accumulation are float32, the weights are rounded
to the operand dtype before ``P @ V`` (the roundings of
``dot_product_attention``'s grouped branch), the result is rounded once to
the query's dtype.

Module discipline (``decode_matmul``): ``interpret=None`` auto-selects
interpreter mode off-TPU, so tier-1 runs this body on the CPU;
:func:`paged_plan` answers from shapes alone whether the TPU can tile
them — ``fused_paged_reason`` names a refusal and ``decode_impl='auto'``
then serves the flax paged step, whose own read
(:func:`tpusystem.ops.attention.paged_attention`) asks the same plan
(:func:`tpusystem.ops.attention.paged_read`) and keeps its gather where
the plan refuses.
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
               dtype, interpret: bool, kv_heads: int | None = None
               ) -> int | None:
    """Pure tiling decision: how many table columns one chunk walks, or
    ``None`` when the TPU cannot run these shapes — the pool's minor dim
    (``kv_heads * head_dim``; ``kv_heads`` left out: as many as query
    ``heads``) must fill whole lanes and a block of ``block`` positions
    whole sublane tiles of ``dtype`` (16 rows of bf16, 8 of f32), since
    each block is its own DMA into the window; ``heads`` must be whole
    groups of ``kv_heads``. Interpret mode has no tiling constraints. The
    chunk is as many blocks as cover ``CHUNK_POSITIONS`` positions, never
    more than the table holds."""
    kv_heads = kv_heads or heads
    if heads % kv_heads:
        return None
    if not interpret:
        sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
        if (kv_heads * head_dim) % LANES or block % sublanes:
            return None
    return max(1, min(max_blocks, CHUNK_POSITIONS // block))


def _kernel(table_ref, cursor_ref, q_ref, k_hbm, v_hbm, out_ref,
            k_win, v_win, acc, top, denom, slot_ref, sems, *,
            head_dim: int, group: int, block: int, chunk: int,
            max_seq: int, scale: float):
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

    # the query laid out block-diagonally: row h keeps the lanes of head
    # h's key/value head. One [1, width] row spread over the sublanes at
    # group 1; grouped, [heads, head_dim] repeated once a key/value head
    lane = jax.lax.broadcasted_iota(jnp.int32, (padded_heads, width), 1)
    stored = jax.lax.broadcasted_iota(jnp.int32, (padded_heads, width), 0)
    if group > 1:       # the key/value head of query head (row) h
        stored = jax.lax.div(stored, group)
    own = (lane >= stored * head_dim) & (lane < (stored + 1) * head_dim)
    query = q_ref[...].astype(jnp.float32)
    if group > 1:
        query = jnp.concatenate([query] * (width // head_dim), axis=1)
    query = jnp.where(own, query, 0.0).astype(
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
    if group == 1:
        context = jnp.sum(context, axis=0, keepdims=True)
    else:       # each row's own key/value head's lanes: the rest is zero
        context = sum(context[:, first:first + head_dim]
                      for first in range(0, width, head_dim))
    out_ref[...] = context.astype(out_ref.dtype)


def paged_decode_attention(query, key_pool, value_pool, table, cursor, *,
                           block: int, interpret: bool | None = None):
    """One new token per row attended over the paged pool in place.

    Args:
        query: ``[rows, heads, head_dim]`` (the compute dtype).
        key_pool, value_pool: ``[slots, kv_heads * head_dim]`` pools as
            stored — this step's K and V already written at each row's
            slot. Left in HBM; only the blocks a row holds are read.
            ``heads`` is ``kv_heads`` times a whole ``group``, read off
            these shapes: query head ``h`` attends key/value head
            ``h // group``.
        table: ``[rows, max_blocks]`` int32 physical block per logical
            block (unmapped columns point at the trash block).
        cursor: ``[rows]`` int32 position of this step's token; a row
            attends positions ``0 … cursor``.
        block: positions per block.

    Returns the ``[rows, heads, head_dim]`` context in ``query.dtype``.
    Raises ``ValueError`` where :func:`paged_plan` refuses the shapes —
    the engine asks the plan first (``fused_paged_reason``,
    :func:`tpusystem.ops.attention.paged_read`)."""
    interpret = auto_interpret(interpret)
    rows, heads, head_dim = query.shape
    width = key_pool.shape[-1]
    max_blocks = table.shape[1]
    if (key_pool.shape != value_pool.shape or key_pool.ndim != 2
            or width % head_dim or (heads * head_dim) % width):
        raise ValueError(f'pools {key_pool.shape} / {value_pool.shape} do '
                         f'not hold [slots, key/value heads * {head_dim}] '
                         f'for {heads} query heads')
    kv_heads = width // head_dim
    group = heads // kv_heads
    chunk = paged_plan(heads, head_dim, block, max_blocks, key_pool.dtype,
                       interpret, kv_heads)
    if chunk is None:
        raise ValueError(
            f'paged_decode_attention cannot tile heads={heads} on '
            f'{kv_heads} head_dim={head_dim} block={block} '
            f'{key_pool.dtype} on the TPU')
    span = chunk * block
    padded_heads = -(-heads // 16) * 16        # whole bf16 sublane tiles
    kernel = functools.partial(
        _kernel, head_dim=head_dim, group=group, block=block, chunk=chunk,
        max_seq=max_blocks * block, scale=head_dim ** -0.5)
    itemsize = jnp.dtype(key_pool.dtype).itemsize
    if group == 1:      # one row of every head's lanes, as the pool's
        shape = (1, width)
        query = query.reshape(rows, *shape)
    else:               # a row a head (zero rows pad the sublane tile)
        shape = (padded_heads, head_dim)
        query = jnp.pad(query, ((0, 0), (0, padded_heads - heads), (0, 0)))
    per_row = pl.BlockSpec((None, *shape), lambda r, *_: (r, 0, 0))
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
        out_shape=jax.ShapeDtypeStruct((rows, *shape), query.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * padded_heads * width * max_blocks * block,
            bytes_accessed=2 * rows * max_blocks * block * width * itemsize,
            transcendentals=rows * padded_heads * max_blocks * block),
        interpret=interpret,
        name='paged_decode_attention',
    )(table, cursor, query, streamed_from_hbm(key_pool, interpret),
      streamed_from_hbm(value_pool, interpret))
    if group == 1:
        return context.reshape(rows, heads, head_dim)
    return context[:, :heads]
