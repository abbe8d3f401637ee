"""Paged latent decode attention — one Pallas TPU kernel that walks the
block table of a latent pool.

The decode path of :func:`tpusystem.ops.attention.latent_attention`
attends one new token per row over a paged pool whose row is one latent
``[c_kv (rank) ; k_rope]`` shared by every head, with the up-projection
absorbed into the query. :func:`paged_latent_attention` reads that pool
where it lies, on the walk of its sibling
:func:`tpusystem.ops.pallas.paged_attention.paged_decode_attention`:

* the grid is ``(rows,)``; the pool stays in HBM (``memory_space=
  pl.ANY``) and goes in once; the table and the cursors are
  scalar-prefetch operands, so every address is known before a row starts;
* for each row the kernel walks that row's own table columns ``0 …
  cursor // block`` in a ``fori_loop`` over its chunks and DMAs those
  blocks, one contiguous ``[block, lanes]`` run each, and no others,
  into a double-buffered VMEM window of ``CHUNK_POSITIONS`` rows, the
  next chunk (or the next row's first) in flight while the current one is
  attended. A row pays nothing for chunks past its cursor, not even a
  grid step;
* flash's online softmax carries a running maximum, denominator and
  accumulator in float32 across a row's chunks. Positions past the cursor
  inside the last chunk are masked; blocks past it are never fetched (the
  window is zeroed once, so what a block never fetched leaves there is
  finite and its probability is exactly zero). A parked row (cursor 0 on
  the trash block) reads one position.

Every head attends the same rows, so a chunk's scores are one product
``Q [heads, rank + rope] · chunk^T`` (taken as the content part plus the
rope part, both starting on a lane boundary, so the lanes that pad a
stored row never enter a product) and its mix ``P · chunk[:, :rank]``,
both straight from the window as it lies. The pool is read as stored,
``[slots, lanes]`` with the rows padded to whole lanes
(:func:`tpusystem.ops.attention.latent_attention` says why).

``interpret=None`` auto-selects interpreter mode off-TPU;
:func:`latent_plan` answers from shapes alone whether the TPU can tile
them and hold the window (``None``: the caller keeps its XLA read).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.pallas import auto_interpret, streamed_from_hbm

NEG_INF = -1e30
LANES = 128
CHUNK_POSITIONS = 512       # latent rows in one window: PERF.md §6, PR 33
VMEM_BYTES = 12 * 2 ** 20   # of the 16 MiB a kernel may scope on a v5e


def latent_plan(heads: int, rank: int, block: int, max_blocks: int, dtype,
                interpret: bool, lanes: int | None = None) -> int | None:
    """How many table columns one chunk walks, or ``None`` where the TPU
    cannot run these shapes: the content part (``rank``) must fill whole
    lanes, the heads whole sublane tiles of the query, a block whole
    sublane tiles of ``dtype`` (each block is one DMA into the window),
    and what the kernel keeps in VMEM (the double window, a row's query
    and result blocks twice, the accumulator, a chunk's scores) must fit
    there. ``lanes`` is the stored width of a pool row where the caller
    knows it; left out, the least a pool of this rank has (one lane tile
    of rope after the content). Interpret mode has no constraints."""
    chunk = max(1, min(max_blocks, CHUNK_POSITIONS // block))
    if not interpret:
        itemsize = jnp.dtype(dtype).itemsize
        sublanes = 8 * max(1, 4 // itemsize)
        if rank % LANES or heads % sublanes or block % sublanes:
            return None
        lanes, span = lanes or rank + LANES, chunk * block
        kept = (2 * span * lanes * itemsize
                + 2 * heads * (lanes + rank) * itemsize
                + heads * rank * 4 + 3 * heads * span * 4)
        if kept > VMEM_BYTES:
            return None
    return chunk


def _kernel(table_ref, cursor_ref, q_ref, pool_hbm, out_ref, window, acc,
            top, denom, slot_ref, sems, *, rank: int, width: int,
            block: int, chunk: int, max_seq: int, scale: float):
    row, rows = pl.program_id(0), pl.num_programs(0)
    span = chunk * block                       # positions per chunk

    def depth_of(r):        # positions row r holds, this step's included
        return jnp.minimum(cursor_ref[r] + 1, max_seq)

    def move(r, index, slot, start: bool):
        """Start (or wait for) the DMAs of chunk ``index`` of row ``r``
        into window ``slot``: one per table column the row has filled."""
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

    query = q_ref[...]                                   # [heads, width]
    acc[...] = jnp.zeros_like(acc)
    top[...] = jnp.full_like(top, NEG_INF)
    denom[...] = jnp.zeros_like(denom)
    depth = depth_of(row)
    chunks = pl.cdiv(depth, span)
    transposed = (((1,), (1,)), ((), ()))

    def attend(index, carry):
        slot = slot_ref[0]
        more = index + 1 < chunks       # else: the next row's first chunk

        @pl.when(more | (row + 1 < rows))
        def _prefetch():
            move(jnp.where(more, row, jnp.minimum(row + 1, rows - 1)),
                 jnp.where(more, index + 1, 0), 1 - slot, start=True)

        move(row, index, slot, start=False)
        latent = window[slot].astype(query.dtype)        # [span, lanes]
        content = latent[:, :rank]
        scores = (jax.lax.dot_general(
            query[:, :rank], content, transposed,
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                query[:, rank:], latent[:, rank:width], transposed,
                preferred_element_type=jnp.float32)) * scale
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
            weights.astype(query.dtype), content, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        top[...] = after
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, chunks, attend, 0)
    out_ref[...] = (acc[...] / denom[...]).astype(out_ref.dtype)


def paged_latent_attention(query, pool, table, cursor, *, rank: int,
                           width: int, block: int, scale: float,
                           interpret: bool | None = None):
    """One new token per row attended over the paged latent pool in place.

    Args:
        query: ``[rows, heads, rank + rope]``: each head's content query
            already carried through the key half of the up-projection
            (``rank`` wide), then its rotated rope query.
        pool: ``[slots, lanes]`` as stored (``lanes >= width``: the rows
            padded to whole lanes), this step's rows already written. Left
            in HBM; only the blocks a row holds are read.
        table: ``[rows, max_blocks]`` int32 physical block per logical
            block (unmapped columns point at the trash block).
        cursor: ``[rows]`` int32 position of this step's token; a row
            attends positions ``0 … cursor``.
        rank: width of the content part; ``width``: ``rank + rope``;
        ``block``: positions per block; ``scale``: the softmax scale.

    Returns ``softmax(Q · rows^T · scale) · rows[:, :rank]``, ``[rows,
    heads, rank]`` in ``query.dtype`` (the caller carries it through the
    value half of the up-projection). Raises ``ValueError`` where
    :func:`latent_plan` refuses the shapes."""
    interpret = auto_interpret(interpret)
    rows, heads, _ = query.shape
    max_blocks, lanes = table.shape[1], pool.shape[1]
    if query.shape[2] != width or lanes < width:
        raise ValueError(f'query {query.shape} and pool {pool.shape} do not '
                         f'hold rows of {width}')
    chunk = latent_plan(heads, rank, block, max_blocks, pool.dtype, interpret,
                        lanes)
    if chunk is None:
        raise ValueError(
            f'paged_latent_attention cannot tile heads={heads} rank={rank} '
            f'block={block} lanes={lanes} {pool.dtype} on the TPU')
    span, max_seq = chunk * block, max_blocks * block
    per_row = lambda minor: pl.BlockSpec((None, heads, minor),
                                         lambda row, *_: (row, 0, 0))
    kernel = functools.partial(_kernel, rank=rank, width=width, block=block,
                               chunk=chunk, max_seq=max_seq, scale=scale)
    # a row sits anywhere between an empty table and a full one
    positions = rows * max_seq // 2
    itemsize = jnp.dtype(pool.dtype).itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[per_row(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_row(rank),
            scratch_shapes=[
                pltpu.VMEM((2, span, lanes), pool.dtype),
                pltpu.VMEM((heads, rank), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, rank), query.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(
            flops=2 * positions * heads * (width + rank),
            bytes_accessed=(positions * lanes
                            + rows * heads * (width + rank)) * itemsize,
            transcendentals=positions * heads),
        interpret=interpret,
        name='paged_latent_attention',
    )(table, cursor, query, streamed_from_hbm(pool, interpret))
