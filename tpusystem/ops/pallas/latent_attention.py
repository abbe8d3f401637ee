"""Paged latent decode attention — one Pallas TPU kernel that walks the
block table of a latent pool.

The decode path of :func:`tpusystem.ops.attention.latent_attention`
attends one new token per row over a paged pool whose row is one latent
``[c_kv (rank) ; k_rope]`` shared by every head, with the up-projection
absorbed into the query. :func:`paged_latent_attention` reads that pool
where it lies: the grid is ``(rows, chunks)``; the table and the cursors
are scalar-prefetch operands, and each of a chunk's blocks is a block
operand of its own whose index map looks the physical block up in the
row's table, so the pipeline fetches the next chunk's blocks while this
one is attended and nothing is gathered into HBM first. Past a row's
cursor the maps stay on its last filled block: an operand whose block
does not change is not fetched again, and the chunk's arithmetic is
skipped, so a row pays for the positions it holds (to the chunk), not for
the deepest row's bucket. Flash's online softmax carries a running
maximum, denominator and accumulator in float32 across a row's chunks.

Every head attends the same rows, so a chunk's scores are one product
``Q [heads, rank + rope] · chunk^T`` (taken as the content part plus the
rope part, both starting on a lane boundary) and its mix ``P · chunk[:,
:rank]``. The pool is read as stored, ``[slots, lanes]`` with the rows
padded to whole lanes (:func:`tpusystem.ops.attention.latent_attention`
says why).

``interpret=None`` auto-selects interpreter mode off-TPU;
:func:`latent_plan` answers from shapes alone whether the TPU can tile
them (``None``: the caller keeps its XLA read).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.pallas import auto_interpret

NEG_INF = -1e30
LANES = 128
CHUNK_POSITIONS = 256       # latent rows attended per grid step


def latent_plan(heads: int, rank: int, block: int, max_blocks: int, dtype,
                interpret: bool) -> int | None:
    """How many table columns one chunk walks, or ``None`` where the TPU
    cannot run these shapes: the content part (``rank``) must fill whole
    lanes (the rope part then starts on a lane boundary), the heads whole
    sublane tiles of the query, and a block whole sublane tiles of
    ``dtype`` (each block is a block operand of its own). Interpret mode
    has no tiling constraints."""
    if not interpret:
        sublanes = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
        if rank % LANES or heads % sublanes or block % sublanes:
            return None
    return max(1, min(max_blocks, CHUNK_POSITIONS // block))


def _kernel(table_ref, cursor_ref, q_ref, *refs, rank: int, width: int,
            block: int, chunk: int, max_seq: int, scale: float):
    del table_ref                     # the index maps read it
    blocks, (out_ref, acc, top, denom) = refs[:chunk], refs[chunk:]
    row, index = pl.program_id(0), pl.program_id(1)
    span = chunk * block
    depth = jnp.minimum(cursor_ref[row] + 1, max_seq)

    @pl.when(index == 0)
    def _start():
        acc[...] = jnp.zeros_like(acc)
        top[...] = jnp.full_like(top, NEG_INF)
        denom[...] = jnp.zeros_like(denom)

    @pl.when(index * span < depth)
    def _attend():
        query = q_ref[...]                               # [heads, width]
        latent = jnp.concatenate([ref[...] for ref in blocks],
                                 axis=0).astype(query.dtype)
        content = latent[:, :rank]                       # [span, rank]
        transposed = (((1,), (1,)), ((), ()))
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

    @pl.when(index == pl.num_programs(1) - 1)
    def _finish():
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
            padded to whole lanes), this step's rows already written. Only
            the blocks a row holds are read.
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
    chunk = latent_plan(heads, rank, block, max_blocks, pool.dtype, interpret)
    if chunk is None:
        raise ValueError(
            f'paged_latent_attention cannot tile heads={heads} rank={rank} '
            f'block={block} {pool.dtype} on the TPU')
    max_seq = max_blocks * block

    def held(offset: int):
        """The physical block behind column ``chunk * index + offset`` of
        the row's table, or its last filled block past the cursor."""
        def index_map(row, index, table_ref, cursor_ref):
            last = jnp.minimum(cursor_ref[row], max_seq - 1) // block
            return table_ref[row, jnp.minimum(index * chunk + offset,
                                              last)], 0
        return pl.BlockSpec((block, lanes), index_map)

    per_row = lambda minor: pl.BlockSpec((None, heads, minor),
                                         lambda row, index, *_: (row, 0, 0))
    kernel = functools.partial(_kernel, rank=rank, width=width, block=block,
                               chunk=chunk, max_seq=max_seq, scale=scale)
    positions = rows * max_seq
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, pl.cdiv(max_blocks, chunk)),
            in_specs=[per_row(width)] + [held(offset)
                                         for offset in range(chunk)],
            out_specs=per_row(rank),
            scratch_shapes=[pltpu.VMEM((heads, rank), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, heads, rank), query.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        cost_estimate=pl.CostEstimate(
            flops=2 * positions * heads * (width + rank),
            bytes_accessed=positions * lanes * jnp.dtype(pool.dtype).itemsize,
            transcendentals=positions * heads),
        interpret=interpret,
        name='paged_latent_attention',
    )(table, cursor, query, *([pool] * chunk))
