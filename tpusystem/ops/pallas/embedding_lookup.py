"""Row gather / row scatter-add — the shared Pallas kernel pair under
embedding lookup.

The fused-MoE kernels (:mod:`tpusystem.ops.pallas.grouped_matmul`) are
structurally embedding kernels already: ``gather_rows_matmul`` DMAs
scattered source rows into VMEM tiles (a lookup whose consumer happens to
be a matmul) and ``matmul_scatter_rows``'s epilogue read-modify-writes
finished rows onto arbitrary destination rows (a grad scatter whose
producer happens to be a matmul). This module hoists the *row movement*
halves into a standalone pair the recommender workload
(:mod:`tpusystem.recsys`) rides:

* :func:`gather_rows` — the **lookup direction**. The kernel walks a
  scalar-prefetched id list and DMAs table rows from HBM straight into a
  double-buffered VMEM scratch (tile t+1's rows stream in while tile t
  is scaled and stored), multiplies by a per-row scale (0 masks padded /
  foreign-shard ids, a pooling weight otherwise), and writes the block.
  The table never leaves HBM whole.

* :func:`scatter_add_rows` — the **grad direction** (the transpose of
  the gather). Each cotangent row is read-modify-written onto its
  table row in **float32**, strictly sequentially within a tile, so
  duplicate ids in one batch — the scatter-add collision case a Zipfian
  id distribution guarantees — accumulate exactly (TPU grids execute
  sequentially on a core, and each row's read completes before its
  write issues). Sentinel ids (``>= table rows``) skip their DMAs.

:func:`embedding_lookup` wraps the pair in a ``custom_vjp``: forward is
the scaled gather, backward scatter-adds the cotangents into a
zero-initialized f32 table (rounded once to the table dtype) and
re-gathers rows for the scale cotangent.

Fallback discipline (per :mod:`~tpusystem.ops.pallas.decode_matmul`,
adapted for a *training* hot path): the pure :func:`lookup_plan` pins
the ``jnp.take``/segment-sum fallback **off-TPU or on untileable
shapes** — unlike the decode kernels, lookups run inside every train
step, where an interpreter-mode kernel would be pure overhead, so
``impl='auto'`` never interprets. Explicit ``impl='fused'`` bypasses the
plan (``interpret=None`` still auto-selects interpreter mode off-TPU),
which is how tier-1 CPU tests drive the kernels' numerics directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.pallas import auto_interpret

LANES = 128   # lane tile; TPU block minor dims must be multiples
SUBLANES = 8  # sublane tile for f32
SCALE_LANES = 8   # trailing dim of the per-row scale input — a compact
                  # [rows] f32 vector is not Mosaic-lowerable (the
                  # grouped_matmul lesson); 8 replicated lanes are.


def _pick_block(size: int, want: int, granule: int) -> int | None:
    """Largest divisor of ``size`` that is <= ``want`` and a multiple of
    ``granule`` (1 in interpret mode — no tiling constraints there)."""
    want = min(want, size)
    best = None
    for candidate in range(granule, want + 1, granule):
        if size % candidate == 0:
            best = candidate
    return best


def lookup_plan(count: int, dim: int, dtype, interpret: bool,
                want_rows: int = 256) -> int | None:
    """Pure tiling decision for one ``[count]``-id lookup into a
    ``[*, dim]`` table: the id-block size, or ``None`` for the
    ``jnp.take``/segment-sum fallback.

    ``None`` in interpret mode (off-TPU) **by design**: the lookup sits
    in the training hot path, where an interpreted kernel per step is
    pure overhead — the decode kernels' auto-interpret discipline does
    not transfer. On TPU, ``None`` when the row minor dim cannot tile
    (``dim`` not a LANES multiple) or no id block divides ``count``.
    Pinned by tests so a jax upgrade cannot silently change which shapes
    run fused.
    """
    if interpret:
        return None
    if dim % LANES:
        return None
    granule = SUBLANES * 4 // max(1, jnp.dtype(dtype).itemsize)
    return _pick_block(count, want_rows, granule)


def _scale_input(scale: jax.Array) -> jax.Array:
    """[rows] f32 -> [rows, SCALE_LANES] replicated (Mosaic-tileable)."""
    return jnp.tile(jnp.asarray(scale, jnp.float32)[:, None],
                    (1, SCALE_LANES))


def _gather_kernel(id_ref, src_any, scale_ref, out_ref, scr, sem, *,
                   block_rows: int, tiles: int):
    """Grid (tiles,). Each tile's source rows are DMA'd from HBM into the
    double-buffered scratch — tile t+1's rows are issued right after tile
    t's wait, so the gather streams behind the scale-and-store."""
    tile = pl.program_id(0)

    def for_each_row(t, action):
        def body(i, _):
            row = id_ref[t * block_rows + i]
            copy = pltpu.make_async_copy(src_any.at[row],
                                         scr.at[t % 2, i], sem.at[t % 2])
            action(copy)
            return 0
        jax.lax.fori_loop(0, block_rows, body, 0)

    @pl.when(tile == 0)
    def _prologue():
        for_each_row(0, lambda copy: copy.start())
    for_each_row(tile, lambda copy: copy.wait())

    @pl.when(tile + 1 < tiles)
    def _stream_next():
        for_each_row(tile + 1, lambda copy: copy.start())

    # scale in f32 (0 masks padded/foreign ids), round once to out dtype —
    # the exact formula of the take fallback, so f32 parity is bitwise
    scaled = scr[tile % 2].astype(jnp.float32) * scale_ref[:, :1]
    out_ref[...] = scaled.astype(out_ref.dtype)


def gather_rows(src, row_ids, row_scale, *, block_rows: int = 256,
                out_dtype=None, interpret: bool | None = None):
    """Fused row gather: ``out[j] = row_scale[j] * src[row_ids[j]]``.

    Args:
        src: [rows, dim] table — stays in HBM; rows are DMA'd on demand.
        row_ids: [n] int32 source row per output row, pre-clamped to
            [0, rows); masked by ``row_scale`` instead of bounds-checked
            (the grouped_matmul contract).
        row_scale: [n] float per-row factor — 0 for padded / non-owned
            ids, 1 (or a pooling weight) otherwise; applied in f32.

    Returns [n, dim] in ``out_dtype`` (default ``src.dtype``).
    """
    interpret = auto_interpret(interpret)
    count, dim = row_ids.shape[0], src.shape[1]
    out_dtype = out_dtype or src.dtype
    granule = 1 if interpret else (
        SUBLANES * 4 // max(1, jnp.dtype(src.dtype).itemsize))
    block = _pick_block(count, block_rows, granule)
    if block is None or (not interpret and dim % LANES):
        raise ValueError(
            f'gather_rows cannot tile n={count}, dim={dim} on TPU (need '
            f'id blocks in multiples of {granule}, dim a multiple of '
            f'{LANES}); use the jnp.take fallback (lookup_plan pins it)')
    tiles = count // block
    kernel = functools.partial(_gather_kernel, block_rows=block,
                               tiles=tiles)
    bytes_accessed = (count * dim * src.dtype.itemsize
                      + count * dim * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((block, SCALE_LANES), lambda t, ids: (t, 0)),
            ],
            out_specs=pl.BlockSpec((block, dim), lambda t, ids: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, dim), src.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((count, dim), out_dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(flops=count * dim,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(jnp.asarray(row_ids, jnp.int32), src, _scale_input(row_scale))


def _scatter_add_kernel(id_ref, rows_ref, scale_ref, init_ref, out_any,
                        rd_scr, wr_scr, sem, *, block_rows: int,
                        table_rows: int):
    """Grid (tiles,). Strictly sequential per-row read-modify-write in
    f32: row i's read completes before its write issues, and row i+1's
    read issues only after row i's write completes — so duplicate ids
    within one tile (and across tiles: TPU grids are sequential on a
    core) accumulate exactly instead of losing collisions to a batched
    RMW. Sentinel rows (``>= table_rows``) skip their DMAs entirely."""
    del init_ref
    tile = pl.program_id(0)
    base = tile * block_rows

    def body(i, _):
        row = id_ref[base + i]

        @pl.when(row < table_rows)   # sentinel rows move nothing
        def _valid():
            read = pltpu.make_async_copy(out_any.at[row], rd_scr.at[0], sem)
            read.start()
            read.wait()
            contrib = (rows_ref[pl.ds(i, 1)].astype(jnp.float32)
                       * scale_ref[pl.ds(i, 1), :1])
            wr_scr[...] = rd_scr[...] + contrib
            write = pltpu.make_async_copy(wr_scr.at[0], out_any.at[row], sem)
            write.start()
            write.wait()
        return 0
    jax.lax.fori_loop(0, block_rows, body, 0)


def scatter_add_rows(rows, row_ids, row_scale, table_rows: int, *,
                     block_rows: int = 256,
                     interpret: bool | None = None):
    """Fused row scatter-add: ``out[row_ids[j]] += row_scale[j] * rows[j]``
    into a zero-initialized **float32** ``[table_rows, dim]`` table.

    ``table_rows`` is the sentinel id for padded / non-owned rows — their
    DMAs are skipped entirely. Accumulation is f32 regardless of the
    cotangent dtype (the grad-scatter contract); the caller rounds once
    to the table dtype. Duplicate ids accumulate exactly (see the kernel
    docstring) — the collision case the batched-RMW combine kernel in
    grouped_matmul never faces (one expert seats a token at most once)
    but an embedding grad under a Zipfian batch always does.
    """
    interpret = auto_interpret(interpret)
    count, dim = rows.shape
    granule = 1 if interpret else (
        SUBLANES * 4 // max(1, jnp.dtype(rows.dtype).itemsize))
    block = _pick_block(count, block_rows, granule)
    if block is None or (not interpret and dim % LANES):
        raise ValueError(
            f'scatter_add_rows cannot tile n={count}, dim={dim} on TPU; '
            f'use the segment-sum fallback (lookup_plan pins it)')
    tiles = count // block
    kernel = functools.partial(_scatter_add_kernel, block_rows=block,
                               table_rows=table_rows)
    bytes_accessed = (rows.size * rows.dtype.itemsize
                      + 3 * count * dim * 4)      # read + write per row
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((block, dim), lambda t, ids: (t, 0)),
                pl.BlockSpec((block, SCALE_LANES), lambda t, ids: (t, 0)),
                pl.BlockSpec(memory_space=pl.ANY),   # zeros init
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((1, dim), jnp.float32),
                pltpu.VMEM((1, dim), jnp.float32),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((table_rows, dim), jnp.float32),
        # the zeros init aliases the output: no in-kernel zeroing pass
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=('arbitrary',)),
        cost_estimate=pl.CostEstimate(flops=2 * count * dim,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(jnp.asarray(row_ids, jnp.int32), rows, _scale_input(row_scale),
      jnp.zeros((table_rows, dim), jnp.float32))


# ---------------------------------------------------------------------------
# the differentiable lookup built on the pair


def _take_lookup(table, clamped, scale):
    """Reference / fallback path: XLA gather + masking multiply. The
    transpose of ``jnp.take`` is XLA's scatter-add (the segment-sum), so
    autodiff supplies the grad scatter here. The f32 multiply mirrors the
    kernel's epilogue exactly — f32 forward parity is bitwise."""
    safe = jnp.minimum(clamped, table.shape[0] - 1)
    rows = jnp.take(table, safe, axis=0)
    return (rows.astype(jnp.float32) * scale[:, None]).astype(table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_lookup(config, table, clamped, scale):
    block_rows, interpret = config
    return gather_rows(table, jnp.minimum(clamped, table.shape[0] - 1),
                       scale, block_rows=block_rows, interpret=interpret)


def _fused_lookup_fwd(config, table, clamped, scale):
    out = _fused_lookup(config, table, clamped, scale)
    return out, (table, clamped, scale)


def _fused_lookup_bwd(config, residuals, d_out):
    import numpy as np
    block_rows, interpret = config
    table, clamped, scale = residuals
    # cotangent scatter: out[j] = scale[j] * table[id_j]  =>
    # d_table[id_j] += scale[j] * d_out[j], f32 accumulation, rounded once.
    # Sentinel ids (== table rows) skip their DMAs — no grad for padding.
    d_table = scatter_add_rows(d_out, clamped, scale, table.shape[0],
                               block_rows=block_rows,
                               interpret=interpret).astype(table.dtype)
    # d_scale[j] = <table[id_j], d_out[j]> — one unscaled re-gather
    rows = gather_rows(table, jnp.minimum(clamped, table.shape[0] - 1),
                       jnp.ones_like(scale), block_rows=block_rows,
                       interpret=interpret)
    d_scale = jnp.sum(rows.astype(jnp.float32)
                      * d_out.astype(jnp.float32), axis=-1)
    # mask the sentinel rows' dots (their gather clamped to a REAL row)
    d_scale = jnp.where(clamped < table.shape[0], d_scale, 0.0)
    return (d_table, np.zeros(clamped.shape, jax.dtypes.float0), d_scale)


_fused_lookup.defvjp(_fused_lookup_fwd, _fused_lookup_bwd)


def embedding_lookup(table, ids, weights=None, *, impl: str = 'auto',
                     block_rows: int = 256,
                     interpret: bool | None = None):
    """Differentiable embedding lookup ``out[j] = w[j] * table[ids[j]]``.

    Ids outside ``[0, rows)`` (e.g. ``-1`` multi-hot padding) produce
    zero rows and contribute no gradient. ``weights`` (optional, [n])
    scales each row — a pooling weight; its gradient is the rowwise dot
    with the cotangent.

    ``impl``: ``'take'`` is the XLA gather path (autodiff supplies the
    segment-sum grad scatter), ``'fused'`` the Pallas pair above
    (``custom_vjp``: f32 scatter-add of cotangents), ``'auto'`` consults
    :func:`lookup_plan` — fused on TPU where the shape tiles, take
    otherwise (always take off-TPU: a per-step interpreted kernel is
    pure overhead; parity tests force ``impl='fused'``).
    """
    interpret = auto_interpret(interpret)
    rows = table.shape[0]
    ids = jnp.asarray(ids, jnp.int32)
    valid = (ids >= 0) & (ids < rows)
    clamped = jnp.where(valid, ids, rows)     # sentinel == rows
    scale = valid.astype(jnp.float32)
    if weights is not None:
        scale = scale * jnp.asarray(weights, jnp.float32)
    if impl == 'auto':
        impl = 'fused' if lookup_plan(ids.shape[0], table.shape[1],
                                      table.dtype, interpret,
                                      block_rows) else 'take'
    if impl == 'take':
        return _take_lookup(table, clamped, scale)
    if impl != 'fused':
        raise ValueError(f'unknown impl {impl!r}; '
                         "expected 'auto', 'fused' or 'take'")
    return _fused_lookup((block_rows, interpret), table, clamped, scale)
