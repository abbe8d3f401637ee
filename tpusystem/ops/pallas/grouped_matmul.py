"""Grouped gather-matmul — Pallas TPU kernels for fused MoE dispatch/combine.

The megablocks insight (Gale et al., 2022) applied to this repo's MoE
decomposition: the expert matmuls keep the MXU busy while dispatch/combine
are pure HBM row traffic the MXU idles through. These kernels make the
data movement ride the matmuls instead of preceding/following them:

* :func:`gather_rows_matmul` — the **dispatch direction**. For each expert
  the kernel walks that expert's seating indices (scalar-prefetched) and
  DMAs activation rows from the *unpermuted* token array — which never
  leaves HBM — straight into a VMEM tile that feeds the expert's matmul.
  The [experts*capacity, dim] dispatch buffer of the gather/scatter impls
  is never materialized: the standalone dispatch copy disappears into the
  first expert matmul's loads. Row gathers are double-buffered (tile c+1's
  rows stream in while tile c's hidden sweep runs on the MXU).

* :func:`matmul_scatter_rows` — the **combine direction** (and, with
  swapped operands, the transpose of the dispatch direction). A grouped
  matmul whose epilogue scatters each finished row — scaled by its combine
  weight — directly onto its token's output row via read-modify-write
  DMAs. The k-way weighted sum happens in the epilogue: no token-order
  gather pass ever reads the expert buffer back. TPU Pallas grids execute
  sequentially on a core and rows within one tile belong to one expert
  (distinct tokens), so the RMW accumulation is race-free by construction.

Both kernels take ``transpose_rhs`` so the backward pass *reuses the same
kernels with swapped operands* (d_buffer = gather-matmul of the output
cotangent against w2^T; d_tokens = matmul-scatter of the hidden cotangent
against w1^T) — the discipline the fused flash backward proved. MXU
accumulation is float32 throughout (``preferred_element_type``), rounded
once to the output dtype, matching the gather impl's numerics class.

Row indices use ``rows`` (the source/destination array length) as the
sentinel for empty slots / dropped assignments: gathered sentinel rows are
masked to zero through the per-row scale, scattered sentinel rows skip
their DMAs entirely. ``interpret=None`` auto-selects interpreter mode
off-TPU, so tier-1 CPU tests exercise the kernels' numerics directly.
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
                  # [rows] f32 vector is not Mosaic-lowerable (see
                  # flash.py's STATS note); 8 replicated lanes are.


def _pick_block(size: int, want: int, granule: int) -> int | None:
    """Largest divisor of ``size`` that is <= ``want`` and a multiple of
    ``granule`` (``granule=1`` in interpret mode — the interpreter has no
    tiling constraints, so tiny test shapes still block)."""
    want = min(want, size)
    best = None
    for candidate in range(granule, want + 1, granule):
        if size % candidate == 0:
            best = candidate
    return best


def _blocks(rows_per_group: int, inner: int, interpret: bool,
            want_rows: int, want_inner: int, dtype):
    # sublane tile grows as elements shrink: (8, 128) f32, (16, 128) bf16
    sublanes = SUBLANES * 4 // max(1, jnp.dtype(dtype).itemsize)
    granule = 1 if interpret else sublanes
    inner_granule = 1 if interpret else LANES
    block_rows = _pick_block(rows_per_group, want_rows, granule)
    block_inner = _pick_block(inner, want_inner, inner_granule)
    if block_rows is None or block_inner is None:
        raise ValueError(
            f'grouped_matmul cannot tile rows_per_group={rows_per_group}, '
            f'inner={inner} on TPU (need multiples of {granule}/'
            f'{inner_granule}); pad the capacity/hidden dims or use '
            "sparse_impl='gather'")
    return block_rows, block_inner


def _scale_input(scale: jax.Array) -> jax.Array:
    """[rows] f32 -> [rows, SCALE_LANES] replicated (Mosaic-tileable)."""
    return jnp.tile(scale.astype(jnp.float32)[:, None], (1, SCALE_LANES))


def _gather_matmul_kernel(row_ref, src_any, rhs_ref, scale_ref, out_ref,
                          x_scr, sem, *, block_rows: int, tiles: int,
                          transpose_rhs: bool):
    """Grid (groups, row_tiles, n_tiles), n innermost. At n == 0 the row
    tile's source rows are DMA'd from HBM into the double-buffered VMEM
    scratch — tile t+1's rows are issued right after tile t's wait, so the
    gather streams behind the n-sweep's matmuls."""
    group, row_tile, n_idx = (pl.program_id(0), pl.program_id(1),
                              pl.program_id(2))
    row_tiles = pl.num_programs(1)
    tile = group * row_tiles + row_tile

    def for_each_row(t, action):
        def body(i, _):
            row = row_ref[t * block_rows + i]
            copy = pltpu.make_async_copy(src_any.at[row],
                                         x_scr.at[t % 2, i], sem.at[t % 2])
            action(copy)
            return 0
        jax.lax.fori_loop(0, block_rows, body, 0)

    @pl.when(n_idx == 0)
    def _gather():
        @pl.when(tile == 0)
        def _prologue():
            for_each_row(0, lambda copy: copy.start())
        for_each_row(tile, lambda copy: copy.wait())

        @pl.when(tile + 1 < tiles)
        def _stream_next():
            for_each_row(tile + 1, lambda copy: copy.start())

    gathered = x_scr[tile % 2]
    # per-row scale in the compute dtype: zero for empty slots (masking the
    # stale/clamped gather), the combine weight on the backward reuse —
    # the same multiply the gather impl's custom_vjp pair applies
    scaled = gathered * scale_ref[:, :1].astype(gathered.dtype)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        scaled, rhs_ref[0], contract,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


def gather_rows_matmul(src, rhs, row_ids, row_scale, *,
                       rows_per_group: int, transpose_rhs: bool = False,
                       out_dtype=None, block_rows: int = 512,
                       block_cols: int = 512,
                       interpret: bool | None = None):
    """Fused gather + grouped matmul: ``out[j] = (row_scale[j] *
    src[row_ids[j]]) @ rhs[j // rows_per_group]``.

    Args:
        src: [n, k] token array — stays in HBM; rows are DMA'd on demand.
        rhs: [groups, k, m] stacked weights ([groups, m, k] with
            ``transpose_rhs``, contracted over the trailing dim — the
            backward reuse never materializes a transposed weight copy).
        row_ids: [groups * rows_per_group] int32 source row per output
            row, pre-clamped to [0, n); masked by ``row_scale`` instead
            of bounds-checked.
        row_scale: [groups * rows_per_group] float per-row factor — 0/1
            seat validity on the dispatch direction, the combine weight
            on the d_buffer backward direction (applied in the compute
            dtype, matching the gather impl).
        rows_per_group: static rows per group (= expert capacity).

    Returns [groups * rows_per_group, m] in ``out_dtype`` (default:
    ``src.dtype``), accumulated in float32 on the MXU.
    """
    interpret = auto_interpret(interpret)
    groups = rhs.shape[0]
    contract_dim = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    out_cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if src.shape[1] != contract_dim:
        raise ValueError(f'src cols {src.shape[1]} != rhs contract dim '
                         f'{contract_dim}')
    out_dtype = out_dtype or src.dtype
    block_rows, block_cols = _blocks(rows_per_group, out_cols, interpret,
                                     block_rows, block_cols, src.dtype)
    row_tiles = rows_per_group // block_rows
    tiles = groups * row_tiles

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, block_cols, contract_dim),
                                lambda g, r, n, ids: (g, n, 0))
    else:
        rhs_spec = pl.BlockSpec((1, contract_dim, block_cols),
                                lambda g, r, n, ids: (g, 0, n))
    kernel = functools.partial(
        _gather_matmul_kernel, block_rows=block_rows, tiles=tiles,
        transpose_rhs=transpose_rhs)
    flops = 2 * groups * rows_per_group * contract_dim * out_cols
    bytes_accessed = (src.size * src.dtype.itemsize
                      + rhs.size * rhs.dtype.itemsize
                      + groups * rows_per_group * out_cols
                      * jnp.dtype(out_dtype).itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, row_tiles, out_cols // block_cols),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                rhs_spec,
                pl.BlockSpec((block_rows, SCALE_LANES),
                             lambda g, r, n, ids: (g * row_tiles + r, 0)),
            ],
            out_specs=pl.BlockSpec(
                (block_rows, block_cols),
                lambda g, r, n, ids: (g * row_tiles + r, n)),
            scratch_shapes=[
                pltpu.VMEM((2, block_rows, contract_dim), src.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (groups * rows_per_group, out_cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary', 'arbitrary')),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(row_ids, src, rhs, _scale_input(row_scale))


def _matmul_scatter_kernel(row_ref, lhs_ref, rhs_ref, bias_ref, scale_ref,
                           init_ref, out_any, rows_ref, acc, rd_scr, wr_scr,
                           sem, *, block_rows: int, tokens: int,
                           transpose_rhs: bool, save_rows: bool):
    """Grid (groups, row_tiles, k_tiles), k innermost: f32 accumulation
    over the contraction sweep; the epilogue on the last k step adds the
    bias, optionally stores the plain row block (the residual the MoE
    backward needs), then RMWs each weighted row onto its token's output
    row. Reads are batched (issue all, wait all), the merged tile is one
    vector op, writes are batched; sentinel rows skip their DMAs. The
    sequential TPU grid plus distinct tokens within a tile (one expert
    seats a token at most once) make the RMW exact."""
    del init_ref
    group, row_tile, k_idx = (pl.program_id(0), pl.program_id(1),
                              pl.program_id(2))
    k_steps = pl.num_programs(2)
    base = (group * pl.num_programs(1) + row_tile) * block_rows

    @pl.when(k_idx == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc[...] += jax.lax.dot_general(
        lhs_ref[...], rhs_ref[0], contract,
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == k_steps - 1)
    def _epilogue():
        tile = acc[...]
        if bias_ref is not None:
            tile = tile + bias_ref[0].astype(jnp.float32)
        finished = tile.astype(wr_scr.dtype)
        if save_rows:
            rows_ref[...] = finished

        def for_each_row(action):
            def body(i, _):
                token = row_ref[base + i]

                @pl.when(token < tokens)   # sentinel rows move nothing
                def _valid():
                    action(i, token)
                return 0
            jax.lax.fori_loop(0, block_rows, body, 0)

        def read(i, token):
            pltpu.make_async_copy(out_any.at[token], rd_scr.at[i],
                                  sem).start()

        def read_wait(i, token):
            pltpu.make_async_copy(out_any.at[token], rd_scr.at[i],
                                  sem).wait()

        for_each_row(read)
        for_each_row(read_wait)
        # the k-way weighted combine IS this add: each of a token's seated
        # choices lands here once, in the compute dtype like the gather
        # impl's weighted sum
        weighted = finished * scale_ref[:, :1].astype(finished.dtype)
        wr_scr[...] = rd_scr[...] + weighted

        def write(i, token):
            pltpu.make_async_copy(wr_scr.at[i], out_any.at[token],
                                  sem).start()

        def write_wait(i, token):
            pltpu.make_async_copy(wr_scr.at[i], out_any.at[token],
                                  sem).wait()

        for_each_row(write)
        for_each_row(write_wait)


def matmul_scatter_rows(lhs, rhs, bias, row_ids, row_scale, tokens: int, *,
                        rows_per_group: int, transpose_rhs: bool = False,
                        out_dtype=None, save_rows: bool = True,
                        block_rows: int = 512, block_k: int = 512,
                        interpret: bool | None = None):
    """Fused grouped matmul + scatter-combine: computes ``row[j] =
    lhs[j] @ rhs[j // rows_per_group] (+ bias)`` and accumulates
    ``out[row_ids[j]] += row_scale[j] * row[j]`` in the epilogue.

    Args:
        lhs: [groups * rows_per_group, k] expert-major buffer rows.
        rhs: [groups, k, m] stacked weights ([groups, m, k] with
            ``transpose_rhs``).
        bias: [groups, m] per-group bias added before the scatter, or
            ``None`` (the backward reuse has no bias).
        row_ids: [groups * rows_per_group] int32 destination token per
            row; ``tokens`` is the sentinel for empty slots / dropped
            assignments — their DMAs are skipped entirely.
        row_scale: [groups * rows_per_group] float combine weight (0 for
            empty slots; 1s on the backward reuse).
        tokens: number of output rows.
        save_rows: also return the plain (unweighted, biased) rows —
            the residual the MoE backward needs for d_weights/d_w2; the
            backward reuse passes False and skips that HBM write.

    Returns ``(out [tokens, m], rows [groups*rows_per_group, m] | None)``.
    """
    interpret = auto_interpret(interpret)
    groups = rhs.shape[0]
    contract_dim = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    out_cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if lhs.shape[1] != contract_dim:
        raise ValueError(f'lhs cols {lhs.shape[1]} != rhs contract dim '
                         f'{contract_dim}')
    out_dtype = out_dtype or lhs.dtype
    block_rows, block_k = _blocks(rows_per_group, contract_dim, interpret,
                                  block_rows, block_k, lhs.dtype)
    row_tiles = rows_per_group // block_rows

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, out_cols, block_k),
                                lambda g, r, k, ids: (g, 0, k))
    else:
        rhs_spec = pl.BlockSpec((1, block_k, out_cols),
                                lambda g, r, k, ids: (g, k, 0))
    row_block = pl.BlockSpec(
        (block_rows, out_cols),
        lambda g, r, k, ids: (g * row_tiles + r, 0))
    in_specs = [
        pl.BlockSpec((block_rows, block_k),
                     lambda g, r, k, ids: (g * row_tiles + r, k)),
        rhs_spec,
    ]
    operands = [lhs, rhs]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, out_cols),
                                     lambda g, r, k, ids: (g, 0)))
        operands.append(bias)
    in_specs.append(pl.BlockSpec((block_rows, SCALE_LANES),
                                 lambda g, r, k, ids:
                                 (g * row_tiles + r, 0)))
    operands.append(_scale_input(row_scale))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))   # zero init
    operands.append(jnp.zeros((tokens, out_cols), out_dtype))

    out_shape = [jax.ShapeDtypeStruct((tokens, out_cols), out_dtype)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if save_rows:
        out_shape.append(jax.ShapeDtypeStruct(
            (groups * rows_per_group, out_cols), out_dtype))
        out_specs.append(row_block)

    def kernel(row_ref, lhs_ref, rhs_ref, *rest):
        if bias is not None:
            bias_ref, rest = rest[0], rest[1:]
        else:
            bias_ref = None
        scale_ref, init_ref, out_ref = rest[0], rest[1], rest[2]
        rest = rest[3:]
        rows_ref = rest[0] if save_rows else None
        scratch = rest[1:] if save_rows else rest
        return _matmul_scatter_kernel(
            row_ref, lhs_ref, rhs_ref, bias_ref, scale_ref, init_ref,
            out_ref, rows_ref, *scratch, block_rows=block_rows,
            tokens=tokens, transpose_rhs=transpose_rhs,
            save_rows=save_rows)

    flops = 2 * groups * rows_per_group * contract_dim * out_cols
    bytes_accessed = (lhs.size * lhs.dtype.itemsize
                      + rhs.size * rhs.dtype.itemsize
                      + (1 + save_rows) * groups * rows_per_group * out_cols
                      * jnp.dtype(out_dtype).itemsize
                      + 2 * tokens * out_cols
                      * jnp.dtype(out_dtype).itemsize)
    # the prefetched ids are the LAST positional input index (bias/scale
    # shift it); the zeros init aliases output 0 so `out` needs no
    # in-kernel zeroing pass
    alias_index = 1 + len(operands) - 1   # ids + tensor operands, 0-based
    results = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, row_tiles, contract_dim // block_k),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_rows, out_cols), jnp.float32),
                pltpu.VMEM((block_rows, out_cols), out_dtype),
                pltpu.VMEM((block_rows, out_cols), out_dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=out_shape,
        input_output_aliases={alias_index: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary', 'arbitrary')),
        cost_estimate=pl.CostEstimate(flops=flops,
                                      bytes_accessed=bytes_accessed,
                                      transcendentals=0),
        interpret=interpret,
    )(row_ids, *operands)
    if save_rows:
        return results[0], results[1]
    return results[0], None
