"""Named collective wrappers for ``shard_map`` kernels.

The data plane of the distributed design (SURVEY.md §5): XLA collectives
over ICI within a slice and DCN across slices. GSPMD inserts most of these
implicitly from sharding annotations; explicit kernels (ring attention,
pipeline schedules, MoE dispatch) call these wrappers inside
``jax.shard_map``. They are thin by design — the value is one documented
vocabulary with ring-neighbor conventions fixed in a single place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec



def all_reduce_sum(value, axis: str):
    """Sum over every shard on ``axis`` (gradient reduction)."""
    return lax.psum(value, axis)


def all_reduce_mean(value, axis: str):
    return lax.pmean(value, axis)


def all_gather(value, axis: str, *, dimension: int = 0, tiled: bool = True):
    """Concatenate shards along ``dimension`` (FSDP weight gather)."""
    return lax.all_gather(value, axis, axis=dimension, tiled=tiled)


def reduce_scatter(value, axis: str, *, dimension: int = 0):
    """Sum then scatter along ``dimension`` (ZeRO gradient scatter)."""
    return lax.psum_scatter(value, axis, scatter_dimension=dimension, tiled=True)


def all_to_all(value, axis: str, *, split_dimension: int, concat_dimension: int):
    """Shard-transpose (MoE token dispatch, Ulysses head/seq swap)."""
    return lax.all_to_all(value, axis, split_axis=split_dimension,
                          concat_axis=concat_dimension, tiled=True)


def ring_shift(value, axis: str, *, reverse: bool = False):
    """Send this shard to the next (or previous) rank on the ring —
    the ``ppermute`` at the heart of ring attention and 1F1B pipelines.
    Neighbor convention: rank ``i`` sends to ``(i+1) % n`` when forward.
    """
    size = lax.axis_size(axis)
    if reverse:
        permutation = [(source, (source - 1) % size) for source in range(size)]
    else:
        permutation = [(source, (source + 1) % size) for source in range(size)]
    return lax.ppermute(value, axis, permutation)


def ring_shift_chunked(value, axis: str, *, chunks: int = 1,
                       reverse: bool = False):
    """:func:`ring_shift` with the payload split into ``chunks``
    independent ``ppermute``\\ s along dimension 0.

    Semantically identical to one monolithic shift; the split gives XLA's
    latency-hiding scheduler ``chunks`` independent transfers it can
    interleave with compute at finer granularity — the knob the
    decomposed TP matmuls (:mod:`tpusystem.parallel.overlap`) sweep.
    Shares :func:`ring_shift`'s neighbor convention exactly — rank ``i``
    sends to ``(i + 1) % n`` when forward — so after ``s`` forward shifts
    a device holds the shard of rank ``(i - s) % n``; the all-gather and
    reduce-scatter decompositions both index their row-blocks from that
    convention, which is what keeps the two duals' transposes reusable as
    each other's backward. Requires ``value.shape[0] % chunks == 0``
    (callers plan around this; see ``overlap.allgather_plan``).
    """
    if chunks <= 1:
        return ring_shift(value, axis, reverse=reverse)
    if value.shape[0] % chunks:
        raise ValueError(f'cannot split {value.shape[0]} rows into '
                         f'{chunks} ppermute chunks')
    pieces = jnp.split(value, chunks, axis=0)
    shifted = [ring_shift(piece, axis, reverse=reverse) for piece in pieces]
    return jnp.concatenate(shifted, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def pp_hop(axis, chunks, value):
    """One pipeline stage-to-stage hop: the forward chunked ring shift
    with an explicit transpose, so autodiff of a pipelined schedule
    issues *reversed* chunked sends.

    Forward is exactly :func:`ring_shift_chunked` (rank ``i`` sends to
    ``(i + 1) % n``); the custom backward is the reverse chunked shift of
    the cotangent (rank ``i`` sends to ``(i - 1) % n``) — a pure copy in
    both directions, bitwise-exact in any dtype. The value of the
    custom_vjp is *placement*: under the skewed GPipe schedule
    (``pp='overlap'`` in :class:`~tpusystem.parallel.schedule
    .OverlapSchedule`) the forward hop is issued at tick top, before the
    stage compute that hides it, and autodiff transposes that structure —
    the reversed send of backward tick ``t`` is independent of tick
    ``t``'s block vjp matmuls, so it hides under them instead of
    serializing the reversed ring.
    """
    return ring_shift_chunked(value, axis, chunks=chunks)


def _pp_hop_fwd(axis, chunks, value):
    return pp_hop(axis, chunks, value), None


def _pp_hop_bwd(axis, chunks, _, grad):
    return (ring_shift_chunked(grad, axis, chunks=chunks, reverse=True),)


pp_hop.defvjp(_pp_hop_fwd, _pp_hop_bwd)


def ring_allgather(value, axis: str, *, dimension: int = 0,
                   chunks: int = 1):
    """:func:`all_gather` decomposed into ``axis_size`` ring steps.

    Each device's shard rotates forward one hop per step
    (:func:`ring_shift_chunked`, the shared neighbor convention: after
    ``s`` forward shifts rank ``i`` holds the shard of rank
    ``(i - s) % n``) and is copied into its row-block of the full
    ``[..., n * shard, ...]`` result along ``dimension``. The next hop's
    ``ppermute`` is issued *before* the current block's copy — the
    latency-hiding order every ring in this repo uses — so XLA's
    scheduler can hide the transfers under whatever compute consumes the
    early blocks. The FSDP prefetch path
    (:mod:`tpusystem.parallel.schedule`) builds its parameter gather on
    this; semantically identical to one monolithic ``lax.all_gather``.
    Requires ``value.shape[0] % chunks == 0`` (callers plan around this;
    see ``schedule.fsdp_plan``).
    """
    ring = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    rows = value.shape[dimension]
    shape = list(value.shape)
    shape[dimension] = ring * rows
    out = jnp.zeros(shape, value.dtype)
    held = value
    incoming = ring_shift_chunked(held, axis, chunks=chunks)
    for step in range(ring):
        if step:
            held = incoming
            if step + 1 < ring:
                incoming = ring_shift_chunked(held, axis, chunks=chunks)
        source = (rank - step) % ring
        start = [0] * len(shape)
        start[dimension] = source * rows
        out = lax.dynamic_update_slice(out, held, tuple(start))
    return out


def ring_reducescatter(value, axis: str, *, dimension: int = 0,
                       chunks: int = 1):
    """:func:`reduce_scatter` decomposed into ``axis_size`` ring steps.

    The dual of :func:`ring_allgather`: at step ``t`` every device takes
    block ``(rank - 1 - t) % n`` of its full-size ``value`` along
    ``dimension`` and folds it into the running **float32** sum arriving
    from its predecessor; the sum's forward shift is issued *before* the
    next block's add, so after ``n`` steps block ``rank`` lands home
    carrying all ``n`` contributions with the transfers hidden under the
    compute that produced the later blocks. Semantically identical to
    ``lax.psum_scatter(..., tiled=True)`` up to f32 summation order;
    result is cast back to ``value.dtype``. The FSDP prefetch path uses
    this as the gradient scatter (the transpose of the parameter gather).
    """
    ring = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    rows = value.shape[dimension] // ring
    sizes = list(value.shape)
    sizes[dimension] = rows

    def block(step):
        start = [0] * len(sizes)
        start[dimension] = ((rank - 1 - step) % ring) * rows
        return lax.dynamic_slice(value, tuple(start), tuple(sizes))

    total = block(0).astype(jnp.float32)
    for step in range(1, ring):
        inflight = ring_shift_chunked(total, axis, chunks=chunks)
        total = inflight + block(step)
    return total.astype(value.dtype)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


# ---------------------------------------------------------------------------
# cross-replica parity (SDC detection)


def _bit_checksum(leaf):
    """Order-independent uint32 checksum of a leaf's raw bits.

    ``bitcast -> widen -> wrapping sum``: integer addition is commutative,
    so the checksum is layout- and reduction-order-independent — two
    replicas holding bit-identical data always agree, and any single bit
    flip changes the sum (multi-flip collisions are the usual mod-2^32
    checksum caveat). Float summation would not give that guarantee.
    """
    nbits = np.dtype(leaf.dtype).itemsize * 8
    if nbits > 32:   # 64-bit leaves split into two uint32 words
        bits = lax.bitcast_convert_type(leaf, jnp.uint32)
    else:
        bits = lax.bitcast_convert_type(
            leaf, jnp.dtype(f'uint{nbits}')).astype(jnp.uint32)
    return jnp.sum(bits, dtype=jnp.uint32)


@functools.lru_cache(maxsize=32)
def _checksum_program(mesh, specs, axis: str):
    """Compiled per-(mesh, layout) checksum gather — jit caches per shape."""
    others = tuple(name for name in mesh.axis_names if name != axis)

    def local(*shards):
        vec = jnp.stack([_bit_checksum(shard) for shard in shards])
        if others:
            # fold shard checksums into the replica's full-leaf checksum
            vec = lax.psum(vec, others)
        return lax.all_gather(vec, axis)

    mapped = jax.shard_map(local, mesh=mesh, in_specs=specs,
                        out_specs=PartitionSpec(), check_vma=False)
    return jax.jit(mapped)


def replica_checksums(tree, mesh, *, axis: str = 'data'):
    """Per-replica bit checksums of every leaf in ``tree``.

    The device half of the sentinel's SDC parity check
    (:meth:`tpusystem.train.Sentinel.check_parity`): each device checksums
    its local shard of every leaf, the checksums are summed over the
    non-``axis`` mesh axes (one scalar per leaf per replica) and
    all-gathered over ``axis`` — exchanged bytes are
    ``4 * leaves * axis_size``, independent of the model size, so the check
    is cheap enough for checkpoint cadence.

    Returns ``(matrix, paths)``: a ``[axis_size, leaves]`` uint32 numpy
    matrix (row ``r`` = replica ``r``'s per-leaf checksums; for params
    replicated over ``axis`` all rows must be equal) and the matching leaf
    path strings. The host read is one scalar matrix — the same cadence
    discipline as the health vector.
    """
    leaves = jax.tree.leaves(tree)
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(tree)[0]]
    specs = tuple(
        leaf.sharding.spec
        if isinstance(getattr(leaf, 'sharding', None), NamedSharding)
        else PartitionSpec()
        for leaf in leaves)
    program = _checksum_program(mesh, specs, axis)
    return np.asarray(jax.device_get(program(*leaves))), paths
