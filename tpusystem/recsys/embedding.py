"""Sharded embedding tables — the recommender workload's parameter tier.

A recommender model inverts every assumption the LLM workloads trained
into this codebase: parameters are dominated by embedding tables far too
large for one device (params >> activations), compute per token is tiny,
and the hot path is *row movement* — sparse gathers forward, scatter-adds
backward. This module supplies that tier:

* :class:`ShardedEmbedding` — a flax embedding whose table row-shards
  its vocab dimension over the combined ``expert``/``model`` mesh axes
  (:func:`tpusystem.parallel.sharding.table_row_spec`, the
  ``constrain_expert_major`` seam's sibling). The apply path runs inside
  ``shard_map`` with **device-side id→shard routing**: each shard
  translates global ids into its local row space, masks the ids it does
  not own, looks up its slice, and a ``psum`` over the table axes
  assembles the result — every id's row comes wholly from one shard, so
  the sum adds exact zeros and the sharded forward is **bitwise equal**
  to the unsharded one.

* a **unique-id dedup pass** (:func:`dedup_ids`) before the gather: a
  Zipfian id distribution makes duplicate ids the common case, so the
  table gather reads each distinct row once and the batch-side expansion
  is a cheap dense gather. The dedup also makes the backward's
  device-side scatter collision-free — duplicate cotangents fold into
  unique slots via XLA's segment-sum *before* the table scatter-add
  (the kernel still handles collisions for direct callers).

* the row movement itself rides the hoisted Pallas pair
  (:func:`tpusystem.ops.pallas.embedding_lookup.embedding_lookup` —
  gather + f32 scatter-add ``custom_vjp``), with the pure
  :func:`~tpusystem.ops.pallas.embedding_lookup.lookup_plan` pinning
  the ``jnp.take``/segment-sum fallback off-TPU or on untileable
  shapes.

Init is NEVER routed through ``shard_map`` (the single-init-authority
discipline from the overlap scheduler): the table param is drawn by a
plain initializer, so param trees and checkpoints are bitwise invariant
to the mesh and every knob here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.ops.pallas.embedding_lookup import embedding_lookup
from tpusystem.parallel.mesh import DATA, FSDP
from tpusystem.parallel.sharding import (TABLE_AXES, constrain_table_rows,
                                         table_row_spec)
from tpusystem.registry import register


def dedup_ids(ids, sentinel: int):
    """Static-shape unique-id pass: ``(reps, inverse)`` with
    ``reps[inverse[j]] == ids[j]``.

    ``reps`` is ``[n]`` — the distinct ids packed at the front, the rest
    padded with ``sentinel`` (an out-of-range id the lookup masks to a
    zero row, which ``inverse`` never points at). Pure sort/cumsum/
    scatter, so it jits with static shapes; callers map invalid ids to
    ``sentinel`` *before* deduping so all padding collapses into one
    rep. The values after expansion are identical with or without the
    pass — dedup is a traffic optimization, not a semantic knob."""
    n = ids.shape[0]
    order = jnp.argsort(ids)
    sorted_ids = jnp.take(ids, order)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_ids[1:] != sorted_ids[:-1]])
    slot = jnp.cumsum(first) - 1                    # slot per sorted element
    reps = jnp.full((n,), sentinel, jnp.int32).at[slot].set(sorted_ids)
    inverse = jnp.zeros((n,), jnp.int32).at[order].set(slot)
    return reps, inverse


def lookup(table, ids, weights=None, *, impl: str = 'auto',
           dedup: bool = True, block_rows: int = 256,
           interpret: bool | None = None):
    """Weighted lookup ``out[j] = w[j] * table[ids[j]]`` with the
    unique-id dedup pass in front of the gather.

    Ids outside ``[0, rows)`` (``-1`` multi-hot padding) produce zero
    rows and no gradient. With ``dedup=True`` the gather touches each
    distinct id once and the backward's batch-side scatter is
    collision-free; the output is bitwise identical either way."""
    rows = table.shape[0]
    ids = jnp.asarray(ids, jnp.int32)
    valid = (ids >= 0) & (ids < rows)
    sent = jnp.where(valid, ids, rows)
    if not dedup:
        return embedding_lookup(table, sent, weights, impl=impl,
                                block_rows=block_rows, interpret=interpret)
    reps, inverse = dedup_ids(sent, rows)
    unique_rows = embedding_lookup(table, reps, None, impl=impl,
                                   block_rows=block_rows,
                                   interpret=interpret)
    # batch-side expansion: a dense gather whose transpose (the
    # duplicate-folding segment-sum) runs before the table scatter-add
    gathered = jnp.take(unique_rows, inverse, axis=0)
    if weights is None:
        return gathered
    scaled = gathered.astype(jnp.float32) * jnp.asarray(
        weights, jnp.float32)[:, None]
    return scaled.astype(table.dtype)


def route_plan(vocab: int, count: int, mesh,
               axes=TABLE_AXES) -> str | None:
    """Pure shardability decision for one lookup: ``None`` when the
    device-side routed path applies, else the blocking reason (the
    caller falls back to the local lookup — GSPMD still places the
    table, it just routes the gather itself). Pinned by tests so mesh
    or shape drift cannot silently change which path runs."""
    if mesh is None:
        return 'no mesh'
    present = tuple(axis for axis in axes if axis in mesh.axis_names)
    shards = 1
    for axis in present:
        shards *= mesh.shape[axis]
    if shards == 1:
        return f'table axes {axes} all have size 1'
    if vocab % shards:
        return f'vocab {vocab} not divisible by {shards} table shards'
    row_shards = 1
    for axis in (DATA, FSDP):
        if axis in mesh.axis_names:
            row_shards *= mesh.shape[axis]
    if count % row_shards:
        return (f'{count} ids not divisible by the {row_shards}-way '
                f'batch sharding')
    return None


@register('ShardedEmbedding', excluded_kwargs={'mesh', 'parent', 'name'})
class ShardedEmbedding(nn.Module):
    """Embedding table row-sharded over the ``expert``/``model`` axes.

    ``__call__(ids, weights=None)`` accepts any id shape (``[B]``
    one-hot, ``[B, K]`` multi-hot with ``-1`` padding, ...) and returns
    ``ids.shape + (features,)`` rows; padded ids yield zero rows, so a
    multi-hot pool is a plain ``sum`` over the hot axis.

    On a mesh where :func:`route_plan` passes, the lookup runs inside
    ``shard_map``: ids (replicated across the table axes, row-sharded
    over data/fsdp with the batch) are routed device-side — global id →
    local row, non-owned ids masked — each shard gathers only its slice,
    and a ``psum`` over the table axes assembles rows. Exactly one shard
    contributes a given row and the rest add zeros, so the sharded
    forward is bitwise equal to the unsharded one. Otherwise (no mesh,
    size-1 table axes, indivisible shapes, init) the local path runs —
    same math, GSPMD left to its own placement.

    Attributes:
        vocab: table rows (must divide by the table-shard count).
        features: embedding dimension.
        mesh: mesh whose ``expert``/``model`` axes shard the rows.
        impl: row-movement impl — ``'auto'`` | ``'fused'`` | ``'take'``
            (:func:`~tpusystem.ops.pallas.embedding_lookup.embedding_lookup`).
        dedup: unique-id pass before the gather (:func:`dedup_ids`).
        init_scale: stddev of the normal table init.
    """

    vocab: int
    features: int
    mesh: object = None
    impl: str = 'auto'
    dedup: bool = True
    init_scale: float = 0.02

    @nn.compact
    def __call__(self, ids, weights=None):
        table = self.param('embedding',
                           nn.initializers.normal(self.init_scale),
                           (self.vocab, self.features), jnp.float32)
        shape = tuple(ids.shape)
        flat = jnp.asarray(ids, jnp.int32).reshape(-1)
        flat_w = (None if weights is None
                  else jnp.asarray(weights, jnp.float32).reshape(-1))
        blocked = (route_plan(self.vocab, flat.shape[0], self.mesh)
                   if not self.is_initializing() else 'initializing')
        if blocked is None:
            out = self._sharded(table, flat, flat_w)
        else:
            out = lookup(table, flat, flat_w, impl=self.impl,
                         dedup=self.dedup)
        return out.reshape(shape + (self.features,))

    def _sharded(self, table, flat, flat_w):
        """Device-side id→shard routing inside ``shard_map``."""
        mesh = self.mesh
        # the annotation point: pin the table row-sharded right up to
        # the manual boundary so GSPMD never reshards it on the way in
        table = constrain_table_rows(table, mesh)
        table_axes = tuple(axis for axis in TABLE_AXES
                           if axis in mesh.axis_names)
        sizes = [mesh.shape[axis] for axis in table_axes]
        shards = 1
        for size in sizes:
            shards *= size
        local_rows = self.vocab // shards
        row_axes = tuple(axis for axis in (DATA, FSDP)
                         if axis in mesh.axis_names)
        row_spec = P(row_axes) if row_axes else P()
        out_spec = P(row_axes, None) if row_axes else P(None, None)
        impl, dedup = self.impl, self.dedup
        # the weights operand exists only when the caller passed weights:
        # the unweighted hot path keeps lookup()'s fast branch (no ones
        # array sharded through the region, no extra multiply/round)
        weighted = flat_w is not None
        in_specs = (P(table_axes, None), row_spec) + (
            (row_spec,) if weighted else ())

        @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                           in_specs=in_specs, out_specs=out_spec)
        def run(local_table, ids, *maybe_w):
            # shard index in table_row_spec's expert-major order
            index = lax.axis_index(table_axes[0])
            for axis, size in zip(table_axes[1:], sizes[1:]):
                index = index * size + lax.axis_index(axis)
            local = ids - index * local_rows
            owned = (ids >= 0) & (local >= 0) & (local < local_rows)
            routed = jnp.where(owned, local, -1)     # -1 = masked out
            partial = lookup(local_table, routed,
                             maybe_w[0] if weighted else None,
                             impl=impl, dedup=dedup)
            # each id's row lives on exactly one shard; the psum adds
            # exact zeros from the others (bitwise-transparent)
            return lax.psum(partial, table_axes)

        return run(table, flat, *((flat_w,) if weighted else ()))
