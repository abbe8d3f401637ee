"""One overlap scheduler — *when* each collective runs relative to compute.

The repo grew three bespoke latency-hiding mechanisms — the decomposed TP
rings (:mod:`tpusystem.parallel.overlap`), GSPMD's implicit FSDP
gather/scatter (:mod:`tpusystem.parallel.sharding`), and the fused MoE
kernels — each behind its own knob, none composable. This module owns the
scheduling decision as one strategy object, :class:`OverlapSchedule`, and
implements its first two big clients:

* **TP rings** (``tp='overlap'``): the existing
  :func:`~tpusystem.parallel.overlap.allgather_matmul` /
  :func:`~tpusystem.parallel.overlap.matmul_reducescatter` decompositions,
  unchanged semantics, selected by the schedule.
* **FSDP prefetch** (``fsdp='prefetch'``): GSPMD lowers a ZeRO-3 layer to
  a *monolithic* parameter all-gather on the critical path of every block
  and a *monolithic* gradient reduce-scatter on its backward. Here the
  FFN kernels enter the manual region still FSDP-sharded and are gathered
  by a ``custom_vjp`` ring pair mirroring the TP decompositions:
  forward, :func:`~tpusystem.parallel.collectives.ring_allgather` issues
  every kernel's gather at FFN entry — the down-projection's transfer
  hides under the up-projection matmul + activation, and the first gather
  depends only on the parameters, so XLA's latency-hiding scheduler is
  free to float it above the attention block that precedes the FFN;
  backward, the transpose is
  :func:`~tpusystem.parallel.collectives.ring_reducescatter` of the
  weight cotangent — issued where autodiff reverses the gather, *after*
  the activation/input cotangents the next layer's backward needs, so the
  scatter is deferred under the remaining backward matmuls instead of
  serializing against them.

**Composition** is the point: :func:`scheduled_ffn` /
:func:`scheduled_swiglu` run both clients inside ONE fully-manual
``shard_map`` — the FSDP weight gather rides ahead of the TP activation
ring, the TP weight-gradient ring feeds straight into the FSDP gradient
scatter — where the three-knob world could not express "prefetch the
fsdp shards of the kernel the model ring is about to consume".

Fallbacks are planned, never implicit: the pure :func:`fsdp_plan` helper
pins which path every leaf takes — ``'skip'`` (axis size 1, leaf below
``fsdp_min_size``, or no divisible dimension: the leaf was never sharded,
nothing to gather), ``'one-shot'`` (the monolithic ``lax.all_gather``
when the requested ``chunks`` cannot tile the shard), ``'ring'``
otherwise — and its dimension choice delegates to
:func:`tpusystem.parallel.sharding.fsdp_shard_dim`, the same function the
placement policy uses, so the manual collectives always agree with where
the policy actually put the shards. Keep ``fsdp_min_size`` equal between
the schedule and the policy (both default 4096) or jit inserts a
reshard at the manual boundary — correct, but the transfer lands back on
the critical path.

Two further collective families joined the schedule as arms of the same
strategy object (they are implemented where the collectives live, and
planned here):

* **Pipeline p2p** (``pp='overlap'``): the GPipe loop
  (:func:`tpusystem.parallel.pipeline.pipeline_apply`) issues the next
  microbatch's activation ``ppermute`` send *under* the current
  microbatch's stage compute — the skewed double-buffered tick (each
  stage sends last tick's output while computing this tick's microbatch,
  the PR-2/PR-6 ring idiom: transfer launched before the compute that
  hides it), with a ``custom_vjp`` hop
  (:func:`tpusystem.parallel.collectives.pp_hop`) so the backward's
  reversed sends hide under the backward matmuls the same way. The pure
  :func:`pp_plan` pins the one-shot fallback (classic post-compute
  sends) when the microbatch rows won't split into ``chunks`` ppermutes
  or the interleaved schedule owns the ticks.
* **MoE expert all-to-all** (``moe='overlap'``): the quota'd sharded
  sparse dispatch (:class:`tpusystem.ops.moe.MoEMLP`) splits its local
  token rows into microbatch pieces and issues piece ``k+1``'s dispatch
  ``all_to_all`` under the expert matmuls of piece ``k`` (the return
  exchange of ``k`` rides under the matmuls of ``k+1``). The pure
  :func:`moe_plan` pins the one-shot fallback (the single whole-batch
  exchange) for the ragged exchanges (receiver-seated, not yet
  pipelined) and for row counts that won't split.

Model wiring: GPT-2 and Llama accept ``schedule=OverlapSchedule(...)``
(threaded through ``Block``/``BlockSpan`` and the Llama twins, scan and
unrolled paths; ``GPT2Pipelined`` threads ``pp=`` into the GPipe loop
and ``moe=`` reaches :class:`~tpusystem.ops.moe.MoEMLP` through the
block plumbing); :func:`resolve_schedule` turns ``schedule=None`` into
the all-GSPMD default. Param trees are built from the same
``DenseParams`` holders either way — the knob never changes a
checkpoint.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.parallel.collectives import ring_allgather, ring_reducescatter
from tpusystem.parallel.mesh import DATA, FSDP, MODEL, SEQ
from tpusystem.parallel.overlap import (_out_dtype, _partial_matmul,
                                        _row_specs, allgather_matmul,
                                        matmul_reducescatter,
                                        overlap_applicable)
from tpusystem.parallel.sharding import fsdp_shard_dim
from tpusystem.registry import register


@register
class OverlapSchedule:
    """Which collectives are decomposed and scheduled, and how finely.

    Args:
        tp: ``'gspmd'`` leaves the Megatron TP collectives to the
            partitioner (monolithic); ``'overlap'`` routes the FFN through
            the decomposed latency-hiding rings
            (:mod:`tpusystem.parallel.overlap`).
        fsdp: ``'gspmd'`` leaves the ZeRO-3 parameter gather / gradient
            scatter to the partitioner; ``'prefetch'`` gathers the FFN
            kernels with the decomposed ring pair at FFN entry and
            scatters their gradients where autodiff reverses it — off the
            critical path both ways.
        chunks: per-hop ``ppermute`` payload split shared by every ring
            this schedule owns (TP and FSDP) — finer interleave for the
            XLA scheduler at more per-transfer overhead.
        fsdp_min_size: leaves with fewer elements are expected unsharded
            (must match the placement policy's ``fsdp_min_size``; the
            plans consult it so a tiny bias is never gathered).
        pp: ``'gspmd'`` keeps the classic GPipe tick (stage-to-stage
            ``ppermute`` after the compute that produced it, on the
            critical path between ticks); ``'overlap'`` skews the GPipe
            loop so every send is issued *under* the next microbatch's
            stage compute (:func:`~tpusystem.parallel.pipeline
            .pipeline_apply`; backward's reversed sends hide under the
            backward matmuls via the ``pp_hop`` custom_vjp).
        moe: ``'gspmd'`` keeps the one-shot expert exchange (the whole
            local batch's ``all_to_all`` before any expert matmul);
            ``'overlap'`` splits the quota'd sharded dispatch into
            microbatch pieces and issues piece ``k+1``'s dispatch under
            the expert matmuls of piece ``k``
            (:class:`tpusystem.ops.moe.MoEMLP`).

    A registered entity: its knobs capture into the experiment identity
    hash (like :class:`~tpusystem.parallel.mesh.MeshSpec`), so runs under
    different schedules are distinguishable while their checkpoints stay
    interchangeable (the schedule never changes a param tree).
    """

    def __init__(self, tp: str = 'gspmd', fsdp: str = 'gspmd',
                 chunks: int = 1, fsdp_min_size: int = 4096,
                 pp: str = 'gspmd', moe: str = 'gspmd'):
        if tp not in ('gspmd', 'overlap'):
            raise ValueError(f'unknown schedule tp {tp!r}; '
                             "expected 'gspmd' or 'overlap'")
        if fsdp not in ('gspmd', 'prefetch'):
            raise ValueError(f'unknown schedule fsdp {fsdp!r}; '
                             "expected 'gspmd' or 'prefetch'")
        if pp not in ('gspmd', 'overlap'):
            raise ValueError(f'unknown schedule pp {pp!r}; '
                             "expected 'gspmd' or 'overlap'")
        if moe not in ('gspmd', 'overlap'):
            raise ValueError(f'unknown schedule moe {moe!r}; '
                             "expected 'gspmd' or 'overlap'")
        if chunks < 1:
            raise ValueError(f'chunks must be >= 1, got {chunks}')
        self.tp = tp
        self.fsdp = fsdp
        self.chunks = chunks
        self.fsdp_min_size = fsdp_min_size
        self.pp = pp
        self.moe = moe

    def _key(self):
        return (self.tp, self.fsdp, self.chunks, self.fsdp_min_size,
                self.pp, self.moe)

    def __eq__(self, other):
        return (isinstance(other, OverlapSchedule)
                and self._key() == other._key())

    def __hash__(self):
        return hash(self._key())

    def __repr__(self):
        return (f'OverlapSchedule(tp={self.tp!r}, fsdp={self.fsdp!r}, '
                f'chunks={self.chunks}, fsdp_min_size={self.fsdp_min_size}, '
                f'pp={self.pp!r}, moe={self.moe!r})')

    @classmethod
    def for_policy(cls, policy, *, tp: str = 'gspmd',
                   fsdp: str = 'prefetch', chunks: int = 1,
                   pp: str = 'gspmd', moe: str = 'gspmd'):
        """Schedule paired to a placement policy: ``fsdp_min_size`` is
        copied from the :class:`~tpusystem.parallel.sharding.ShardingPolicy`
        so the manual in_specs replicate its placement exactly. A
        mismatched pair is still correct, but jit inserts a reshard at
        the manual boundary — the transfer this schedule exists to hide."""
        return cls(tp=tp, fsdp=fsdp, chunks=chunks,
                   fsdp_min_size=policy.fsdp_min_size, pp=pp, moe=moe)


def resolve_schedule(schedule) -> OverlapSchedule:
    """The models' knob seam: ``schedule=None`` is the default
    :class:`OverlapSchedule` (every axis on GSPMD); anything else must
    be an :class:`OverlapSchedule`."""
    if schedule is None:
        return OverlapSchedule()
    if not isinstance(schedule, OverlapSchedule):
        raise TypeError('schedule= expects an OverlapSchedule, got '
                        f'{type(schedule).__name__}')
    return schedule


class FsdpPlan(NamedTuple):
    """Which path one leaf's FSDP gather takes.

    ``path`` is ``'ring'`` (decomposed latency-hiding gather),
    ``'one-shot'`` (monolithic ``lax.all_gather`` — the requested chunks
    cannot tile the shard), or ``'skip'`` (the leaf was never
    fsdp-sharded: trivial axis, tiny leaf, or no divisible dimension —
    it arrives whole, no collective). ``dim`` is the gathered dimension
    (``-1`` when skipped), ``chunks`` the per-hop ppermute split the ring
    will use, ``reason`` documents a fallback.
    """

    path: str
    dim: int
    chunks: int
    reason: str


def fsdp_plan(shape: tuple[int, ...], ring: int, *, taken=(),
              chunks: int = 1, min_size: int = 4096,
              row_split: int = 1) -> FsdpPlan:
    """Plan one leaf's FSDP prefetch — pure, so tests can pin the path.

    Mirrors the placement side exactly: a leaf the policy's
    ``_with_fsdp`` left unsharded (below ``min_size``, or no unclaimed
    dimension divides ``ring``) plans ``'skip'``, and the gathered
    dimension is :func:`~tpusystem.parallel.sharding.fsdp_shard_dim`'s
    choice (``taken`` = indices already claimed by TP rule axes).
    ``row_split`` is how many ways dimension 0 is already sharded
    *inside* the manual region by those rule axes (the TP ring over a
    down-projection's rows): the chunk-tiling check must see the LOCAL
    row count the ppermute will actually split, or a plan could say
    ``'ring'`` for a shard the ring cannot chunk and crash at trace
    time instead of falling back.
    """
    if ring == 1:
        return FsdpPlan('skip', -1, 1, 'axis_size == 1')
    if math.prod(shape) < min_size:
        return FsdpPlan('skip', -1, 1,
                        f'leaf below fsdp_min_size ({min_size})')
    dim = fsdp_shard_dim(tuple(shape), ring, tuple(taken))
    if dim is None:
        return FsdpPlan('skip', -1, 1,
                        'no unsharded dimension divisible by the fsdp axis')
    if shape[0] % row_split:
        return FsdpPlan('one-shot', dim, 1,
                        f'rows ({shape[0]}) not divisible by the row '
                        f'split ({row_split})')
    shard_rows = (shape[0] // ring if dim == 0
                  else shape[0] // row_split)
    if chunks < 1 or shard_rows % chunks:
        return FsdpPlan('one-shot', dim, 1,
                        f'local shard rows ({shard_rows}) not divisible '
                        f'by chunks ({chunks})')
    return FsdpPlan('ring', dim, chunks, '')


_SKIP = FsdpPlan('skip', -1, 1, 'fsdp prefetch inactive')


class PpPlan(NamedTuple):
    """Which tick schedule the GPipe pipeline takes.

    ``path`` is ``'overlap'`` (the skewed double-buffered schedule: every
    stage-to-stage send issued under the next microbatch's compute),
    ``'one-shot'`` (the classic tick — send after the compute that
    produced it; the requested ``chunks`` cannot tile the microbatch
    rows, or the interleaved schedule owns the ticks), or ``'skip'``
    (``stage`` axis of size 1: there are no sends to hide). ``chunks``
    is the per-hop ppermute payload split the overlap hop will use,
    ``reason`` documents a fallback.
    """

    path: str
    chunks: int
    reason: str


def pp_plan(rows: int, stages: int, chunks: int = 1,
            interleave: int = 1) -> PpPlan:
    """Plan the pipeline's stage-to-stage sends — pure, so tests can pin
    the path.

    ``rows`` is the per-device microbatch's leading (batch) dimension —
    what :func:`~tpusystem.parallel.collectives.pp_hop` splits into
    ``chunks`` independent ``ppermute``\\ s. The skewed schedule pays one
    extra fill tick per stage (``M + 2(S-1)`` ticks vs ``M + S - 1``) to
    take every transfer off the tick-to-tick critical path — second-order
    at realistic ``M >= 4S``, which is why the fallback is the classic
    schedule, not a crash. The interleaved (``v > 1``) GPipe forward
    keeps its own tick formulas and stays classic.
    """
    if stages == 1:
        return PpPlan('skip', 1, 'axis_size == 1')
    if interleave > 1:
        return PpPlan('one-shot', 1,
                      'interleaved schedule keeps the classic ticks')
    if chunks < 1 or rows % chunks:
        return PpPlan('one-shot', 1,
                      f'microbatch rows ({rows}) not divisible by chunks '
                      f'({chunks})')
    return PpPlan('overlap', chunks, '')


class MoePlan(NamedTuple):
    """Which dispatch schedule the sharded sparse MoE takes.

    ``path`` is ``'overlap'`` (local rows split into ``pieces``
    microbatch pieces, piece ``k+1``'s dispatch ``all_to_all`` issued
    under the expert matmuls of piece ``k``), ``'one-shot'`` (the single
    whole-batch exchange — the ragged exchanges, or rows that won't
    split), or ``'skip'`` (expert axis of size 1: no exchange exists).
    ``reason`` documents a fallback.
    """

    path: str
    pieces: int
    reason: str


def moe_plan(local_rows: int, expert_size: int, exchange: str = 'quota',
             pieces: int = 2) -> MoePlan:
    """Plan the expert-parallel dispatch pipeline — pure, so tests can
    pin the path.

    Only the quota'd regular-``all_to_all`` formulation pipelines today:
    the ragged exchanges seat capacity at the *receiver* from gathered
    count matrices, so their geometry is a cross-piece dependency the
    pipeline would have to exchange twice. Rows must split evenly into
    ``pieces`` (each piece routes and seats independently — per-piece
    quotas are the quota path's per-sender drop discipline at finer
    grain; with ample capacity all formulations agree exactly).
    """
    if expert_size == 1:
        return MoePlan('skip', 1, 'axis_size == 1')
    if exchange != 'quota':
        return MoePlan('one-shot', 1,
                       f'{exchange!r} exchange seats at the receiver; '
                       'pipelined dispatch is quota-only')
    if pieces < 2 or local_rows % pieces or local_rows < 2 * pieces:
        return MoePlan('one-shot', 1,
                       f'local rows ({local_rows}) will not split into '
                       f'{pieces} pieces')
    return MoePlan('overlap', pieces, '')


class DecodeTpPlan(NamedTuple):
    """Which sharding path the serving engine's compiled steps take.

    ``path`` is ``'single'`` (no mesh, or a trivial ``model`` axis — the
    engine runs exactly as before on one device), ``'gspmd'`` (the decode
    and prefill programs run with TP-sharded matmuls: params placed by
    the module's ``partition_rules()``, the paged KV pool sharded over
    heads, block tables replicated so the host keeps ONE authority), or
    ``'unsupported'`` (the mesh carries a non-trivial axis serving cannot
    shard over — data/fsdp/seq/expert/stage parallelism belongs to
    training; serving batches are row-churned, not data-sharded).
    ``model`` is the TP degree, ``reason`` documents a fallback or gate.
    """

    path: str
    model: int
    reason: str


def decode_tp_plan(mesh) -> DecodeTpPlan:
    """Plan the engine's TP sharding — pure, so tests can pin the path.

    ``mesh`` is a built :class:`jax.sharding.Mesh` (or ``None``). Only
    the ``model`` axis may exceed 1: the engine's row dimension churns
    every step (admit/evict rewrite individual rows in place), so
    sharding rows across devices would turn every seat into a
    cross-device scatter. The fused Pallas chain has no ring arms yet —
    :func:`~tpusystem.train.decode_fused.fused_paged_reason` gates it
    separately and ``decode_impl='auto'`` falls back to the sharded
    flax step.
    """
    if mesh is None:
        return DecodeTpPlan('single', 1, 'no mesh')
    sizes = dict(getattr(mesh, 'shape', {}))
    model = sizes.get(MODEL, 1)
    offending = {axis: size for axis, size in sizes.items()
                 if axis != MODEL and size > 1}
    if offending:
        return DecodeTpPlan(
            'unsupported', model,
            f'serving shards over the {MODEL!r} axis only; mesh carries '
            f'non-trivial {sorted(offending)} — rows churn in place every '
            'step, so data-style sharding would scatter every seat')
    if model == 1:
        return DecodeTpPlan('single', 1, 'model axis of size 1')
    return DecodeTpPlan('gspmd', model, '')


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ring_gather(axis, dim, chunks, shard):
    return ring_allgather(shard, axis, dimension=dim, chunks=chunks)


def _ring_gather_fwd(axis, dim, chunks, shard):
    return _ring_gather(axis, dim, chunks, shard), None


def _ring_gather_bwd(axis, dim, chunks, _, grad):
    # the gather is a copy, so its transpose is the pure reduce-scatter
    # ring: each rank's block of the (per-device partial) cotangent summed
    # around the ring in f32, landing home sharded like the leaf. Issued
    # by autodiff AFTER the cotangents the next layer's backward depends
    # on, so it hides under the remaining backward matmuls. Reduction over
    # non-fsdp axes (data/seq replicas) is shard_map's transpose job —
    # the leaf's in_spec doesn't mention them.
    return (ring_reducescatter(grad, axis, dimension=dim, chunks=chunks),)


_ring_gather.defvjp(_ring_gather_fwd, _ring_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _one_shot_gather(axis, dim, shard):
    return lax.all_gather(shard, axis, axis=dim, tiled=True)


def _one_shot_gather_fwd(axis, dim, shard):
    return _one_shot_gather(axis, dim, shard), None


def _one_shot_gather_bwd(axis, dim, _, grad):
    # lax.all_gather's native transpose would psum_scatter the raw
    # (possibly bf16) cotangent; scatter the f32 cotangent and cast once
    # instead, so a leaf whose chunks stop tiling keeps the SAME
    # f32-reduction contract as the ring path it fell back from
    total = lax.psum_scatter(grad.astype(jnp.float32), axis,
                             scatter_dimension=dim, tiled=True)
    return (total.astype(grad.dtype),)


_one_shot_gather.defvjp(_one_shot_gather_fwd, _one_shot_gather_bwd)


def prefetched(shard, plan: FsdpPlan, axis: str = FSDP):
    """Gather one FSDP-sharded leaf inside ``shard_map``, per its plan.

    ``'ring'`` is the decomposed custom_vjp pair (gather forward,
    reduce-scatter backward); ``'one-shot'`` the monolithic
    ``lax.all_gather`` (its transpose scatters the f32 cotangent — the
    fallback keeps the ring's reduction contract); ``'skip'`` returns
    the leaf untouched.
    """
    if plan.path == 'skip':
        return shard
    if plan.path == 'one-shot':
        return _one_shot_gather(axis, plan.dim, shard)
    return _ring_gather(axis, plan.dim, plan.chunks, shard)


def _weight_spec_plan(base_entries, shape, prefetch_on: bool,
                      schedule: OverlapSchedule, fsdp_size: int,
                      row_split: int = 1):
    """(in_spec, plan) for one FFN kernel: the TP base spec with the fsdp
    axis added on exactly the dimension the placement policy picked.
    ``row_split`` = the TP axis size when ``base_entries[0]`` carries it
    (a down-projection's rows are TP-sharded inside the manual region,
    so the plan's chunk check must see the local row count)."""
    entries = list(base_entries)
    if not prefetch_on:
        return P(*entries), _SKIP
    taken = [index for index, axis in enumerate(entries) if axis is not None]
    plan = fsdp_plan(shape, fsdp_size, taken=taken, chunks=schedule.chunks,
                     min_size=schedule.fsdp_min_size, row_split=row_split)
    if plan.path != 'skip':
        entries[plan.dim] = FSDP
    return P(*entries), plan


def _prefetch_on(schedule: OverlapSchedule, sizes, batch: int) -> bool:
    """The ONE prefetch-safety gate — shared by :func:`schedule_applicable`
    and the ``scheduled_*`` entry points so the condition that prevents
    the fsdp-replicated-batch gradient double-count can never diverge
    from the condition that activates the ring scatter. The manual
    gradient scatter assumes each device contributed a distinct batch
    slice; a replicated batch (e.g. ``module.init``'s batch-1 trace)
    takes the GSPMD path instead."""
    fsdp_size = sizes.get(FSDP, 1)
    return (schedule.fsdp == 'prefetch' and fsdp_size > 1
            and batch % (sizes.get(DATA, 1) * fsdp_size) == 0)


def _prefetch_applicable(schedule, mesh, hidden_shape, grown_features: int,
                         axis: str) -> bool:
    sizes = dict(mesh.shape)
    batch, seq, _ = hidden_shape
    if not _prefetch_on(schedule, sizes, batch):
        return False
    ring = sizes.get(axis, 1)
    if ring > 1:
        return overlap_applicable(mesh, hidden_shape, grown_features, axis)
    return seq % sizes.get(SEQ, 1) == 0


def schedule_applicable(schedule: OverlapSchedule, mesh, hidden_shape,
                        grown_features: int, axis: str = MODEL) -> bool:
    """Should the FFN take the manual scheduled path for this shape?

    True when the schedule decomposes at least one collective family the
    shape supports: TP rings per
    :func:`~tpusystem.parallel.overlap.overlap_applicable`, or FSDP
    prefetch when the fsdp axis is non-trivial AND the batch genuinely
    shards over ``(data, fsdp)``.
    Shapes that qualify for neither fall back to the GSPMD Dense path
    per call site — same params, so the fallback never changes a tree.
    """
    if mesh is None:
        return False
    if (schedule.tp == 'overlap'
            and overlap_applicable(mesh, hidden_shape, grown_features, axis)):
        return True
    return _prefetch_applicable(schedule, mesh, hidden_shape,
                                grown_features, axis)


def _tp_up(rows, w, axis, schedule, sizes):
    """``all_gather(rows) @ w`` under the schedule: the decomposed ring
    when ``tp='overlap'``, the one-shot manual collective otherwise
    (still f32-accumulated — the module's reduction contract)."""
    if schedule.tp == 'overlap' and axis in sizes:
        return allgather_matmul(rows, w, axis, chunks=schedule.chunks)
    if sizes.get(axis, 1) > 1:
        rows = lax.all_gather(rows, axis, axis=0, tiled=True)
    return _partial_matmul(rows, w).astype(_out_dtype(rows, w))


def _tp_down(grown, w, axis, schedule, sizes):
    """``psum_scatter(grown @ w)`` under the schedule — dual of
    :func:`_tp_up`; the one-shot path scatters the f32 product before
    casting (the overlap module's fallback discipline)."""
    if schedule.tp == 'overlap' and axis in sizes:
        return matmul_reducescatter(grown, w, axis, chunks=schedule.chunks)
    product = _partial_matmul(grown, w)
    if sizes.get(axis, 1) > 1:
        product = lax.psum_scatter(product, axis, scatter_dimension=0,
                                   tiled=True)
    return product.astype(_out_dtype(grown, w))


def scheduled_ffn(x, kernel_up, bias_up, kernel_down, bias_down, mesh, *,
                  schedule: OverlapSchedule, activation=jax.nn.gelu,
                  axis: str = MODEL):
    """Sequence-sharded FFN (bias + activation, GPT-2) under one schedule.

    Generalizes :func:`~tpusystem.parallel.overlap.tp_ffn`: the same
    fully-manual ``shard_map`` (batch over ``(data, fsdp)``, sequence
    rows over ``(seq, model)``), with the kernels entering still
    FSDP-sharded when ``schedule.fsdp='prefetch'`` — both kernel gathers
    issue at body entry (the down kernel's transfer hides under the up
    matmul + activation), then the TP collectives run decomposed or
    one-shot per ``schedule.tp``. Biases ride their TP specs untouched
    (they are a rounding error of the FSDP bytes and usually below
    ``fsdp_min_size`` anyway). Weight in_specs replicate the placement
    policy's choice bit-for-bit (same :func:`fsdp_shard_dim`, same
    ``min_size``), so jit inserts no resharding.
    """
    sizes = dict(mesh.shape)
    tp_axis = axis if axis in sizes else None
    fsdp_size = sizes.get(FSDP, 1)
    prefetch_on = _prefetch_on(schedule, sizes, x.shape[0])
    spec_up, plan_up = _weight_spec_plan(
        (None, tp_axis), kernel_up.shape, prefetch_on, schedule, fsdp_size)
    spec_down, plan_down = _weight_spec_plan(
        (tp_axis, None), kernel_down.shape, prefetch_on, schedule, fsdp_size,
        row_split=sizes.get(axis, 1))

    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(_row_specs(mesh, x.shape[0], axis), spec_up, P(tp_axis),
                  spec_down, P(None)),
        out_specs=_row_specs(mesh, x.shape[0], axis))
    def mapped(x, w_up, b_up, w_down, b_down):
        # prefetch order: both kernel gathers issue before the first
        # matmul, so the down kernel's hop rides under the up matmul
        w_up = prefetched(w_up, plan_up)
        w_down = prefetched(w_down, plan_down)
        batch, seq, dim = x.shape
        rows = x.reshape(batch * seq, dim)
        grown = _tp_up(rows, w_up, axis, schedule, sizes)
        grown = activation(grown + b_up)
        out = _tp_down(grown, w_down, axis, schedule, sizes)
        # bias lands after the scatter so the sum counts it exactly once
        out = out + b_down
        return out.reshape(batch, seq, dim)

    return mapped(x, kernel_up, bias_up, kernel_down, bias_down)


def scheduled_swiglu(x, kernel_gate, kernel_up, kernel_down, mesh, *,
                     schedule: OverlapSchedule, axis: str = MODEL):
    """Sequence-sharded SwiGLU FFN (Llama) under one schedule.

    Generalizes :func:`~tpusystem.parallel.overlap.tp_swiglu`: gate and
    up kernels gather over fsdp first (the down kernel's gather hides
    under the fused ring), then concatenate into the single
    ``[dim, 2 * grown]`` right operand so the sequence rows ride the TP
    ring ONCE for both matmuls. No biases (Llama convention).
    """
    sizes = dict(mesh.shape)
    tp_axis = axis if axis in sizes else None
    fsdp_size = sizes.get(FSDP, 1)
    prefetch_on = _prefetch_on(schedule, sizes, x.shape[0])
    spec_gate, plan_gate = _weight_spec_plan(
        (None, tp_axis), kernel_gate.shape, prefetch_on, schedule, fsdp_size)
    spec_up, plan_up = _weight_spec_plan(
        (None, tp_axis), kernel_up.shape, prefetch_on, schedule, fsdp_size)
    spec_down, plan_down = _weight_spec_plan(
        (tp_axis, None), kernel_down.shape, prefetch_on, schedule, fsdp_size,
        row_split=sizes.get(axis, 1))

    @functools.partial(
        jax.shard_map, mesh=mesh, check_vma=False,
        in_specs=(_row_specs(mesh, x.shape[0], axis), spec_gate, spec_up,
                  spec_down),
        out_specs=_row_specs(mesh, x.shape[0], axis))
    def mapped(x, w_gate, w_up, w_down):
        w_gate = prefetched(w_gate, plan_gate)
        w_up = prefetched(w_up, plan_up)
        w_down = prefetched(w_down, plan_down)
        batch, seq, dim = x.shape
        rows = x.reshape(batch * seq, dim)
        fused = jnp.concatenate([w_gate, w_up], axis=1)
        grown = _tp_up(rows, fused, axis, schedule, sizes)
        gate, up = jnp.split(grown, 2, axis=1)
        # jax.nn.silu IS flax's nn.silu (a re-export) — identical numerics
        # to the GSPMD Dense path
        hidden = jax.nn.silu(gate) * up
        out = _tp_down(hidden, w_down, axis, schedule, sizes)
        return out.reshape(batch, seq, dim)

    return mapped(x, kernel_gate, kernel_up, kernel_down)
