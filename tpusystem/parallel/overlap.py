"""Latency-hiding tensor-parallel collectives — decomposed all-gather /
reduce-scatter matmuls over the ``model`` mesh axis.

GSPMD lowers a Megatron TP layer with sequence-sharded activations to a
*monolithic* all-gather before the up-projection and a *monolithic*
reduce-scatter after the down-projection, serializing the ICI transfer
against the MXU. This module applies the repo's latency-hiding idiom
(``ops/ring.py``: issue the next step's ``ppermute`` before this step's
compute) to those dense matmuls, after the decomposition strategy of
GSPMD/TPU-v4 systems work (Xu et al., GSPMD; Wang et al., "Overlap
Communication with Dependent Computation via Decomposition"):

* :func:`allgather_matmul` — ``all_gather(x) @ w`` as ``n`` ring steps.
  Each device's shard of ``x`` rotates around the ring; every step's
  partial matmul (one row-block of the result) runs while the next
  shard's ``ppermute`` is in flight, so the transfer hides under the
  matmul instead of preceding it.
* :func:`matmul_reducescatter` — the dual: ``psum_scatter(x @ w)`` as
  ``n`` chunked partial matmuls whose running f32 sum ring-shifts one hop
  per step toward the row-block's owner, hiding the reduction behind the
  next chunk's compute.

Both carry a ``custom_vjp`` built from the same two decompositions — the
transpose of an overlapped all-gather-matmul *is* an overlapped
matmul-reduce-scatter with swapped operands (and vice versa), and the
weight gradient is the shared :func:`_ring_transpose_matmul` ring (the
gathered operand rotates against static row-blocks of the other factor) —
so the backward pass overlaps exactly like the forward. All partial
matmuls accumulate in float32 (``preferred_element_type``) and the
cross-step reduce-scatter sum is carried in float32, then cast once to
the operands' result dtype.

Fallback: when ``axis_size == 1`` or the requested ``chunks`` cannot tile
the shard rows, both functions take the **one-shot** collective path
(``lax.all_gather`` + matmul / matmul + ``lax.psum_scatter``) — the plan
is computed by the pure :func:`allgather_plan` / :func:`reducescatter_plan`
helpers so tests can pin which path a shape takes.

Model wiring: :func:`tp_ffn` (bias + activation, GPT-2) and
:func:`tp_swiglu` (gate/up fused into ONE ring, Llama) shard_map a whole
sequence-sharded FFN over the mesh; the model families expose them behind
``schedule=OverlapSchedule(tp='overlap' | 'gspmd')``
(:mod:`tpusystem.parallel.schedule`).
Everything here is called *inside* ``shard_map`` except those two
wrappers, which build it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.parallel.collectives import ring_shift_chunked
from tpusystem.parallel.mesh import DATA, FSDP, MODEL, SEQ


class OverlapPlan(NamedTuple):
    """Which path a (shape, ring, chunks) combination takes.

    ``path`` is ``'overlap'`` (decomposed ring) or ``'one-shot'`` (the
    monolithic collective); ``chunks`` is the per-hop ppermute split the
    overlap path will use; ``reason`` documents a fallback.
    """

    path: str
    chunks: int
    reason: str


def allgather_plan(rows: int, ring: int, chunks: int = 1) -> OverlapPlan:
    """Plan for ``allgather_matmul`` with per-device shards of ``rows``."""
    if ring == 1:
        return OverlapPlan('one-shot', 1, 'axis_size == 1')
    if chunks < 1 or rows % chunks:
        return OverlapPlan(
            'one-shot', 1,
            f'shard rows ({rows}) not divisible by chunks ({chunks})')
    return OverlapPlan('overlap', chunks, '')


def reducescatter_plan(rows: int, ring: int, chunks: int = 1) -> OverlapPlan:
    """Plan for ``matmul_reducescatter`` with ``rows`` total result rows.

    ``rows % ring != 0`` raises: a scatter over non-dividing rows has no
    semantics on the one-shot path either (``psum_scatter`` tiles).
    """
    if ring == 1:
        return OverlapPlan('one-shot', 1, 'axis_size == 1')
    if rows % ring:
        raise ValueError(
            f'matmul_reducescatter needs rows ({rows}) divisible by the '
            f'ring ({ring}) — the scattered result has no shape otherwise')
    if chunks < 1 or (rows // ring) % chunks:
        return OverlapPlan(
            'one-shot', 1,
            f'scatter block ({rows // ring}) not divisible by chunks '
            f'({chunks})')
    return OverlapPlan('overlap', chunks, '')


def _partial_matmul(a, b):
    """One ring step's matmul, always accumulating in float32."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _out_dtype(x, w):
    return jnp.result_type(x.dtype, w.dtype)


def _allgather_matmul_overlap(axis, chunks, x, w):
    """The decomposed ring: shard ``s`` of ``x`` rotates forward; its
    partial matmul lands in row-block ``s`` of the result while the next
    shard's ``ppermute`` is in flight."""
    ring = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    rows = x.shape[0]
    out = jnp.zeros((ring * rows, w.shape[1]), _out_dtype(x, w))
    held = x
    # step 1's shard is in flight before step 0's matmul issues — the
    # ops/ring.py latency-hiding order
    incoming = ring_shift_chunked(held, axis, chunks=chunks)
    for step in range(ring):
        if step:
            held = incoming
            if step + 1 < ring:
                incoming = ring_shift_chunked(held, axis, chunks=chunks)
        # forward shifts: at step s we hold the shard of rank (rank - s)
        source = (rank - step) % ring
        partial = _partial_matmul(held, w).astype(out.dtype)
        out = lax.dynamic_update_slice(out, partial, (source * rows, 0))
    return out


def _matmul_reducescatter_overlap(axis, chunks, x, w):
    """The dual ring: at step ``t`` every device computes the partial for
    row-block ``(rank - 1 - t) mod n`` and folds it into the running f32
    sum arriving from the previous rank; the sum's forward shift is
    issued *before* the next partial's matmul, so after ``n`` steps
    block ``rank`` lands home having collected all ``n`` contributions
    with the transfers hidden under the matmuls."""
    ring = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    rows = x.shape[0] // ring
    cols = x.shape[1]

    def block(step):
        start = ((rank - 1 - step) % ring) * rows
        return lax.dynamic_slice(x, (start, 0), (rows, cols))

    total = _partial_matmul(block(0), w)
    for step in range(1, ring):
        inflight = ring_shift_chunked(total, axis, chunks=chunks)
        total = inflight + _partial_matmul(block(step), w)
    return total.astype(_out_dtype(x, w))


def _ring_transpose_matmul(axis, chunks, rotating, sliced):
    """``sum_j rotating_j^T @ sliced[j*m:(j+1)*m]`` with ``rotating_j`` =
    rank ``j``'s shard — the weight-gradient ring both custom_vjps share
    (the gathered operand rotates against static row-blocks of the local
    cotangent/input). f32 accumulator, same overlap order as the forward
    rings."""
    ring = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    rows = rotating.shape[0]
    held = rotating
    incoming = ring_shift_chunked(held, axis, chunks=chunks)
    total = jnp.zeros((rotating.shape[1], sliced.shape[1]), jnp.float32)
    for step in range(ring):
        if step:
            held = incoming
            if step + 1 < ring:
                incoming = ring_shift_chunked(held, axis, chunks=chunks)
        source = (rank - step) % ring
        rows_block = lax.dynamic_slice(
            sliced, (source * rows, 0), (rows, sliced.shape[1]))
        total = total + _partial_matmul(held.T, rows_block)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _allgather_matmul(axis, chunks, x, w):
    return _allgather_matmul_overlap(axis, chunks, x, w)


def _allgather_matmul_fwd(axis, chunks, x, w):
    return _allgather_matmul_overlap(axis, chunks, x, w), (x, w)


def _allgather_matmul_bwd(axis, chunks, residuals, grad):
    # y = AG(x) @ w: dx is the dual decomposition with swapped operands
    # (an overlapped matmul-reduce-scatter of the cotangent against w^T),
    # dw the shared transpose ring — the backward overlaps like the fwd.
    x, w = residuals
    dx = _matmul_reducescatter_overlap(axis, chunks, grad, w.T).astype(x.dtype)
    dw = _ring_transpose_matmul(axis, chunks, x, grad).astype(w.dtype)
    return dx, dw


_allgather_matmul.defvjp(_allgather_matmul_fwd, _allgather_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _matmul_reducescatter(axis, chunks, x, w):
    return _matmul_reducescatter_overlap(axis, chunks, x, w)


def _matmul_reducescatter_fwd(axis, chunks, x, w):
    return _matmul_reducescatter_overlap(axis, chunks, x, w), (x, w)


def _matmul_reducescatter_bwd(axis, chunks, residuals, grad):
    # z = RS(x @ w): the reduce-scatter's transpose is the all-gather, so
    # dx is an overlapped all-gather-matmul of the cotangent against w^T;
    # dw is the same transpose ring with the roles swapped.
    x, w = residuals
    dx = _allgather_matmul_overlap(axis, chunks, grad, w.T).astype(x.dtype)
    dw = _ring_transpose_matmul(axis, chunks, grad, x).T.astype(w.dtype)
    return dx, dw


_matmul_reducescatter.defvjp(_matmul_reducescatter_fwd,
                             _matmul_reducescatter_bwd)


def allgather_matmul(x, w, axis: str = MODEL, *, chunks: int = 1):
    """``all_gather(x, axis) @ w`` with the transfer hidden under compute.

    Call inside ``shard_map``. ``x`` is this device's row shard
    ``[rows, k]`` of a ``[ring * rows, k]`` tensor sharded over ``axis``;
    ``w`` is the local ``[k, p]`` column shard of a Megatron up-projection
    (never gathered). Decomposes into ``axis_size`` ring steps — each
    step's partial matmul fills one row-block of the ``[ring * rows, p]``
    result while the next shard's ``ppermute`` is in flight. ``chunks``
    splits each hop's payload into that many independent ``ppermute``\\ s
    (finer interleave for the scheduler; see
    :func:`~tpusystem.parallel.collectives.ring_shift_chunked`).

    Differentiable: the custom_vjp computes ``dx`` as the dual overlapped
    :func:`matmul_reducescatter` of the cotangent against ``w.T`` and
    ``dw`` via the shared transpose ring. Falls back to the one-shot
    ``lax.all_gather`` + matmul when ``axis_size == 1`` or ``chunks``
    cannot tile the shard (see :func:`allgather_plan`).
    """
    plan = allgather_plan(x.shape[0], lax.axis_size(axis), chunks)
    if plan.path == 'one-shot':
        gathered = lax.all_gather(x, axis, axis=0, tiled=True)
        return _partial_matmul(gathered, w).astype(_out_dtype(x, w))
    return _allgather_matmul(axis, plan.chunks, x, w)


def matmul_reducescatter(x, w, axis: str = MODEL, *, chunks: int = 1):
    """``psum_scatter(x @ w, axis)`` with the reduction hidden under compute.

    Call inside ``shard_map``. ``x`` is the local ``[rows, k]`` activation
    against ``w``'s local ``[k, p]`` row shard of a Megatron
    down-projection; the ``[rows, k] @ [k, p]`` partial products are
    summed over the ring and row-block ``r`` of the ``[rows / ring, p]``
    result lands on rank ``r`` (``lax.psum_scatter`` tiled semantics).
    Decomposes into ``axis_size`` chunked partial matmuls whose running
    f32 sum ring-shifts one hop per step toward its owner — each shift is
    issued before the next chunk's matmul, hiding the reduce behind the
    compute.

    Differentiable: ``dx`` is the dual overlapped :func:`allgather_matmul`
    of the cotangent against ``w.T``. Falls back to the one-shot
    matmul + ``lax.psum_scatter`` when ``axis_size == 1`` or ``chunks``
    cannot tile the scatter block (:func:`reducescatter_plan`); rows not
    divisible by the ring raise (no scatter semantics exist).
    """
    plan = reducescatter_plan(x.shape[0], lax.axis_size(axis), chunks)
    if plan.path == 'one-shot':
        # scatter the f32 partial products and cast AFTER: the fallback
        # must keep the module's f32-reduction contract, or a silently
        # non-tiling layer would sum its ring in bf16
        product = _partial_matmul(x, w)
        if lax.axis_size(axis) > 1:
            product = lax.psum_scatter(product, axis, scatter_dimension=0,
                                       tiled=True)
        return product.astype(_out_dtype(x, w))
    return _matmul_reducescatter(axis, plan.chunks, x, w)


# ---------------------------------------------------------------------------
# Model wiring: sequence-sharded FFN behind ``OverlapSchedule(tp=)``
# ---------------------------------------------------------------------------


def _define_dense_params():
    """Build the :class:`DenseParams` flax module on first access (PEP 562
    ``__getattr__`` below): the core collectives in this module are
    jax-only, and eagerly importing flax here would put it on the import
    path of every ``tpusystem.parallel`` consumer (multihost tooling,
    mesh utilities) that never touches a model."""
    from flax import linen as nn

    class DenseParams(nn.Module):
        """Bare ``kernel``/``bias`` params under the module's scope —
        exactly what ``nn.Dense`` would create (same paths, shapes,
        initializers), but retrievable so the overlap path can run the
        matmul through the decomposed collectives. A model may init
        through ``nn.Dense`` and apply through this holder (or vice
        versa): the param trees are identical, so the schedule never
        changes a checkpoint."""

        features: int
        use_bias: bool = True

        @nn.compact
        def __call__(self, in_features: int):
            kernel = self.param('kernel', nn.initializers.lecun_normal(),
                                (in_features, self.features))
            if not self.use_bias:
                return kernel, None
            bias = self.param('bias', nn.initializers.zeros,
                              (self.features,))
            return kernel, bias

    return DenseParams


def __getattr__(name: str):
    if name == 'DenseParams':
        cls = _define_dense_params()
        globals()['DenseParams'] = cls
        return cls
    raise AttributeError(f'module {__name__!r} has no attribute {name!r}')


def overlap_applicable(mesh, hidden_shape, grown_features: int,
                       axis: str = MODEL) -> bool:
    """Can the overlap FFN shard ``[batch, seq, dim]`` activations with
    the hidden dim split over ``axis``? Falls back to the GSPMD path when
    the mesh is absent, the TP axis is trivial, the sequence cannot shard
    over ``(seq, model)`` rows, or the FFN width cannot split."""
    if mesh is None:
        return False
    sizes = dict(mesh.shape)
    ring = sizes.get(axis, 1)
    if ring <= 1:
        return False
    _, seq, _ = hidden_shape
    row_split = ring * sizes.get(SEQ, 1)
    return seq % row_split == 0 and grown_features % ring == 0


def _row_specs(mesh, batch: int, axis: str):
    """Activation spec [batch, seq, dim]: batch over (data, fsdp) when it
    divides (replicated for e.g. ``module.init``'s batch-1 trace — the
    ring.py convention), sequence rows over (seq, model). Mentions only
    axes the mesh actually has, so plain ``jax.sharding.Mesh`` layouts
    (not built by ``MeshSpec``) work too."""
    sizes = dict(mesh.shape)
    data_axes = tuple(name for name in (DATA, FSDP) if name in sizes)
    data_parallel = math.prod(sizes[name] for name in data_axes)
    batch_axes = (data_axes if data_axes and batch % data_parallel == 0
                  else None)
    row_axes = tuple(name for name in (SEQ, axis) if name in sizes)
    return P(batch_axes, row_axes or None, None)


def tp_ffn(x, kernel_up, bias_up, kernel_down, bias_down, mesh, *,
           activation=jax.nn.gelu, axis: str = MODEL, chunks: int = 1):
    """Sequence-sharded Megatron FFN with decomposed collectives.

    ``x`` is the global ``[batch, seq, dim]`` activation; the up kernel
    ``[dim, grown]`` splits columns on ``axis``, the down kernel
    ``[grown, dim]`` rows (the models' standard partition rules, so jit
    inserts no weight resharding). Inside ``shard_map`` the sequence rows
    all-gather *into* the up matmul, the activation applies on the
    gathered rows, and the down matmul reduce-scatters rows back —
    both collectives overlapped. Output is ``[batch, seq, dim]`` sharded
    like the input.

    Since the unified scheduler landed this is the TP-only special case
    of :func:`tpusystem.parallel.schedule.scheduled_ffn` (kept as the
    stable two-knob API; the delegation is exact — same specs, same
    body, same numerics).
    """
    from tpusystem.parallel.schedule import OverlapSchedule, scheduled_ffn
    return scheduled_ffn(x, kernel_up, bias_up, kernel_down, bias_down,
                         mesh, schedule=OverlapSchedule(tp='overlap',
                                                        chunks=chunks),
                         activation=activation, axis=axis)


def tp_swiglu(x, kernel_gate, kernel_up, kernel_down, mesh, *,
              axis: str = MODEL, chunks: int = 1):
    """Sequence-sharded SwiGLU FFN (Llama) with decomposed collectives.

    The gate and up projections share one all-gather: their column shards
    concatenate into a single ``[dim, 2 * grown]`` right operand, so the
    sequence rows ride the ring ONCE for both matmuls. No biases (Llama
    convention).

    Since the unified scheduler landed this is the TP-only special case
    of :func:`tpusystem.parallel.schedule.scheduled_swiglu` (kept as the
    stable two-knob API; the delegation is exact).
    """
    from tpusystem.parallel.schedule import OverlapSchedule, scheduled_swiglu
    return scheduled_swiglu(x, kernel_gate, kernel_up, kernel_down, mesh,
                            schedule=OverlapSchedule(tp='overlap',
                                                     chunks=chunks),
                            axis=axis)
