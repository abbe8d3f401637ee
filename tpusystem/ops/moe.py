"""Mixture-of-experts — expert parallelism over the ``expert`` mesh axis.

The reference has only a dense MLP (SURVEY.md §2.4: "EP/MoE | absent");
this module supplies the TPU-native design: experts live as one stacked
weight tensor with a leading ``experts`` dimension sharded over the
``expert`` mesh axis. Token routing has two formulations behind one layer:

* **sparse**: sort/segment dispatch — a stable argsort by expert id
  gives each assignment its position-in-expert, and scatter/gather moves
  only the O(tokens·k) selected rows (the dense tensors are
  O(tokens·experts·capacity) ≈ O(tokens²·k) in memory and FLOPs).
  Single-shard row movement has three implementations behind
  ``sparse_impl``: ``'scatter'`` (row scatter/scatter-add), ``'gather'``
  (scatter-free custom_vjp pair) and ``'fused'`` (megablocks-style
  Pallas grouped gather-matmul — the rows never make a standalone HBM
  round trip at all; see :func:`_fused_moe`).
  Single-shard it runs directly; on multi-device meshes it runs inside
  ``shard_map`` with token rows sharded over (data, fsdp, seq, expert)
  and a regular differentiable ``all_to_all`` carrying each sender's
  fixed per-expert quota to the expert's owner — SURVEY §2.4's
  ragged-style exchange, made static-shaped by quota padding.
* **dense**: one-hot dispatch/combine einsums (the Switch/GSPMD
  formulation); the partitioner shards them freely and inserts the
  collectives itself. ``dispatch='auto'`` falls back here when the
  sharded-sparse preconditions fail (indivisible rows/experts, model-axis
  TP inside experts).

Capacity model: each expert processes at most
``capacity = round(k * tokens / experts * capacity_factor)`` tokens per
batch; overflow tokens fall through the residual connection (standard
drop-token semantics). Router runs in float32 with a load-balance loss
(Switch eq. 4) plus a router z-loss for logit stability; the layer returns
``(output, aux_loss)`` and :class:`tpusystem.train.losses.WithAuxLoss`
folds the aux term into any base criterion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.parallel.mesh import EXPERT


def _ragged_transport(transport: str, axis: str, operand, out_init,
                      in_off, send_sz, out_off, recv_sz):
    """One ragged exchange over ``axis``: chunk ``d`` of ``operand``
    (``[in_off[d], in_off[d] + send_sz[d])``) lands on device ``d`` at
    offset ``out_off[d]`` of its ``out_init``-shaped buffer.

    ``transport='ragged'`` is ``jax.lax.ragged_all_to_all`` — bytes on the
    wire are the *actual* routed rows. ``'gathered'`` is a semantically
    identical emulation (all_gather + masked slice) for backends whose XLA
    has no ragged-all-to-all lowering (CPU, incl. the virtual test meshes);
    it moves more bytes but seats identically, so tests pin the semantics
    the TPU transport then inherits.
    """
    if transport == 'ragged':
        return lax.ragged_all_to_all(operand, out_init, in_off, send_sz,
                                     out_off, recv_sz, axis_name=axis)
    if transport != 'gathered':
        raise ValueError(f'unknown ragged transport {transport!r}')
    n = lax.axis_size(axis)
    me = lax.axis_index(axis)
    all_ops = lax.all_gather(operand, axis)              # [n, S, cols]
    all_in_off = lax.all_gather(in_off, axis)            # [n, n]
    all_send = lax.all_gather(send_sz, axis)
    all_out_off = lax.all_gather(out_off, axis)
    out = out_init
    rows = jnp.arange(out_init.shape[0])
    for sender in range(n):
        src_off = all_in_off[sender, me]
        size = all_send[sender, me]
        dst_off = all_out_off[sender, me]
        take = jnp.clip(rows - dst_off + src_off, 0, operand.shape[0] - 1)
        values = jnp.take(all_ops[sender], take, axis=0)
        mask = (rows >= dst_off) & (rows < dst_off + size)
        out = jnp.where(mask[:, None], values, out)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ragged_exchange(transport, axis, operand, out_init, in_off, send_sz,
                     out_off, recv_off, recv_sz, rev_out_off):
    """Differentiable ragged exchange.

    ``ragged_all_to_all`` has no transpose rule in XLA, so the backward is
    supplied explicitly (ROADMAP: custom_vjp for the reverse exchange): the
    cotangent of the output is exchanged *back* with the send/recv roles
    swapped — my received chunks (``recv_off``/``recv_sz``) return to their
    senders, landing at the positions they were sent from
    (``rev_out_off[d]`` = the offset device ``d`` used for me, i.e. its
    ``in_off[me]``).
    """
    return _ragged_transport(transport, axis, operand, out_init,
                             in_off, send_sz, out_off, recv_sz)


def _ragged_exchange_fwd(transport, axis, operand, out_init, in_off, send_sz,
                         out_off, recv_off, recv_sz, rev_out_off):
    out = _ragged_transport(transport, axis, operand, out_init,
                            in_off, send_sz, out_off, recv_sz)
    residuals = (in_off, send_sz, recv_off, recv_sz, rev_out_off,
                 operand.shape, out_init.shape)
    return out, residuals


def _ragged_exchange_bwd(transport, axis, residuals, cot):
    in_off, send_sz, recv_off, recv_sz, rev_out_off, op_shape, out_shape = residuals
    # reverse roles: my received chunks carry the cotangent home
    d_operand = _ragged_transport(
        transport, axis, cot, jnp.zeros(op_shape, cot.dtype),
        recv_off, recv_sz, rev_out_off, send_sz)
    # out_init passes through wherever nothing was received
    rows = jnp.arange(out_shape[0])
    received = jnp.zeros((out_shape[0],), bool)
    for sender in range(recv_off.shape[0]):
        received = received | ((rows >= recv_off[sender])
                               & (rows < recv_off[sender] + recv_sz[sender]))
    d_init = jnp.where(received[:, None], 0, cot)
    f0 = lambda arr: np.zeros(arr.shape, jax.dtypes.float0)
    return (d_operand, d_init, f0(in_off), f0(send_sz), f0(send_sz),
            f0(recv_off), f0(recv_sz), f0(rev_out_off))


_ragged_exchange.defvjp(_ragged_exchange_fwd, _ragged_exchange_bwd)


def expert_capacity(tokens: int, experts: int, k: int,
                    capacity_factor: float) -> int:
    """Per-expert token budget (at least 1, at most all tokens)."""
    return max(1, min(tokens, int(tokens * k * capacity_factor / experts)))


def route_top_k(gates: jax.Array, k: int, capacity: int):
    """Build dispatch/combine tensors from router probabilities.

    Args:
        gates: [tokens, experts] router probabilities (float32).
        k: choices per token; chosen gates renormalize to sum to 1.
        capacity: per-expert slot budget.

    Returns:
        dispatch: [tokens, experts, capacity] 0/1 routing tensor.
        combine: same shape, dispatch weighted by the (renormalized) gate.
        fraction: [experts] fraction of tokens whose *first* choice was the
            expert (the load-balance loss term).

    Slots are granted choice-major: every token's first choice is seated
    before any second choice, and within a choice in token order — so drop
    behavior is deterministic and first choices always win over overflow.
    """
    tokens, experts = gates.shape
    top_gates, top_experts = jax.lax.top_k(gates, k)
    top_gates = top_gates / (jnp.sum(top_gates, -1, keepdims=True) + 1e-9)

    dispatch = jnp.zeros((tokens, experts, capacity), jnp.float32)
    combine = jnp.zeros((tokens, experts, capacity), jnp.float32)
    seated = jnp.zeros((experts,), jnp.float32)
    for choice in range(k):
        onehot = jax.nn.one_hot(top_experts[:, choice], experts)  # [N, E]
        position = jnp.cumsum(onehot, axis=0) - 1 + seated
        seated = seated + jnp.sum(onehot, axis=0)
        fits = (position < capacity) * onehot
        slot = jax.nn.one_hot(position.astype(jnp.int32), capacity)  # [N, E, C]
        placed = fits[:, :, None] * slot
        dispatch = dispatch + placed
        combine = combine + placed * top_gates[:, choice][:, None, None]
    first_choice = jax.nn.one_hot(top_experts[:, 0], experts)
    fraction = jnp.mean(first_choice, axis=0)
    return dispatch, combine, fraction


def _seating_positions(keys: jax.Array, length: int):
    """Rank each element among equals: position-in-group via one stable
    argsort plus a scatter-inverted permutation.

    ``keys`` are small non-negative integers (< ``length``); returns each
    element's 0-based position among the elements sharing its key, in
    stable (input) order — the seating primitive behind every sparse
    dispatch path (sender compaction, receiver capacity, slot assignment),
    kept single so the seating-order invariant cannot drift between them.
    """
    order = jnp.argsort(keys, stable=True)
    # invert the permutation with one scatter (a second argsort is O(n log n))
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
    counts = jnp.bincount(keys, length=length)
    starts = jnp.cumsum(counts) - counts
    return ranks - starts[keys], counts


def route_top_k_sparse(gates: jax.Array, k: int, capacity: int):
    """Sort-based routing: the O(tokens·k) replacement for the dense
    [tokens, experts, capacity] one-hot tensors (SURVEY §2.4 mandates
    ragged-style dispatch; the dense einsums are an O(tokens²)·k FLOP and
    memory cliff at real expert counts).

    Returns ``(token_ids, slots, weights, fraction)`` flat per-assignment
    arrays (length ``tokens*k``): assignment ``i`` sends token
    ``token_ids[i]`` to buffer row ``slots[i]`` (``experts*capacity`` means
    dropped — scatter/gather with ``mode='drop'``/``fill`` discards it) and
    its output is combined back with ``weights[i]``.

    Seating matches :func:`route_top_k` exactly: assignments are flattened
    choice-major and position-in-expert comes from a *stable* sort by
    expert id, so every first choice seats before any second choice and
    within a choice tokens seat in order.
    """
    tokens, experts = gates.shape
    top_gates, top_experts = jax.lax.top_k(gates, k)
    top_gates = top_gates / (jnp.sum(top_gates, -1, keepdims=True) + 1e-9)

    expert_ids = top_experts.T.reshape(-1)             # [k*N] choice-major
    weights = top_gates.T.reshape(-1)
    token_ids = jnp.tile(jnp.arange(tokens), k)

    position, _ = _seating_positions(expert_ids, experts)
    keep = position < capacity
    slots = jnp.where(keep, expert_ids * capacity + position,
                      experts * capacity)              # out of range = dropped

    fraction = jnp.mean(jax.nn.one_hot(top_experts[:, 0], experts), axis=0)
    return token_ids, slots, weights, fraction


def _invert_seating(slots, k: int, tokens: int, buffer_rows: int):
    """Invert the choice-major seating once in integer space (the only
    scatter in the gather impl — ``buffer_rows`` int32 elements): buffer
    row -> assignment (``slot_asg``, ``k*tokens`` sentinel for empty),
    buffer row -> token (``slot_token``, ``tokens`` sentinel;
    ``token_ids[a] = a % tokens`` by route_top_k_sparse's choice-major
    layout), and the per-choice ``[k, tokens]`` view of ``slots``."""
    assignments = k * tokens
    slot_asg = jnp.full((buffer_rows,), assignments,
                        jnp.int32).at[slots].set(
        jnp.arange(assignments, dtype=jnp.int32), mode='drop')
    slot_token = jnp.where(slot_asg < assignments, slot_asg % tokens, tokens)
    return slot_asg, slot_token, slots.reshape(k, tokens)


@jax.custom_vjp
def _gather_dispatch(flat, slot_token, slots_by_choice):
    """Scatter-free expert-buffer fill: ``buffer[j] = flat[slot_token[j]]``.

    ``slot_token`` maps each of the ``experts*capacity`` buffer rows to
    its token (``tokens`` = out-of-range for empty slots, so the gather's
    ``fill_value=0`` zeroes them); ``slots_by_choice`` is ``[k, tokens]``
    buffer rows per (choice, token) (``experts*capacity`` when dropped),
    used only by the backward. Both directions lower to *gathers* plus a
    k-way sum — on TPU the row-scatter formulation
    (``buffer.at[slots].set(rows)``) pays the scatter lowering in the
    forward AND a scatter-add transpose in the backward; this is the
    same class of fix as round 4's decode cache write (14x)."""
    return flat.at[slot_token].get(mode='fill', fill_value=0)


def _gather_dispatch_fwd(flat, slot_token, slots_by_choice):
    out = _gather_dispatch(flat, slot_token, slots_by_choice)
    return out, (slot_token, slots_by_choice)


def _gather_dispatch_bwd(residuals, d_buffer):
    slot_token, slots_by_choice = residuals
    # d_flat[t] = sum over t's seated choices of d_buffer at that slot:
    # k gathers (OOB rows of dropped assignments fill 0) + a k-way sum
    d_flat = sum(d_buffer.at[slots_by_choice[c]].get(mode='fill',
                                                     fill_value=0)
                 for c in range(slots_by_choice.shape[0]))
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return d_flat, zero(slot_token), zero(slots_by_choice)


_gather_dispatch.defvjp(_gather_dispatch_fwd, _gather_dispatch_bwd)


@jax.custom_vjp
def _gather_combine(buffer, weights, slots_by_choice, slot_token, slot_asg):
    """Scatter-free combine: ``out[t] = sum_c w[c,t] * buffer[slot(c,t)]``.

    Replaces the gather + ``at[token_ids].add`` scatter-add of the
    scatter formulation: ``token_ids`` is ``tile(arange(tokens), k)`` by
    construction (route_top_k_sparse flattens choice-major), so the
    scatter-add over it IS a reshape-to-[k, tokens]-and-sum — expressed
    directly here. Backward: ``d_buffer`` gathers ``d_out`` by
    ``slot_token`` weighted by the per-slot gate (``weights[slot_asg]``),
    ``d_weights`` is a rowwise dot of the re-gathered buffer rows with
    ``d_out`` — gathers only, no scatter in either direction."""
    k = slots_by_choice.shape[0]
    compute = buffer.dtype
    out = None
    for c in range(k):
        gathered = buffer.at[slots_by_choice[c]].get(mode='fill',
                                                     fill_value=0)
        w = weights.reshape(k, -1)[c][:, None].astype(compute)
        out = gathered * w if out is None else out + gathered * w
    return out


def _gather_combine_fwd(buffer, weights, slots_by_choice, slot_token,
                        slot_asg):
    out = _gather_combine(buffer, weights, slots_by_choice, slot_token,
                          slot_asg)
    return out, (buffer, weights, slots_by_choice, slot_token, slot_asg)


def _combine_bwd_terms(buffer, weights, slots_by_choice, slot_token,
                       slot_asg, d_out, compute):
    """The weighted-combine backward, shared by the gather impl and the
    fused impl so their numerics cannot drift (tests pin them against
    each other): ``d_buffer`` gathers the output cotangent by
    ``slot_token`` scaled by the per-slot gate (compute dtype, empty
    slots fill 0); ``d_weights`` is the choice-major concat of f32
    rowwise dots of the re-gathered buffer rows with ``d_out``."""
    w_slot = weights.at[slot_asg].get(mode='fill', fill_value=0)
    d_buffer = (w_slot[:, None].astype(compute)
                * d_out.at[slot_token].get(mode='fill', fill_value=0))
    d_w = []
    for c in range(slots_by_choice.shape[0]):
        gathered = buffer.at[slots_by_choice[c]].get(mode='fill',
                                                     fill_value=0)
        d_w.append(jnp.sum(gathered.astype(jnp.float32)
                           * d_out.astype(jnp.float32), axis=-1))
    return d_buffer, jnp.concatenate(d_w).astype(weights.dtype)


def _gather_combine_bwd(residuals, d_out):
    buffer, weights, slots_by_choice, slot_token, slot_asg = residuals
    d_buffer, d_weights = _combine_bwd_terms(
        buffer, weights, slots_by_choice, slot_token, slot_asg, d_out,
        buffer.dtype)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (d_buffer, d_weights, zero(slots_by_choice), zero(slot_token),
            zero(slot_asg))


_gather_combine.defvjp(_gather_combine_fwd, _gather_combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_moe(config, flat, w1, b1, w2, b2, weights, slot_token, slot_asg,
               slots_by_choice):
    """Megablocks-style fused sparse MoE: dispatch rides the first expert
    matmul's loads, the k-way weighted combine rides the second's epilogue.

    Two Pallas grouped-matmul kernels
    (:mod:`tpusystem.ops.pallas.grouped_matmul`) replace the
    dispatch/FFN/combine pipeline: :func:`gather_rows_matmul` DMAs token
    rows from the *unpermuted* ``flat`` straight into the up-projection's
    MXU tiles (the ``[experts*capacity, dim]`` dispatch buffer is never
    materialized), and :func:`matmul_scatter_rows` accumulates each
    down-projected row onto its token's output row, scaled by its combine
    weight, in the matmul's epilogue (no buffer-order result is gathered
    back). The backward reuses the SAME kernels with swapped operands —
    ``d_buffer`` gather-matmuls the output cotangent against w2^T with the
    combine weights as the per-row scale, ``d_flat`` matmul-scatters the
    hidden cotangent against w1^T — with f32 MXU accumulation matching the
    gather impl's numerics class (parity is tolerance-bounded, not
    bitwise: summation orders differ).

    ``config`` is ``(capacity, interpret)`` — static; ``interpret=None``
    auto-selects interpreter mode off-TPU so CPU tests run the kernels.
    Integer seating arrays ride as differentiable args returning float0
    (the repo's custom_vjp convention). All float operands arrive in the
    compute dtype; master-weight casts live in the caller.
    """
    out, _ = _fused_moe_fwd(config, flat, w1, b1, w2, b2, weights,
                            slot_token, slot_asg, slots_by_choice)
    return out


def _fused_moe_fwd(config, flat, w1, b1, w2, b2, weights, slot_token,
                   slot_asg, slots_by_choice):
    from tpusystem.ops.pallas.grouped_matmul import (gather_rows_matmul,
                                                     matmul_scatter_rows)
    capacity, interpret = config
    tokens = flat.shape[0]
    experts = w1.shape[0]
    clamped = jnp.minimum(slot_token, tokens - 1)
    valid = (slot_token < tokens).astype(jnp.float32)
    # per-slot combine weight; empty slots (sentinel slot_asg) fill 0
    w_slot = weights.at[slot_asg].get(mode='fill', fill_value=0)

    up = gather_rows_matmul(flat, w1, clamped, valid,
                            rows_per_group=capacity, interpret=interpret)
    pre = up.reshape(experts, capacity, -1) + b1[:, None]
    grown = nn.gelu(pre).reshape(experts * capacity, -1)
    out, shrunk = matmul_scatter_rows(grown, w2, b2, slot_token, w_slot,
                                      tokens, rows_per_group=capacity,
                                      interpret=interpret)
    residuals = (flat, w1, b1, w2, b2, weights, slot_token, slot_asg,
                 slots_by_choice, clamped, w_slot, pre, shrunk)
    return out, residuals


def _fused_moe_bwd(config, residuals, d_out):
    from tpusystem.ops.pallas.grouped_matmul import (gather_rows_matmul,
                                                     matmul_scatter_rows)
    (flat, w1, b1, w2, b2, weights, slot_token, slot_asg, slots_by_choice,
     clamped, w_slot, pre, shrunk) = residuals
    capacity, interpret = config
    tokens, compute = flat.shape[0], flat.dtype
    experts = w1.shape[0]
    valid = (slot_token < tokens).astype(jnp.float32)
    grown = nn.gelu(pre)                           # recomputed, VPU-cheap

    # combine backward: the EXACT terms of _gather_combine_bwd, via the
    # shared helper, against the kernel-saved shrunk rows
    d_shrunk, d_weights = _combine_bwd_terms(
        shrunk, weights, slots_by_choice, slot_token, slot_asg, d_out,
        compute)
    d_shrunk3 = d_shrunk.reshape(experts, capacity, -1)
    d_w2 = jnp.einsum('ech,ecd->ehd', grown, d_shrunk3,
                      preferred_element_type=jnp.float32).astype(w2.dtype)
    d_b2 = jnp.sum(d_shrunk3.astype(jnp.float32), axis=1).astype(b2.dtype)

    # same kernel, swapped operands: d_grown[j] = w_slot[j] *
    # d_out[token_j] @ w2[e]^T — the gather rides the matmul again
    d_grown = gather_rows_matmul(d_out, w2, clamped, w_slot,
                                 rows_per_group=capacity,
                                 transpose_rhs=True, interpret=interpret)
    _, gelu_vjp = jax.vjp(nn.gelu, pre)
    (d_pre,) = gelu_vjp(d_grown.reshape(experts, capacity, -1)
                        .astype(pre.dtype))
    d_b1 = jnp.sum(d_pre.astype(jnp.float32), axis=1).astype(b1.dtype)

    # dispatch backward: d_flat[t] = sum of t's seated d_expert_in rows,
    # i.e. the scatter-combine kernel against w1^T with unit weights
    d_flat, _ = matmul_scatter_rows(d_pre.reshape(experts * capacity, -1),
                                    w1, None, slot_token, valid, tokens,
                                    rows_per_group=capacity,
                                    transpose_rhs=True, save_rows=False,
                                    interpret=interpret)
    # d_w1 needs the gathered rows the forward never materialized; one
    # XLA gather rematerializes them (the gather impl's backward pays the
    # same class of traffic)
    expert_in = flat.at[slot_token].get(mode='fill', fill_value=0)
    d_w1 = jnp.einsum('ecd,ech->edh',
                      expert_in.reshape(experts, capacity, -1), d_pre,
                      preferred_element_type=jnp.float32).astype(w1.dtype)

    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (d_flat.astype(flat.dtype), d_w1, d_b1, d_w2, d_b2, d_weights,
            f0(slot_token), f0(slot_asg), f0(slots_by_choice))


_fused_moe.defvjp(_fused_moe_fwd, _fused_moe_bwd)


class MoEMLP(nn.Module):
    """Expert-parallel FFN: drop-in for the dense fc->gelu->proj block.

    Returns ``(output, aux_loss)`` where ``aux_loss`` already carries the
    configured coefficients. Weights are stacked [experts, ...] float32
    masters cast to ``dtype`` per use; pass ``mesh`` to pin the dispatched
    activations to the expert axis (otherwise GSPMD chooses).

    **Drop semantics across dispatch paths** (they agree exactly whenever
    capacity is ample — no drops — which is the recommended operating
    point): the dense and single-shard sparse paths seat tokens in global
    choice-major order (every first choice before any second choice,
    token-major within a choice). On a multi-device mesh the quota'd
    sharded-sparse path (``exchange='quota'``, the ``'auto'``/``'sparse'``
    default) instead decides drops *per sender*: each shard seats its own
    assignments choice-major into a fixed per-expert quota (its
    integer-truncated share of the capacity), so under tight capacity
    *which* tokens overflow differs from the dense path, and a sender with
    a locally-skewed routing drops tokens the global formulation would
    seat. ``exchange='ragged'`` restores receiver-side global-order
    seating within each expert-axis group (and moves only the actual
    routed rows); see its docstring for the remaining cross-group caveat.
    """

    experts: int
    k: int = 2
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    dtype: jnp.dtype = jnp.bfloat16
    balance_coef: float = 1e-2
    z_coef: float = 1e-3
    mesh: object = None
    dispatch: str = 'auto'   # 'sparse' | 'dense' | 'auto'
    # multi-device sparse exchange: 'quota' ships fixed per-sender quotas
    # through a regular all_to_all (pads to the quota); 'ragged' ships the
    # actual routed rows through jax.lax.ragged_all_to_all with
    # receiver-side global-order capacity seating; 'ragged-emulated' is the
    # same seating semantics over an all_gather transport for backends
    # whose XLA cannot lower ragged-all-to-all (CPU test/virtual meshes)
    exchange: str = 'quota'
    # single-shard sparse data movement: 'gather' routes dispatch+combine
    # through the scatter-free custom_vjp pair (_gather_dispatch /
    # _gather_combine — gathers + k-way sums in both directions, one tiny
    # int scatter to invert the seating); 'scatter' is the row-scatter
    # formulation (the A/B reference); 'fused'
    # folds dispatch into the up-projection's loads and the weighted
    # combine into the down-projection's epilogue with the Pallas grouped
    # gather-matmul kernels (_fused_moe — megablocks-style; bitwise
    # parity with gather/scatter is NOT expected, only tolerance-bounded:
    # the MXU accumulates in f32 and sums in different orders)
    sparse_impl: str = 'gather'
    # full_capacity: seat EVERY assignment — capacity = tokens, the
    # ample-capacity operating point made unconditional. With no drops
    # each token's expert mix depends only on that token, so outputs are
    # independent of co-batched traffic and of pad-bucket width — the
    # property the serving engine's shared-batch decode step needs for
    # token-exactness (and what lifts its MoE gate). Decode clones set
    # it (models.gpt2.Block passes full_capacity=decode); training keeps
    # the capacity_factor economics. Governs the single-shard paths —
    # decode clones reset mesh=None, so decode always lands there.
    full_capacity: bool = False
    # schedule: parallel.OverlapSchedule — its moe= arm governs the
    # sharded quota dispatch. moe='overlap' splits the local token rows
    # into microbatch pieces and software-pipelines the exchange: piece
    # k+1's dispatch all_to_all issues UNDER the expert matmuls of piece
    # k, and piece k's return exchange rides under the matmuls of k+1 —
    # the expert a2a leaves the critical path the way the TP/FSDP rings
    # did. Pure moe_plan (parallel/schedule.py) pins the one-shot
    # fallback (ragged exchanges, rows that won't split); None or
    # moe='gspmd' keeps the single whole-batch exchange. Routing runs on
    # the full local rows either way (aux losses bitwise-invariant); per-
    # piece quotas are the quota path's per-sender drop discipline at
    # finer grain — with ample capacity (no drops) outputs are bitwise-
    # equal to the one-shot path
    schedule: object = None

    @nn.compact
    def __call__(self, hidden):
        batch_shape, dim = hidden.shape[:-1], hidden.shape[-1]
        hidden_dim = self.mlp_ratio * dim
        flat = hidden.reshape(-1, dim)
        tokens = flat.shape[0]

        router = self.param('router', nn.initializers.normal(0.02),
                            (dim, self.experts), jnp.float32)
        init = nn.initializers.lecun_normal()
        w1 = self.param('w1', init, (self.experts, dim, hidden_dim), jnp.float32)
        b1 = self.param('b1', nn.initializers.zeros, (self.experts, hidden_dim), jnp.float32)
        w2 = self.param('w2', init, (self.experts, hidden_dim, dim), jnp.float32)
        b2 = self.param('b2', nn.initializers.zeros, (self.experts, dim), jnp.float32)

        # 'sparse' is the O(tokens·k) sort/scatter path. Single-shard it
        # runs directly; on a multi-device mesh it runs inside shard_map
        # with token rows sharded over (data, fsdp, expert) and a regular
        # all_to_all moving each sender's per-expert quota to the expert's
        # owner (_sharded_sparse — SURVEY §2.4's ragged-style dispatch,
        # made exchangeable with static shapes by fixed per-sender
        # quotas). 'auto' falls back to the dense one-hot einsums when the
        # sharded preconditions don't hold (divisibility, unsharded model
        # axis); explicit 'sparse' raises instead of silently degrading.
        if self.sparse_impl not in ('gather', 'scatter', 'fused'):
            raise ValueError(f'unknown sparse_impl {self.sparse_impl!r}; '
                             "expected 'gather', 'scatter' or 'fused'")
        mode = self.dispatch
        if mode == 'auto':
            if self.mesh is None or self.mesh.size == 1:
                mode = 'sparse'
            else:
                problem = self._sharded_sparse_blocker(tokens)
                mode = 'dense' if problem else 'sparse_sharded'
        elif mode == 'sparse':
            if self.mesh is not None and self.mesh.size > 1:
                problem = self._sharded_sparse_blocker(tokens)
                if problem:
                    raise ValueError(
                        f'dispatch=sparse on a multi-device mesh: {problem} '
                        f"(use dispatch='auto' to fall back to dense)")
                mode = 'sparse_sharded'
        elif mode != 'dense':
            raise ValueError(f'unknown dispatch {self.dispatch!r}; '
                             "expected 'sparse', 'dense' or 'auto'")
        compute = jnp.dtype(self.dtype)

        if mode == 'sparse_sharded':
            if self.sparse_impl == 'fused' and self.dispatch == 'sparse':
                # the sharded formulations own their row movement (quota /
                # ragged exchanges); the fused kernels are single-shard
                # today. An EXPLICIT dispatch='sparse' raises rather than
                # silently running a different impl (the repo contract);
                # dispatch='auto' keeps its no-raise promise and proceeds
                # with the sharded formulation.
                raise ValueError(
                    "sparse_impl='fused' is single-shard only; on a "
                    'multi-device mesh the sharded sparse path uses its '
                    "exchange formulation (see exchange=). Use "
                    "sparse_impl='gather' there, or dispatch='auto' to "
                    'accept the sharded formulation.')
            if self.exchange in ('ragged', 'ragged-emulated'):
                output, aux = self._sharded_ragged(flat, router, w1, b1, w2,
                                                   b2, compute)
            elif self.exchange == 'quota':
                output, aux = self._sharded_sparse(flat, router, w1, b1, w2,
                                                   b2, compute)
            else:
                raise ValueError(f'unknown exchange {self.exchange!r}; '
                                 "expected 'quota', 'ragged' or "
                                 "'ragged-emulated'")
            return output.reshape(*batch_shape, dim).astype(hidden.dtype), aux

        logits = flat.astype(jnp.float32) @ router
        gates = jax.nn.softmax(logits)
        capacity = (tokens if self.full_capacity
                    else expert_capacity(tokens, self.experts, self.k,
                                         self.capacity_factor))

        if mode == 'sparse':
            token_ids, slots, weights, fraction = route_top_k_sparse(
                gates, self.k, capacity)
        else:
            dispatch, combine, fraction = route_top_k(gates, self.k, capacity)

        # Switch load-balance loss: experts * <fraction_dispatched * mean_prob>
        balance = self.experts * jnp.sum(fraction * jnp.mean(gates, axis=0))
        z_term = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = self.balance_coef * balance + self.z_coef * z_term

        if mode == 'sparse' and self.sparse_impl in ('gather', 'fused'):
            # ONE seating inversion serves both impls — the parity their
            # tests pin depends on them reading identical slot maps
            slot_asg, slot_token, slots_by_choice = _invert_seating(
                slots, self.k, tokens, self.experts * capacity)

        if mode == 'sparse' and self.sparse_impl == 'fused':
            # megablocks-style: both data movements ride the expert
            # matmuls (Pallas grouped gather-matmul / matmul-scatter);
            # no dispatch buffer, no combine gather — see _fused_moe
            output = _fused_moe(
                (capacity, None), flat.astype(compute), w1.astype(compute),
                b1.astype(compute), w2.astype(compute), b2.astype(compute),
                weights, slot_token, slot_asg, slots_by_choice)
            return output.reshape(*batch_shape, dim).astype(hidden.dtype), aux

        if mode == 'sparse':
            if self.sparse_impl == 'gather':
                expert_in = _gather_dispatch(flat.astype(compute),
                                             slot_token, slots_by_choice)
            else:
                rows = flat.astype(compute)[token_ids]     # [k*N, D] gather
                expert_in = jnp.zeros((self.experts * capacity, dim), compute)
                expert_in = expert_in.at[slots].set(rows, mode='drop')
            expert_in = expert_in.reshape(self.experts, capacity, dim)
        else:
            expert_in = jnp.einsum('nec,nd->ecd', dispatch.astype(compute),
                                   flat.astype(compute))

        expert_in = self._constrain(expert_in)
        shrunk = self._ffn(expert_in, w1, b1, w2, b2, compute)
        shrunk = self._constrain(shrunk)

        if mode == 'sparse':
            buffer = shrunk.reshape(self.experts * capacity, dim)
            if self.sparse_impl == 'gather':
                output = _gather_combine(buffer, weights, slots_by_choice,
                                         slot_token, slot_asg)
            else:
                output = self._sparse_combine(buffer, slots, token_ids,
                                              weights, tokens, dim, compute)
        else:
            output = jnp.einsum('nec,ecd->nd', combine.astype(compute), shrunk)
        return output.reshape(*batch_shape, dim).astype(hidden.dtype), aux

    def _ffn(self, expert_in, w1, b1, w2, b2, compute):
        """The per-expert MLP — one implementation for every dispatch path,
        so the parity the tests pin cannot drift."""
        grown = jnp.einsum('ecd,edh->ech', expert_in, w1.astype(compute))
        grown = nn.gelu(grown + b1[:, None].astype(compute))
        return (jnp.einsum('ech,ehd->ecd', grown, w2.astype(compute))
                + b2[:, None].astype(compute))

    @staticmethod
    def _sparse_combine(buffer, slots, token_ids, weights, tokens, dim,
                        compute):
        gathered = buffer.at[slots].get(mode='fill', fill_value=0)
        return jnp.zeros((tokens, dim), compute).at[token_ids].add(
            gathered * weights[:, None].astype(compute))

    def _constrain(self, value):
        from tpusystem.parallel.sharding import constrain_expert_major
        return constrain_expert_major(value, self.mesh)

    def _sharded_sparse_blocker(self, tokens: int) -> str | None:
        """Why the sharded sparse path cannot run (None = it can)."""
        from tpusystem.parallel.mesh import DATA, FSDP, MODEL, SEQ
        shape = dict(self.mesh.shape)
        # the dispatch shard_map names all four row axes in its specs, so a
        # hand-built mesh missing any of them must fall back to dense
        # instead of raising a KeyError mid-trace
        missing = [axis for axis in (DATA, FSDP, SEQ, EXPERT)
                   if axis not in shape]
        if missing:
            return (f'mesh lacks the standard row axes {missing} the '
                    'sparse dispatch shards over')
        shards = (shape.get(DATA, 1) * shape.get(FSDP, 1)
                  * shape.get(SEQ, 1) * shape.get(EXPERT, 1))
        if shape.get(MODEL, 1) > 1:
            return 'model-axis TP inside experts is dense-only'
        if self.experts % shape.get(EXPERT, 1):
            return (f'{self.experts} experts not divisible by the expert '
                    f'axis ({shape.get(EXPERT, 1)})')
        if tokens % shards:
            return (f'{tokens} token rows not divisible by '
                    f'data*fsdp*seq*expert = {shards}')
        return None

    def _sharded_sparse(self, flat, router, w1, b1, w2, b2, compute):
        """Expert-parallel sparse dispatch inside ``shard_map``.

        Token rows shard over (data, fsdp, expert); each device seats its
        assignments into a ``[experts, quota]`` send buffer with
        :func:`route_top_k_sparse` (quota = its share of the global
        capacity), one **regular** ``all_to_all`` over the expert axis
        hands every expert's rows to its owner, the FFN runs on
        ``[local_experts, senders*quota]`` seated rows (no receiver-side
        sort), and the inverse exchange brings outputs home for the
        weighted combine. Fixed per-sender quotas are what make the
        exchange static-shaped — the ragged-a2a formulation SURVEY §2.4
        calls for, with padding instead of raggedness; ``all_to_all``
        differentiates (its transpose is the reverse exchange), so the
        whole path trains. Capacity semantics differ from the dense path:
        drops are decided per sender (choice-major within each shard), not
        by global token order — with ample capacity (no drops) the two
        paths agree exactly.

        With ``schedule.moe='overlap'``
        (:class:`~tpusystem.parallel.schedule.OverlapSchedule`, planned by
        the pure :func:`~tpusystem.parallel.schedule.moe_plan`) the local
        rows split into microbatch pieces and the exchanges software-
        pipeline: piece ``k+1``'s dispatch ``all_to_all`` is issued
        *before* piece ``k``'s expert matmuls in program order — the two
        are dataflow-independent, so the transfer hides under the MXU
        work — and piece ``k``'s return exchange rides under the matmuls
        of ``k+1`` the same way. Routing runs on the full local rows
        first (router logits/gates and the aux losses are bitwise
        identical to the one-shot path); each piece seats into its own
        per-piece quota (the per-sender drop discipline at finer grain:
        with ample capacity, outputs are bitwise-equal to one-shot).
        """
        import functools

        from jax import lax

        from tpusystem.parallel.mesh import DATA, FSDP, SEQ
        from tpusystem.parallel.schedule import MoePlan, moe_plan

        mesh = self.mesh
        expert_ax = mesh.shape[EXPERT]
        local_experts = self.experts // expert_ax
        shards = (mesh.shape[DATA] * mesh.shape[FSDP] * mesh.shape[SEQ]
                  * expert_ax)
        local_rows = flat.shape[0] // shards
        # clamp like expert_capacity: a sender cannot route more than its
        # local_rows assignments to any one expert, so a larger quota only
        # pads the all_to_all buffers with unreachable zero rows
        quota = max(1, min(local_rows,
                           int(local_rows * self.k * self.capacity_factor
                               / self.experts)))
        dim = flat.shape[1]
        experts, k = self.experts, self.k
        capacity_factor = self.capacity_factor
        row_axes = (DATA, FSDP, SEQ, EXPERT)
        row_spec = P(row_axes, None)
        if (self.schedule is not None
                and getattr(self.schedule, 'moe', 'gspmd') == 'overlap'):
            plan = moe_plan(local_rows, expert_ax, self.exchange)
        else:
            plan = MoePlan('one-shot', 1, 'moe overlap inactive')

        def exchange(buffer):
            # chunk d of a send buffer (global expert order, owners
            # contiguous) goes to device d; twice the same tiled exchange
            # is the identity, which is how outputs come home
            return lax.all_to_all(buffer, EXPERT, split_axis=0,
                                  concat_axis=0, tiled=True)

        def seat(rows_piece, gates_piece, piece_quota):
            """Route one piece into its [experts * piece_quota, dim] send
            buffer (choice-major per-sender seating — the path's one drop
            discipline, at the piece's own quota)."""
            token_ids, slots, weights, _ = route_top_k_sparse(
                gates_piece, k, piece_quota)
            send = jnp.zeros((experts * piece_quota, dim), compute)
            send = send.at[slots].set(rows_piece.astype(compute)[token_ids],
                                      mode='drop')
            return send, (slots, token_ids, weights)

        def expert_pass(recv, piece_quota, w1, b1, w2, b2):
            """Seated arrivals -> expert FFN -> buffer-order returns."""
            expert_in = (recv.reshape(expert_ax, local_experts,
                                      piece_quota, dim)
                         .transpose(1, 0, 2, 3)
                         .reshape(local_experts, expert_ax * piece_quota,
                                  dim))
            shrunk = self._ffn(expert_in, w1, b1, w2, b2, compute)
            return (shrunk.reshape(local_experts, expert_ax, piece_quota,
                                   dim)
                    .transpose(1, 0, 2, 3)
                    .reshape(experts * piece_quota, dim))

        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(row_spec, P(), P(EXPERT, None, None), P(EXPERT, None),
                      P(EXPERT, None, None), P(EXPERT, None)),
            out_specs=(row_spec, P()))
        def run(rows, router, w1, b1, w2, b2):
            # routing always runs on the FULL local rows: one logits
            # matmul, bitwise-identical gates and aux losses under either
            # dispatch schedule — only the seating/exchange is per-piece
            logits = rows.astype(jnp.float32) @ router
            gates = jax.nn.softmax(logits)

            if plan.path == 'overlap':
                pieces = plan.pieces
                piece_rows = rows.shape[0] // pieces
                piece_quota = max(1, min(piece_rows,
                                         int(piece_rows * k
                                             * capacity_factor / experts)))
                routed = [
                    seat(lax.dynamic_slice_in_dim(rows, p * piece_rows,
                                                  piece_rows),
                         lax.dynamic_slice_in_dim(gates, p * piece_rows,
                                                  piece_rows),
                         piece_quota)
                    for p in range(pieces)]
                # the software pipeline: piece p+1's dispatch a2a issues
                # BEFORE piece p's expert matmuls (independent, so the
                # transfer hides under the MXU work); piece p's return
                # a2a issues after its matmuls and completes under p+1's
                recv = [None] * pieces
                recv[0] = exchange(routed[0][0])
                outs = []
                for p in range(pieces):
                    if p + 1 < pieces:
                        recv[p + 1] = exchange(routed[p + 1][0])
                    back = expert_pass(recv[p], piece_quota, w1, b1, w2, b2)
                    buffer = exchange(back)
                    slots, token_ids, weights = routed[p][1]
                    outs.append(self._sparse_combine(
                        buffer, slots, token_ids, weights, piece_rows, dim,
                        compute))
                output = jnp.concatenate(outs, axis=0)
                # the load-balance fraction, exactly as route_top_k_sparse
                # computes it, from the full gates
                _, top_experts = jax.lax.top_k(gates, k)
                fraction = jnp.mean(jax.nn.one_hot(top_experts[:, 0],
                                                   experts), axis=0)
            else:
                token_ids, slots, weights, fraction = route_top_k_sparse(
                    gates, k, quota)
                send = jnp.zeros((experts * quota, dim), compute)
                send = send.at[slots].set(rows.astype(compute)[token_ids],
                                          mode='drop')
                buffer = exchange(expert_pass(exchange(send), quota,
                                              w1, b1, w2, b2))
                output = self._sparse_combine(buffer, slots, token_ids,
                                              weights, rows.shape[0], dim,
                                              compute)

            # Switch balance/z losses over GLOBAL token statistics
            fraction = lax.pmean(fraction, row_axes)
            mean_gates = lax.pmean(jnp.mean(gates, axis=0), row_axes)
            balance = experts * jnp.sum(fraction * mean_gates)
            z_term = lax.pmean(
                jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), row_axes)
            aux = self.balance_coef * balance + self.z_coef * z_term
            return output, aux

        return run(flat, router, w1, b1, w2, b2)

    def _sharded_ragged(self, flat, router, w1, b1, w2, b2, compute):
        """Expert-parallel sparse dispatch with a **ragged** exchange.

        Differences from :meth:`_sharded_sparse` (the quota path):

        * the exchange ships the *actual* routed rows —
          ``jax.lax.ragged_all_to_all`` with per-destination offsets/sizes
          (``exchange='ragged'``) or the all_gather emulation with
          identical seating (``'ragged-emulated'``, for backends whose XLA
          cannot lower the primitive) — instead of padding every sender to
          a fixed per-expert quota; under balanced routing at capacity
          factor ``c`` the quota path moves ``~c``x the bytes of this one.
        * capacity is enforced at the **receiver** in global
          ``(choice, token)`` order within the expert-axis group: every
          row travels with a routing key, the expert's owner sorts its
          arrivals and seats the first ``capacity`` — so a sender with
          locally-skewed routing can fill seats the quota path would have
          dropped (its fixed share) while another sender's quota sat
          empty. Remaining divergence from the dense path: competition is
          per expert-axis *group* (the data/fsdp/seq replicas of the
          expert weights each seat their own token subset against a
          proportional ``capacity``), so with drops the seated set matches
          dense only when routing pressure is uniform across groups; with
          ample capacity all paths agree exactly.
        * a sender caps its per-expert sends at ``min(local_rows,
          capacity)`` — rows beyond that could never seat anywhere, since
          a sender's own assignments to one expert are already in global
          order.

        Both exchanges differentiate through :func:`_ragged_exchange`
        (custom_vjp; the reverse exchange carries the cotangent home).
        """
        from tpusystem.parallel.mesh import DATA, FSDP, SEQ

        mesh = self.mesh
        expert_ax = mesh.shape[EXPERT]
        local_experts = self.experts // expert_ax
        shards = (mesh.shape[DATA] * mesh.shape[FSDP] * mesh.shape[SEQ]
                  * expert_ax)
        local_rows = flat.shape[0] // shards
        dim = flat.shape[1]
        experts, k = self.experts, self.k
        group_tokens = local_rows * expert_ax
        capacity = expert_capacity(group_tokens, experts, k,
                                   self.capacity_factor)
        send_cap = min(local_rows, capacity)
        send_bound = min(local_rows * k, experts * send_cap)
        recv_bound = expert_ax * local_experts * send_cap
        key_span = k * expert_ax * local_rows
        transport = 'ragged' if self.exchange == 'ragged' else 'gathered'
        row_axes = (DATA, FSDP, SEQ, EXPERT)
        row_spec = P(row_axes, None)

        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(row_spec, P(), P(EXPERT, None, None), P(EXPERT, None),
                      P(EXPERT, None, None), P(EXPERT, None)),
            out_specs=(row_spec, P()))
        def run(rows, router, w1, b1, w2, b2):
            me = lax.axis_index(EXPERT)
            logits = rows.astype(jnp.float32) @ router
            gates = jax.nn.softmax(logits)
            top_gates, top_experts = jax.lax.top_k(gates, k)
            top_gates = top_gates / (jnp.sum(top_gates, -1, keepdims=True)
                                     + 1e-9)
            expert_ids = top_experts.T.reshape(-1)       # [k*L] choice-major
            weights = top_gates.T.reshape(-1)
            token_ids = jnp.tile(jnp.arange(local_rows), k)
            choice_ids = jnp.arange(k * local_rows) // local_rows
            # global (choice, token) seating key within the expert group
            key = (choice_ids * (expert_ax * local_rows)
                   + me * local_rows + token_ids).astype(jnp.int32)

            # sender-side compaction: choice-major stable seating by expert
            # is (expert, key) order within this sender, so keeping the
            # first send_cap per expert keeps exactly the globally-seatable
            # ones
            position, counts = _seating_positions(expert_ids, experts)
            keep = position < send_cap
            counts_kept = jnp.minimum(counts, send_cap)
            kept_starts = jnp.cumsum(counts_kept) - counts_kept
            send_slot = jnp.where(keep, kept_starts[expert_ids] + position,
                                  send_bound)

            send_rows = jnp.zeros((send_bound, dim), compute)
            send_rows = send_rows.at[send_slot].set(
                rows.astype(compute)[token_ids], mode='drop')
            sentinel_row = jnp.asarray([[experts, key_span]], jnp.int32)
            send_meta = jnp.tile(sentinel_row, (send_bound, 1))
            send_meta = send_meta.at[send_slot].set(
                jnp.stack([expert_ids.astype(jnp.int32), key], axis=1),
                mode='drop')

            # exchange geometry from the gathered count matrix
            dev_counts = counts_kept.reshape(expert_ax, local_experts).sum(
                axis=1).astype(jnp.int32)
            in_off = (jnp.cumsum(dev_counts) - dev_counts).astype(jnp.int32)
            counts_mat = lax.all_gather(dev_counts, EXPERT)  # [sender, dest]
            recv_sz = counts_mat[:, me]
            recv_off = (jnp.cumsum(recv_sz) - recv_sz).astype(jnp.int32)
            out_off = (jnp.cumsum(counts_mat, axis=0) - counts_mat)[me]
            rev_out_off = (jnp.cumsum(counts_mat, axis=1)
                           - counts_mat)[:, me].astype(jnp.int32)
            out_off = out_off.astype(jnp.int32)

            recv_rows = _ragged_exchange(
                transport, EXPERT, send_rows,
                jnp.zeros((recv_bound, dim), compute),
                in_off, dev_counts, out_off, recv_off, recv_sz, rev_out_off)
            recv_meta = _ragged_transport(
                transport, EXPERT, send_meta,
                jnp.tile(sentinel_row, (recv_bound, 1)),
                in_off, dev_counts, out_off, recv_sz)

            # receiver-side seating in global (choice, token) order
            r_expert, r_key = recv_meta[:, 0], recv_meta[:, 1]
            valid = r_expert < experts
            local_e = jnp.clip(r_expert - me * local_experts, 0,
                               local_experts - 1)
            seat_key = jnp.where(valid, local_e * key_span + r_key,
                                 local_experts * key_span)
            order2 = jnp.argsort(seat_key, stable=True)
            ranks2 = jnp.zeros_like(order2).at[order2].set(
                jnp.arange(order2.size))
            e_counts = jnp.bincount(
                jnp.where(valid, local_e, local_experts),
                length=local_experts + 1)[:local_experts]
            e_starts = jnp.cumsum(e_counts) - e_counts
            position2 = ranks2 - e_starts[local_e]
            seat = valid & (position2 < capacity)
            slot2 = jnp.where(seat, local_e * capacity + position2,
                              local_experts * capacity)

            expert_in = jnp.zeros((local_experts * capacity, dim), compute)
            expert_in = expert_in.at[slot2].set(recv_rows, mode='drop')
            expert_in = expert_in.reshape(local_experts, capacity, dim)

            shrunk = self._ffn(expert_in, w1, b1, w2, b2, compute)

            buffer = shrunk.reshape(local_experts * capacity, dim)
            out_rows = buffer.at[slot2].get(mode='fill', fill_value=0)
            returned = _ragged_exchange(
                transport, EXPERT, out_rows,
                jnp.zeros((send_bound, dim), compute),
                recv_off, recv_sz, rev_out_off, in_off, dev_counts, out_off)
            gathered = returned.at[send_slot].get(mode='fill', fill_value=0)
            output = jnp.zeros((local_rows, dim), compute).at[token_ids].add(
                gathered * weights[:, None].astype(compute))

            # Switch balance/z losses over GLOBAL token statistics
            fraction = jnp.mean(jax.nn.one_hot(top_experts[:, 0], experts),
                                axis=0)
            fraction = lax.pmean(fraction, row_axes)
            mean_gates = lax.pmean(jnp.mean(gates, axis=0), row_axes)
            balance = experts * jnp.sum(fraction * mean_gates)
            z_term = lax.pmean(
                jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), row_axes)
            aux = self.balance_coef * balance + self.z_coef * z_term
            return output, aux

        return run(flat, router, w1, b1, w2, b2)


def moe_partition_rules():
    """Sharding rules for stacked expert weights: experts over the
    ``expert`` axis, FFN hidden over ``model`` (TP within an expert)."""
    return (
        (r'moe/w1$', P(EXPERT, None, 'model')),
        (r'moe/b1$', P(EXPERT, 'model')),
        (r'moe/w2$', P(EXPERT, 'model', None)),
        (r'moe/b2$', P(EXPERT, None)),
        (r'moe/router$', P()),
    )


# --- gated experts with a group-limited router and a held share -----------

def group_limited_top_k(scores: jax.Array, k: int, groups: int,
                        keep_groups: int):
    """Group-limited greedy top-k (DeepSeek-V2's ``group_limited_greedy``).

    ``scores`` is ``[tokens, experts]`` (softmax probabilities, float32);
    the experts lie in ``groups`` equal consecutive groups. A group's score
    is its largest member; the ``keep_groups`` best groups stay and every
    other expert's score is set to 0; the ``k`` largest of what is left
    are the token's experts. Returns ``(ids, weights)``, both ``[tokens,
    k]``, the weights being the chosen scores themselves (not
    renormalised). Ties go to the lower index (``lax.top_k``)."""
    tokens, experts = scores.shape
    if experts % groups:
        raise ValueError(f'{experts} experts do not split into {groups} '
                         'equal groups')
    per_group = experts // groups
    group_score = jnp.max(scores.reshape(tokens, groups, per_group), axis=-1)
    _, kept = lax.top_k(group_score, keep_groups)
    keep = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :],
                   axis=1)                                  # [tokens, groups]
    limited = jnp.where(jnp.repeat(keep, per_group, axis=1), scores, 0.0)
    weights, ids = lax.top_k(limited, k)
    return ids.astype(jnp.int32), weights


def corrected_top_k(scores: jax.Array, correction: jax.Array, k: int):
    """Top-k by a corrected score, weighed by the uncorrected one (the
    ``nemotron_h`` family's router, DeepSeek-V3's ``noaux_tc`` with one
    group).

    ``scores`` is ``[tokens, experts]`` (sigmoid scores, float32) and
    ``correction [experts]`` the router's learned per-expert term: the ``k``
    chosen are the largest of ``scores + correction``, and the term enters
    nothing else. Returns ``(ids, weights)``, both ``[tokens, k]``: the
    weights are the chosen experts' own scores over their sum (+ 1e-20), so
    they add up to 1. Ties go to the lower index (``lax.top_k``)."""
    _, ids = lax.top_k(scores + correction.astype(scores.dtype), k)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids.astype(jnp.int32), chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def seat_held(ids: jax.Array, start: int, count: int):
    """Seat the assignments that fall on the ``count`` experts held here
    (``start .. start + count - 1``), sorted by expert.

    ``ids`` is ``[tokens, k]``, every token's chosen experts over the whole
    router. Returns ``(order, held, sizes)``: ``order [tokens * k]`` lists
    the flattened assignments expert by expert, those on experts held
    elsewhere last; ``held [tokens * k]`` says which sorted rows are seated
    here; ``sizes [count]`` is how many each held expert got — the group
    sizes of a grouped matrix product over the sorted rows. Nothing is
    ever dropped: the bound is the static ``tokens * k``."""
    local = ids.reshape(-1) - start
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)           # elsewhere sorts last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, jnp.take(mine, order), sizes


class GatedExperts(nn.Module):
    """A bias-free expert layer told which experts it holds.

    The router scores **all** ``experts`` in float32, from the router
    matrix as handed, whatever its type. ``scoring='softmax'`` (DeepSeek-V2)
    picks ``k`` by :func:`group_limited_top_k` and weighs each by ``scale``
    times its softmax score, not renormalised; ``scoring='sigmoid'`` (the
    ``nemotron_h`` family) picks by :func:`corrected_top_k` — sigmoid scores
    plus the learned ``correction`` leaf for the choice only — and weighs
    each by ``scale`` times its own score over the chosen ones' sum.
    ``held = (start, count)`` names the experts
    whose matrices live here (``None``: all of them); the layer sums
    ``w_e * E_e(h)`` over a token's chosen experts *that it holds* — what
    the others would have added is left out, as on one chip of an
    expert-parallel deployment before the exchange — plus the shared
    experts ``S(h)`` (one gated MLP of ``shared_width``; 0: none), which
    every holder computes alike. ``form='gated'``: ``E(h) = down(silu(gate
    h) * up h)``, three matrices; ``form='relu2'``: ``E(h) = down(relu(up
    h)²)``, two, and no ``gate`` leaf — routed and shared alike, at
    ``width``, which need not be a multiple of the model's width.
    ``pad_to`` (0: as they are) stores the routed experts' matrices with
    both their dimensions, the model's and ``width``, rounded up to whole
    multiples of it; what lies in the padding is read by nothing (the rows
    go in zero-padded, the padded columns of the hidden activation are
    zeroed, the padded output columns dropped). It buys the grouped
    product's kernel dimensions it tiles well: on a v5e a ``[576, 2688] x
    [32, 2688, 1856]`` product takes 4.2 ms and ``[576, 2816] x [32, 2816,
    2048]`` 0.86 ms (a minor dimension that is not lane-dense, 1856, is
    besides kept off the minor position by the TPU, and the whole stack
    copied every call).

    Work and memory follow the assignments seated here, not ``experts x
    tokens``: the (token, choice) pairs on held experts are sorted by
    expert (:func:`seat_held`) and the three products run as grouped
    products (``jax.lax.ragged_dot``: on the TPU one Mosaic grouped-matmul
    program that skips an expert nobody chose) over at most ``tokens * k``
    rows. No capacity, no dropped token at any batch, and a row's output
    does not depend on what is batched with it.

    Scopes (``jax.named_scope``): ``router`` (scores, the choice, the
    sort), ``experts`` (the gathers, the grouped products, the weighted
    sum), ``shared``. Counters: where the caller makes the ``expert_load``
    collection mutable, ``sow`` leaves one int32 scalar under each of
    :attr:`LOAD` for this call (``seated``: assignments seated here;
    ``hit``: held experts that got any; ``largest``: the most one held
    expert got); the serving engine sums the layers' under those names and
    reads them with the tick's tokens. Where the caller makes ``routing``
    mutable, ``chosen`` holds the ``k`` experts every token was given
    (``[tokens, k]`` int32, of all ``experts``, held or not): what a
    caller needs to replay the layer's choices (``Engine(routing_sink=)``)."""

    LOAD = ('seated', 'hit', 'largest')

    experts: int                    # the router's width
    k: int
    width: int
    groups: int = 1
    keep_groups: int = 1
    scale: float = 1.0
    shared_width: int = 0
    held: tuple | None = None       # (first expert held, how many)
    dtype: jnp.dtype = jnp.bfloat16
    scoring: str = 'softmax'        # | 'sigmoid' (corrected top-k)
    form: str = 'gated'             # | 'relu2' (two matrices, no gate)
    pad_to: int = 0                 # routed matrices' dims, rounded up

    @nn.compact
    def __call__(self, hidden):
        if self.scoring not in ('softmax', 'sigmoid') \
                or self.form not in ('gated', 'relu2'):
            raise ValueError(f'scoring={self.scoring!r}, form={self.form!r}: '
                             "expected 'softmax' | 'sigmoid' and 'gated' | "
                             "'relu2'")
        gated = self.form == 'gated'
        batch_shape, dim = hidden.shape[:-1], hidden.shape[-1]
        start, count = self.held if self.held is not None \
            else (0, self.experts)
        if start < 0 or count < 1 or start + count > self.experts:
            raise ValueError(f'held={self.held} lies outside the '
                             f"router's {self.experts} experts")
        compute = jnp.dtype(self.dtype)
        init = nn.initializers.lecun_normal()
        router = self.param('router', nn.initializers.normal(0.02),
                            (dim, self.experts), jnp.float32)
        if self.scoring == 'sigmoid':
            correction = self.param('correction', nn.initializers.zeros,
                                    (self.experts,), jnp.float32)
        whole = lambda size: -(-size // self.pad_to) * self.pad_to \
            if self.pad_to else size
        wide, deep = whole(dim), whole(self.width)
        if gated:
            gate = self.param('gate', init, (count, wide, deep), jnp.float32)
        up = self.param('up', init, (count, wide, deep), jnp.float32)
        down = self.param('down', init, (count, deep, wide), jnp.float32)
        flat = hidden.reshape(-1, dim)
        tokens = flat.shape[0]

        with jax.named_scope('router'):
            # scores from the input as handed (float32 where the caller
            # keeps its residual stream so): a rounding here flips experts
            logits = jnp.dot(flat.astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
            flat = flat.astype(compute)
            if self.scoring == 'sigmoid':
                ids, weights = corrected_top_k(jax.nn.sigmoid(logits),
                                               correction, self.k)
            else:
                ids, weights = group_limited_top_k(
                    jax.nn.softmax(logits, axis=-1), self.k, self.groups,
                    self.keep_groups)
            order, held, sizes = seat_held(ids, start, count)
            token_of = order // self.k
            weight_of = jnp.where(
                held, self.scale * jnp.take(weights.reshape(-1), order), 0.0)
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
        latest = dict(reduce_fn=lambda _, new: new)
        for name, count in zip(self.LOAD, (jnp.sum(sizes), jnp.sum(sizes > 0),
                                           jnp.max(sizes))):
            self.sow('expert_load', name, count.astype(jnp.int32),
                     init_fn=lambda: jnp.zeros((), jnp.int32), **latest)
        self.sow('routing', 'chosen', ids.astype(jnp.int32),
                 init_fn=lambda: jnp.zeros((tokens, self.k), jnp.int32),
                 **latest)

        with jax.named_scope('experts'):
            rows = jnp.take(flat, token_of, axis=0)       # [tokens * k, dim]
            if wide != dim:
                rows = jnp.pad(rows, ((0, 0), (0, wide - dim)))
            grown = lax.ragged_dot(rows, up.astype(compute), sizes)
            if gated:
                grown = nn.silu(lax.ragged_dot(rows, gate.astype(compute),
                                               sizes)) * grown
            else:
                grown = jnp.square(nn.relu(grown))
            if deep != self.width:
                grown = jnp.where(jnp.arange(deep) < self.width, grown, 0)
            shrunk = lax.ragged_dot(grown, down.astype(compute),
                                    sizes)[:, :dim]
            # rows past the seated ones hold whatever the product left
            weighed = jnp.where(held[:, None],
                                shrunk.astype(jnp.float32)
                                * weight_of[:, None], 0.0).astype(compute)
            routed = jnp.sum(
                jnp.take(weighed, back, axis=0).reshape(tokens, self.k, dim),
                axis=1, dtype=jnp.float32)
        output = routed
        if self.shared_width:
            with jax.named_scope('shared'):
                dense = lambda features, name: nn.Dense(
                    features, use_bias=False, dtype=compute,
                    name=f'shared_{name}')
                grown = dense(self.shared_width, 'up')(flat)
                if gated:
                    grown = nn.silu(dense(self.shared_width, 'gate')(flat)) \
                        * grown
                else:
                    grown = jnp.square(nn.relu(grown))
                shared = dense(dim, 'down')(grown)
            output = output + shared.astype(jnp.float32)
        return output.reshape(*batch_shape, dim).astype(hidden.dtype)
