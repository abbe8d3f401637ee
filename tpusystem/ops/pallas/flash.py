"""Flash attention — Pallas TPU kernel.

Blockwise-online-softmax attention: O(seq) memory instead of the O(seq^2)
scores tensor that XLA attention materializes (the allocation that caps
single-chip GPT-2 batch size). Forward and backward are hand-written
kernels; the public entry :func:`flash_attention` carries a ``custom_vjp``
so ``jax.grad`` works transparently.

Kernel shape notes (see /opt/skills/guides/pallas_guide.md):
* grid iterates (batch*heads, q_block, kv_block) with the kv dimension
  innermost — running max/sum/accumulator live in VMEM scratch across the
  kv sweep and the output block is written once on the final kv step;
* softmax statistics are kept as (block_q, 128) f32 tiles (lane-replicated)
  to match the VPU tile shape *inside* the kernel, but logsumexp is stored
  to HBM as a compact (bh, seq, 8) array (sublane-tile replication only);
* causal blocks strictly above the diagonal are skipped via predication;
  the diagonal block applies a triangular mask from 2D broadcasted_iota;
* logsumexp is saved for the backward pass, which recomputes P blockwise
  (dq kernel sweeps kv; dk/dv kernel sweeps q innermost).

SPMD note: a ``pallas_call`` is a manual computation that GSPMD cannot
auto-partition, so the raw kernel runs **one device per shard**. To compose
with GSPMD policies (DP/FSDP/TP), :func:`sharded_flash_attention` wraps the
kernel in ``shard_map`` — attention is embarrassingly parallel over
batch x heads, so batch shards over the (data, fsdp) axes and heads over
the model axis, matching the Megatron-style TP rules the model families
ship. ``attend(kernel='flash', mesh=...)`` routes there automatically.

``interpret=True`` runs the same kernels in interpreter mode for CPU tests.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusystem.ops.attention import NEG_INF

from tpusystem.ops.pallas import auto_interpret

LANES = 128  # VPU lane count: in-VMEM softmax stats are (block_q, LANES) tiles
G1_VMEM_LIMIT = 96 * 1024 * 1024  # scoped-VMEM budget requested by the
             # resident-dq fused backward; past its estimated working set
             # the backward auto-routes to the split sweeps.
STATS = 8    # trailing dim of HBM-stored lse/delta — the f32 sublane tile.
             # Mosaic requires the last two block dims divisible by (8, 128) or
             # equal to the array dims, so a compact (bh, seq) layout is not
             # lowerable; (bh, seq, 8) stores 8 replicated f32 per position,
             # 16x less HBM than lane-replicated (bh, seq, 128).


def _masked_scores(query, key, *, scale, causal, q_idx, kv_idx,
                   block_q, block_kv):
    """f32 (block_q, block_kv) scores with the causal mask applied.

    Shared by the forward, dq and dkv kernels so the mask/scale arithmetic
    cannot drift between forward and backward.
    """
    scores = jax.lax.dot_general(
        query, key, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) + q_idx * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + kv_idx * block_kv
        scores = jnp.where(rows >= cols, scores, NEG_INF)
    return scores


def _visible(causal: bool, q_idx, kv_idx, block_q: int, block_kv: int):
    """Predicate: does this (q, kv) block intersect the causal triangle?"""
    return (not causal) or (q_idx * block_q + block_q - 1 >= kv_idx * block_kv)


def _keep_mask(seed, head_row, q_idx, kv_idx, block_q: int, block_kv: int,
               dropout: float):
    """Per-tile Bernoulli(1 - dropout) keep mask, reproducible by position.

    A counter-style hash (xorshift-multiply mixing) of the *global*
    (head-row, query-position, key-position) triple plus the step seed —
    not the sequential hardware PRNG — so the forward, dq, and dkv kernels
    regenerate byte-identical masks even though their grids sweep the
    tiles in different orders, and interpret mode (CPU tests) produces the
    same masks as the TPU lowering.
    """
    shape = (block_q, block_kv)
    rows = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
            + (q_idx * block_q).astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
            + (kv_idx * block_kv).astype(jnp.uint32))
    x = rows * jnp.uint32(0x9E3779B1) ^ cols * jnp.uint32(0x85EBCA77)
    x = x + seed.astype(jnp.uint32) + head_row.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> 8) < jnp.uint32(int(round((1.0 - dropout) * (1 << 24))))


def _flash_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr,
                      *, scale: float, causal: bool,
                      block_q: int, block_kv: int, dropout: float):
    # program_id must be read at the kernel top level (not inside pl.when
    # bodies — interpret mode does not substitute it there)
    head, q_idx, kv_idx = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv_steps = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip blocks strictly above the diagonal
    @pl.when(_visible(causal, q_idx, kv_idx, block_q, block_kv))
    def _block():
        query = q_ref[0]                      # (block_q, head_dim)
        value = v_ref[0]
        scores = _masked_scores(query, k_ref[0], scale=scale, causal=causal,
                                q_idx=q_idx, kv_idx=kv_idx,
                                block_q=block_q, block_kv=block_kv)

        m_prev = m_scr[:, :1]                               # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        probs = jnp.exp(scores - m_new)                     # (block_q, block_kv)
        correction = jnp.exp(m_prev - m_new)                # (block_q, 1)
        # the softmax denominator accumulates UNmasked probabilities —
        # attention-probability dropout drops normalized weights, it does
        # not renormalize over survivors (the 'xla' path's semantics)
        l_new = correction * l_scr[:, :1] + jnp.sum(probs, axis=1, keepdims=True)
        if dropout:
            keep = _keep_mask(seed_ref[0], head, q_idx, kv_idx,
                              block_q, block_kv, dropout)
            contrib = probs * keep
        else:
            contrib = probs
        acc_scr[...] = acc_scr[...] * correction + jax.lax.dot_general(
            contrib.astype(value.dtype), value, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kv_idx == kv_steps - 1)
    def _finish():
        l_final = l_scr[:, :1]
        safe_l = jnp.where(l_final == 0.0, 1.0, l_final)
        out = acc_scr[...] / safe_l
        if dropout:
            out = out / (1.0 - dropout)       # inverted-dropout scaling
        o_ref[0] = out.astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)                # (block_q, 1)
        lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], STATS))


def _flash_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref,
                     dq_scr, *, scale: float, causal: bool,
                     block_q: int, block_kv: int, dropout: float):
    head, q_idx, kv_idx = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv_steps = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_visible(causal, q_idx, kv_idx, block_q, block_kv))
    def _block():
        key, value = k_ref[0], v_ref[0]
        grad_out = do_ref[0]
        scores = _masked_scores(q_ref[0], key, scale=scale, causal=causal,
                                q_idx=q_idx, kv_idx=kv_idx,
                                block_q=block_q, block_kv=block_kv)
        probs = jnp.exp(scores - lse_ref[0, :, :1])          # (block_q, 1)
        dprobs = jax.lax.dot_general(
            grad_out, value, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout:
            # d(out)/d(score): the kept-weight term carries the mask and
            # the 1/(1-p) scale; the softmax-denominator term keeps the
            # full (unmasked) probability — see the forward's l rule
            keep = _keep_mask(seed_ref[0], head, q_idx, kv_idx,
                              block_q, block_kv, dropout)
            dprobs = keep * dprobs / (1.0 - dropout)
        dscores = probs * (dprobs - delta_ref[0, :, :1]) * scale
        dq_scr[...] += jax.lax.dot_general(
            dscores.astype(key.dtype), key, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_idx == kv_steps - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                      *, scale: float, causal: bool,
                      block_q: int, block_kv: int, q_steps: int, group: int,
                      dropout: float):
    # the innermost grid dim sweeps (group member, q block) pairs under
    # GQA: the q-block index for causal masking is its q_steps remainder,
    # and dk/dv accumulate across the whole sweep
    kv_idx, sweep = pl.program_id(1), pl.program_id(2)
    q_idx = sweep % q_steps
    # the mask row is the QUERY head's bh row (the forward hashed with
    # program_id(0) over B*Hq; this grid's dim 0 walks KV rows); read at
    # top level — interpret mode does not substitute program_id in when-bodies
    head_row = pl.program_id(0) * group + sweep // q_steps

    @pl.when(sweep == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_visible(causal, q_idx, kv_idx, block_q, block_kv))
    def _block():
        query, value = q_ref[0], v_ref[0]
        grad_out = do_ref[0]
        scores = _masked_scores(query, k_ref[0], scale=scale, causal=causal,
                                q_idx=q_idx, kv_idx=kv_idx,
                                block_q=block_q, block_kv=block_kv)
        probs = jnp.exp(scores - lse_ref[0, :, :1])           # (bq, bkv)
        dprobs = jax.lax.dot_general(
            grad_out, value, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout:
            keep = _keep_mask(seed_ref[0], head_row, q_idx, kv_idx,
                              block_q, block_kv, dropout)
            kept = probs * keep / (1.0 - dropout)
            dprobs = keep * dprobs / (1.0 - dropout)
        else:
            kept = probs
        dv_scr[...] += jax.lax.dot_general(
            kept.astype(grad_out.dtype), grad_out, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bkv, d)
        dscores = probs * (dprobs - delta_ref[0, :, :1]) * scale
        dk_scr[...] += jax.lax.dot_general(
            dscores.astype(query.dtype), query, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(sweep == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_block_terms(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, head_row, q_idx, kv_idx,
                     *, scale, causal, block_q, block_kv, dropout):
    """The backward block math shared by both fused kernels: recompute
    scores/probs once and return ``(kept, dscores, query, key, grad_out)``
    — ``kept`` feeds dv (mask-and-rescaled under dropout), ``dscores``
    feeds dk and dq. One definition so the GQA partial-array kernel and
    the MHA resident-dq kernel cannot drift numerically."""
    query, key, value = q_ref[0], k_ref[0], v_ref[0]
    grad_out = do_ref[0]
    scores = _masked_scores(query, key, scale=scale, causal=causal,
                            q_idx=q_idx, kv_idx=kv_idx,
                            block_q=block_q, block_kv=block_kv)
    probs = jnp.exp(scores - lse_ref[0, :, :1])               # (bq, bkv)
    dprobs = jax.lax.dot_general(
        grad_out, value, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if dropout:
        keep = _keep_mask(seed_ref[0], head_row, q_idx, kv_idx,
                          block_q, block_kv, dropout)
        kept = probs * keep / (1.0 - dropout)
        dprobs = keep * dprobs / (1.0 - dropout)
    else:
        kept = probs
    dscores = probs * (dprobs - delta_ref[0, :, :1]) * scale
    return kept, dscores, query, key, grad_out


def _flash_fused_bwd_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, dq_ref, dk_ref, dv_ref,
                            dk_scr, dv_scr,
                            *, scale: float, causal: bool,
                            block_q: int, block_kv: int, group: int,
                            dropout: float):
    """Single-pass backward: dq, dk and dv from ONE score recomputation.

    The split backward (:func:`_flash_dq_kernel` + :func:`_flash_dkv_kernel`)
    computes ``scores = q k^T`` and ``dprobs = do v^T`` twice per visible
    block — once per kernel. Fused, the seven backward matmuls drop to five
    (scores, dprobs, dv, dk, dq), a 2/7 cut of the backward's MXU work.

    Grid layout (the splash-attention fused-backward shape): ``(kv_steps,
    bh, q_steps)`` with the KV dimension OUTERMOST. Within one kv section
    every query head of a KV group and every q block revisit the same
    dk/dv output block consecutively, so dk/dv accumulate in VMEM scratch
    and flush once per (kv head, kv block). dq cannot accumulate across
    the outer kv dimension (non-consecutive revisits), so each grid step
    writes its partial to a ``(kv_steps, bh, seq_q, d)`` output that the
    caller reduces with a plain sum — free at the headline tiling where
    kv_steps == 1.
    """
    kv_idx, head_row, q_idx = (pl.program_id(0), pl.program_id(1),
                               pl.program_id(2))
    q_steps = pl.num_programs(2)

    @pl.when(jnp.logical_and(head_row % group == 0, q_idx == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = _visible(causal, q_idx, kv_idx, block_q, block_kv)

    @pl.when(visible)
    def _block():
        kept, dscores, query, key, grad_out = _bwd_block_terms(
            seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            head_row, q_idx, kv_idx, scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, dropout=dropout)
        dv_scr[...] += jax.lax.dot_general(
            kept.astype(grad_out.dtype), grad_out, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bkv, d)
        dk_scr[...] += jax.lax.dot_general(
            dscores.astype(query.dtype), query, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_ref[0, 0] = jax.lax.dot_general(
            dscores.astype(key.dtype), key, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)

    @pl.when(jnp.logical_not(visible))
    def _skip():
        # the partial-dq block is written every step (revisit semantics
        # would otherwise leave the previous block's bytes in the buffer)
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(jnp.logical_and(head_row % group == group - 1,
                             q_idx == q_steps - 1))
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_fused_bwd_g1_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                               lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                               dk_scr, dv_scr,
                               *, scale: float, causal: bool,
                               block_q: int, block_kv: int, dropout: float):
    """Fused backward without the partial-dq array (``group == 1``).

    Grid ``(bh, kv_steps, q_steps)``: for one head row, every (kv, q)
    block maps to the SAME f32 dq output block ``(1, seq_q, d)``, which
    Pallas keeps resident in VMEM across the whole row — dq accumulates
    in place in float32 and is written to HBM once per row (single
    rounding, zero partial traffic; the ``(kv_steps, ...)`` partial array
    of :func:`_flash_fused_bwd_kernel` costs ~2% MFU at seq 16k). dk/dv
    accumulate in scratch across each kv row's q sweep as usual. GQA
    (group > 1) cannot use this layout — a KV head's dk/dv revisits are
    non-consecutive when bh is outermost — and keeps the partial-array
    kernel."""
    kv_idx, q_idx = pl.program_id(1), pl.program_id(2)
    head = pl.program_id(0)
    kv_steps, q_steps = pl.num_programs(1), pl.num_programs(2)

    @pl.when(jnp.logical_and(kv_idx == 0, q_idx == 0))
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(q_idx == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_visible(causal, q_idx, kv_idx, block_q, block_kv))
    def _block():
        kept, dscores, query, key, grad_out = _bwd_block_terms(
            seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            head, q_idx, kv_idx, scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, dropout=dropout)
        dv_scr[...] += jax.lax.dot_general(
            kept.astype(grad_out.dtype), grad_out, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bkv, d)
        dk_scr[...] += jax.lax.dot_general(
            dscores.astype(query.dtype), query, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(q_idx * block_q, block_q)
        dq_ref[0, rows, :] += jax.lax.dot_general(
            dscores.astype(key.dtype), key, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_idx == q_steps - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _fit_block(seq: int, want: int, granule: int = LANES) -> int | None:
    """Largest lane-aligned divisor of ``seq`` that is <= ``want``.

    Keeps mid-size sequence lengths (768, 1536, ...) on the flash kernel
    with a smaller tile instead of silently dropping to the O(seq^2) XLA
    fallback when the requested tile does not divide them. Sequences at or
    under one granule run as a single block; sequences that no aligned
    tile divides return None (XLA fallback).
    """
    want = min(want, seq)
    if seq <= granule:
        # single block, if it tiles onto the sublanes; otherwise XLA
        return seq if seq % 8 == 0 else None
    best = None
    for candidate in range(granule, want + 1, granule):
        if seq % candidate == 0:
            best = candidate
    return best


def _block_sizes(seq_q: int, seq_kv: int, block_q: int, block_kv: int):
    block_q = _fit_block(seq_q, block_q)
    block_kv = _fit_block(seq_kv, block_kv)
    if block_q is None or block_kv is None:
        return None
    return block_q, block_kv


def _flash_fwd(q, k, v, seed, causal, scale, block_q, block_kv, interpret,
               group=1, dropout=0.0):
    """q: [B*Hq, S, D]; k/v: [B*Hkv, S, D] with Hq = Hkv * group.

    GQA lives entirely in the index maps: query row ``i`` reads KV row
    ``i // group`` (b-major head layout makes that exact), so grouped KV
    is never materialized at the query head count. ``seed`` is a [1] int32
    (SMEM) feeding the positional dropout hash. Returns
    (out, residuals)."""
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    grid = (bh, seq_q // block_q, seq_kv // block_kv)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, dropout=dropout)
    # the seed input exists only on the dropout path, so the dropout=0
    # program (the perf-critical one) is identical to a seedless build
    seed_args, seed_specs, kernel = _seed_wiring(kernel, seed, dropout)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, head_dim), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((1, block_kv, head_dim),
                         lambda i, j, k_: (i // group, k_, 0)),
            pl.BlockSpec((1, block_kv, head_dim),
                         lambda i, j, k_: (i // group, k_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((1, block_q, STATS), lambda i, j, k_: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, STATS), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(*seed_args, q, k, v)
    return out, (q, k, v, seed, out, lse)


def _seed_wiring(kernel, seed, dropout):
    """Seed input wiring: present only when dropout is active (the
    dropout=0 kernels never read it, and omitting the argument keeps the
    hot-path program identical to a seedless build). Returns
    ``(extra_args, extra_in_specs, kernel)``."""
    if dropout:
        return (seed,), [pl.BlockSpec(memory_space=pltpu.SMEM)], kernel
    return (), [], functools.partial(kernel, None)


def _flash_bwd_impl(causal, scale, block_q, block_kv, interpret, group,
                    dropout, backward, residuals, grad_out, grad_lse):
    """Backward for :func:`_flash_lse`. ``grad_lse`` (bh, seq_q) is the
    cotangent of the logsumexp output (ring attention merges chunk results
    by lse, so gradient flows into it; plain ``flash_attention`` discards
    lse and its cotangent arrives as zeros); per-score gradient is
    p*(dprobs - (delta - dlse)), so it folds into the precomputed delta
    term. Under dropout the kernels regenerate the forward's positional
    keep masks from the same seed.

    ``backward``: ``'fused'`` runs the single-pass dq+dk+dv kernel (one
    score recomputation per block — 5 backward matmuls instead of 7);
    ``'split'`` keeps the separate dq / dkv sweeps — the manual A/B
    reference. The resident-dq fused variant (group 1, multi-kv-step)
    additionally auto-routes to ``'split'`` when its estimated VMEM
    working set (whole-row f32 dq + block IO + f32 score intermediates)
    exceeds the 96 MB limit it requests — the one fused layout whose
    working set grows with ``seq_q`` rather than the block sizes."""
    q, k, v, seed, out, lse = residuals
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    delta = jnp.sum(grad_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (bh, seq_q, 1)
    if grad_lse is not None:
        delta = delta - grad_lse.astype(jnp.float32)[..., None]
    delta = jnp.broadcast_to(delta, (bh, seq_q, STATS))

    resident_dq = backward == 'fused' and group == 1 and seq_kv > block_kv
    if resident_dq:
        # Conservative working-set estimate for the resident-dq layout:
        # whole-row f32 dq, double-buffered input blocks, f32 dk/dv
        # scratch, and ~3 f32 (block_q, block_kv) score intermediates.
        # Past the limit requested below, Mosaic would fail the
        # pallas_call — route to the split sweeps instead (block-sized
        # working set, independent of seq_q).
        g1_bytes = (4 * seq_q * head_dim
                    + 2 * q.dtype.itemsize * (3 * block_q + 2 * block_kv)
                    * head_dim
                    + 2 * 4 * block_kv * head_dim
                    + 3 * 4 * block_q * block_kv)
        if g1_bytes > G1_VMEM_LIMIT:
            warnings.warn(
                f"fused flash backward: estimated VMEM working set "
                f"{g1_bytes / 2**20:.1f} MB exceeds the "
                f"{G1_VMEM_LIMIT >> 20} MB limit at this (seq, block) "
                "combination; falling back to the split dq/dkv sweeps.",
                stacklevel=2)
            backward, resident_dq = 'split', False
    if resident_dq:
        # multi-kv-step MHA: accumulate dq in a resident f32 output block
        # (no partial array, single rounding — see the kernel docstring).
        # The whole-row dq block plus the f32 score intermediates exceed
        # the default scoped-VMEM budget at long seq; raise the limit.
        kv_steps, q_steps = seq_kv // block_kv, seq_q // block_q
        kernel = functools.partial(
            _flash_fused_bwd_g1_kernel, scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, dropout=dropout)
        seed_args, seed_specs, kernel = _seed_wiring(kernel, seed, dropout)
        q_row = lambda i, kv, j: (i, j, 0)
        kv_row = lambda i, kv, j: (i, kv, 0)
        dq_f32, dk, dv = pl.pallas_call(
            kernel,
            grid=(bh, kv_steps, q_steps),
            in_specs=seed_specs + [
                pl.BlockSpec((1, block_q, head_dim), q_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_q, head_dim), q_row),
                pl.BlockSpec((1, block_q, STATS), q_row),
                pl.BlockSpec((1, block_q, STATS), q_row),
            ],
            out_specs=[
                pl.BlockSpec((1, seq_q, head_dim), lambda i, kv, j: (i, 0, 0)),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq_q, head_dim), jnp.float32),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, head_dim), jnp.float32),
                pltpu.VMEM((block_kv, head_dim), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=G1_VMEM_LIMIT),
            interpret=interpret,
        )(*seed_args, q, k, v, grad_out, lse, delta)
        dq = dq_f32.astype(q.dtype)
        return dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0)

    if backward == 'fused':
        kv_steps, q_steps = seq_kv // block_kv, seq_q // block_q
        kernel = functools.partial(
            _flash_fused_bwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, group=group, dropout=dropout)
        seed_args, seed_specs, kernel = _seed_wiring(kernel, seed, dropout)
        q_row = lambda kv, i, j: (i, j, 0)
        kv_row = lambda kv, i, j: (i // group, kv, 0)
        # partials in f32 when they will be summed across kv steps: bf16
        # rounding before a 16-way sum (seq 16k at 1024 tiles) would make
        # dq noisier than the split path's f32 scratch accumulation; at
        # kv_steps == 1 (headline) the sum is a copy and q.dtype is exact
        partial_dtype = q.dtype if kv_steps == 1 else jnp.float32
        dq_partial, dk, dv = pl.pallas_call(
            kernel,
            grid=(kv_steps, bh, q_steps),
            in_specs=seed_specs + [
                pl.BlockSpec((1, block_q, head_dim), q_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_q, head_dim), q_row),
                pl.BlockSpec((1, block_q, STATS), q_row),
                pl.BlockSpec((1, block_q, STATS), q_row),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, head_dim),
                             lambda kv, i, j: (kv, i, j, 0)),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
                pl.BlockSpec((1, block_kv, head_dim), kv_row),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((kv_steps, bh, seq_q, head_dim),
                                     partial_dtype),
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_kv, head_dim), jnp.float32),
                pltpu.VMEM((block_kv, head_dim), jnp.float32),
            ],
            interpret=interpret,
        )(*seed_args, q, k, v, grad_out, lse, delta)
        dq = jnp.sum(dq_partial, axis=0, dtype=jnp.float32).astype(q.dtype)
        return dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0)

    dq_kernel = functools.partial(
        _flash_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, dropout=dropout)
    seed_args, seed_specs, dq_kernel = _seed_wiring(dq_kernel, seed, dropout)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_q // block_q, seq_kv // block_kv),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, head_dim), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((1, block_kv, head_dim),
                         lambda i, j, k_: (i // group, k_, 0)),
            pl.BlockSpec((1, block_kv, head_dim),
                         lambda i, j, k_: (i // group, k_, 0)),
            pl.BlockSpec((1, block_q, head_dim), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((1, block_q, STATS), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((1, block_q, STATS), lambda i, j, k_: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim), lambda i, j, k_: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
    )(*seed_args, q, k, v, grad_out, lse, delta)

    q_steps = seq_q // block_q
    dkv_kernel = functools.partial(
        _flash_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, q_steps=q_steps, group=group,
        dropout=dropout)
    seed_args, seed_specs, dkv_kernel = _seed_wiring(dkv_kernel, seed, dropout)
    # grid dim 0 walks KV rows; the innermost dim sweeps every (group
    # member, q block) pair so one kv head's dk/dv accumulates over all
    # the query heads that shared it
    row = lambda i, k_, j: (i * group + j // q_steps, j % q_steps, 0)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh // group, seq_kv // block_kv, q_steps * group),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, head_dim), row),
            pl.BlockSpec((1, block_kv, head_dim), lambda i, k_, j: (i, k_, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda i, k_, j: (i, k_, 0)),
            pl.BlockSpec((1, block_q, head_dim), row),
            pl.BlockSpec((1, block_q, STATS), row),
            pl.BlockSpec((1, block_q, STATS), row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, head_dim), lambda i, k_, j: (i, k_, 0)),
            pl.BlockSpec((1, block_kv, head_dim), lambda i, k_, j: (i, k_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(*seed_args, q, k, v, grad_out, lse, delta)
    return dq, dk, dv, np.zeros(seed.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, seed, causal, scale, block_q, block_kv, interpret,
               group, dropout, backward):
    (out, lse), _ = _flash_lse_fwd(q, k, v, seed, causal, scale, block_q,
                                   block_kv, interpret, group, dropout,
                                   backward)
    return out, lse


def _flash_lse_fwd(q, k, v, seed, causal, scale, block_q, block_kv, interpret,
                   group, dropout, backward):
    out, residuals = _flash_fwd(q, k, v, seed, causal, scale, block_q,
                                block_kv, interpret, group, dropout)
    lse = residuals[5][..., 0]                                # (bh, seq_q)
    return (out, lse), residuals


def _flash_lse_bwd(causal, scale, block_q, block_kv, interpret, group,
                   dropout, backward, residuals, grads):
    grad_out, grad_lse = grads
    return _flash_bwd_impl(causal, scale, block_q, block_kv, interpret,
                           group, dropout, backward, residuals, grad_out,
                           grad_lse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(query, key, value, *, causal: bool = True,
                    scale: float | None = None,
                    block_q: int = 1024, block_kv: int = 1024,
                    interpret: bool | None = None,
                    dropout: float = 0.0, dropout_rng=None,
                    backward: str = 'fused'):
    """Flash attention over [batch, length, heads, head_dim] tensors.

    Drop-in for :func:`tpusystem.ops.attention.dot_product_attention`
    (GQA handled in-kernel: grouped KV is shared across each query-head
    group via the block index maps, never broadcast) in single-device-per-shard
    contexts — see the module docstring for the GSPMD caveat. Falls back to
    the XLA path when the sequence length does not divide the block sizes.
    ``interpret=None`` auto-selects interpreter mode off-TPU so the same
    model code runs in CPU tests.

    ``dropout > 0`` (with ``dropout_rng``) drops attention probabilities
    in-kernel with the 'xla' path's semantics: normalized weights are
    dropped (no renormalization over survivors) and survivors scale by
    ``1/(1-p)``. Masks come from a positional counter hash seeded by
    ``dropout_rng``, regenerated identically in the backward kernels —
    nothing O(seq^2) is ever stored.

    Thin front of :func:`flash_attention_lse`: the discarded lse output
    costs nothing (the kernel computes it regardless) and its zero
    cotangent folds to a no-op in the shared backward.
    """
    out, _ = flash_attention_lse(query, key, value, causal=causal,
                                 scale=scale, block_q=block_q,
                                 block_kv=block_kv, interpret=interpret,
                                 dropout=dropout, dropout_rng=dropout_rng,
                                 backward=backward)
    return out


def flash_attention_lse(query, key, value, *, causal: bool = True,
                        scale: float | None = None,
                        block_q: int = 1024, block_kv: int = 1024,
                        interpret: bool | None = None,
                        dropout: float = 0.0, dropout_rng=None,
                        backward: str = 'fused'):
    """Flash attention that also returns the softmax logsumexp.

    Returns ``(out [B,S,H,D], lse [B,S,H] float32)``. The lse output is what
    lets blockwise results merge exactly: ring attention computes each KV
    chunk's ``(out_i, lse_i)`` independently and combines them with
    logsumexp weights (see :mod:`tpusystem.ops.ring`). Differentiable in
    both outputs — the lse cotangent folds into the backward kernels' delta
    term. Falls back to a differentiable XLA path (explicit scores +
    logsumexp) when no lane-aligned block divides the sequence.

    ``dropout``/``dropout_rng``: in-kernel attention-probability dropout
    (see :func:`flash_attention`). The lse output stays the FULL softmax
    denominator (dropout does not renormalize), so blockwise merges are
    unaffected.

    ``backward='fused'`` (default) runs the single-pass dq+dk+dv backward
    kernel — one score recomputation per block, 5 matmuls instead of the
    split path's 7; ``'split'`` keeps the separate dq / dkv kernels (the
    A/B reference and large-tile fallback; see :func:`_flash_bwd_impl`).
    """
    interpret = auto_interpret(interpret)
    if dropout:
        if dropout_rng is None:
            raise ValueError('dropout > 0 needs a dropout_rng key')
        seed = jax.random.randint(dropout_rng, (1,), 0, jnp.iinfo(jnp.int32).max,
                                  dtype=jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)

    batch, seq_q, q_heads, head_dim = query.shape
    kv_heads = key.shape[2]
    assert q_heads % kv_heads == 0, (
        f'query heads ({q_heads}) must be a multiple of KV heads '
        f'({kv_heads}) for grouped-query attention')
    # GQA stays grouped: the kernel maps each query head to its KV head via
    # the block index maps, so KV is never materialized q_heads wide
    group = q_heads // kv_heads
    scale = scale if scale is not None else head_dim ** -0.5

    if backward not in ('fused', 'split'):
        raise ValueError(f"backward must be 'fused' or 'split', got {backward!r}")
    # Tile-size note (measured on v5e, seq 8k-16k MHA): kv-2048 tiles are
    # 6-9% faster on the isolated fwd+bwd attention chain, but the WHOLE
    # training step with remat is 2-5% slower (the rematerialized forward
    # runs twice and loses more at 2048 than the backward gains), so the
    # 1024/1024 default stands; pass block_kv explicitly to override.
    sizes = _block_sizes(seq_q, key.shape[1], block_q, block_kv)
    if sizes is None:
        from tpusystem.ops.attention import repeat_kv_heads
        key, value = repeat_kv_heads(query, key, value)
        return _xla_attention_lse(query, key, value, causal=causal,
                                  scale=scale, dropout=dropout,
                                  dropout_rng=dropout_rng)
    block_q, block_kv = sizes

    def to_bh(tensor):  # [B,S,H,D] -> [B*H, S, D]
        return tensor.transpose(0, 2, 1, 3).reshape(-1, tensor.shape[1], head_dim)

    out, lse = _flash_lse(to_bh(query), to_bh(key), to_bh(value), seed,
                          causal, scale, block_q, block_kv, interpret, group,
                          float(dropout), backward)
    out = out.reshape(batch, q_heads, seq_q, head_dim).transpose(0, 2, 1, 3)
    lse = lse.reshape(batch, q_heads, seq_q).transpose(0, 2, 1)
    return out, lse


def _xla_attention_lse(query, key, value, *, causal: bool, scale: float,
                       dropout: float = 0.0, dropout_rng=None):
    """Reference (out, lse) pair in plain XLA ops — the fallback for
    sequence lengths the kernel cannot tile, and the 'einsum' inner kernel
    of ring attention."""
    from tpusystem.ops.attention import causal_mask

    scores = jnp.einsum('bqhd,bkhd->bhqk', query, key,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        scores = jnp.where(causal_mask(query.shape[1], key.shape[1]),
                           scores, NEG_INF)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)        # [B,H,Q]
    weights = jnp.exp(scores - lse[..., None])
    if dropout and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout, weights.shape)
        weights = jnp.where(keep, weights / (1.0 - dropout), 0.0)
    out = jnp.einsum('bhqk,bkhd->bqhd', weights.astype(value.dtype), value)
    return out, lse.transpose(0, 2, 1)                        # lse -> [B,S,H]


def sharded_flash_attention(query, key, value, mesh, *, causal: bool = True,
                            scale: float | None = None,
                            dropout: float = 0.0, dropout_rng=None):
    """Flash attention composed with GSPMD policies via ``shard_map``.

    Attention is embarrassingly parallel over batch x heads: batch shards
    over the (data, fsdp) mesh axes and heads over the model axis — the
    layout the TP partition rules already give the QKV projections — and
    the Pallas kernel runs independently per shard. Differentiable (the
    kernel's ``custom_vjp`` composes with ``shard_map``'s transpose).

    Axes that do not divide the corresponding tensor dimension are left
    replicated (e.g. ``module.init`` traces with batch 1). Under GQA the
    KV-head axis shards over ``model`` when divisible; otherwise KV heads
    are broadcast up to the query head count first.
    """
    from math import prod

    from jax.sharding import PartitionSpec as P

    from tpusystem.ops.attention import repeat_kv_heads
    from tpusystem.parallel.mesh import DATA, FSDP, MODEL

    shape = dict(mesh.shape)
    batch_axes = tuple(axis for axis in (DATA, FSDP) if shape.get(axis, 1) > 1)
    if batch_axes and query.shape[0] % prod(shape[a] for a in batch_axes):
        batch_axes = ()
    model = shape.get(MODEL, 1)
    head_axis = MODEL if model > 1 and query.shape[2] % model == 0 else None
    if head_axis and key.shape[2] % model:
        warnings.warn(
            f"sharded_flash_attention: {key.shape[2]} KV heads do not divide "
            f"the model axis ({model}); broadcasting KV to the "
            f"{query.shape[2]} query heads. This is correct but forfeits the "
            "GQA KV memory saving on this mesh — pick a model axis that "
            "divides the KV head count to keep grouped KV.",
            stacklevel=2)
        key, value = repeat_kv_heads(query, key, value)

    spec = P(batch_axes or None, None, head_axis, None)

    # check_vma=False: pallas_call out_shapes carry no varying-mesh-axis
    # info, so shard_map's replication checker cannot see through the kernel
    @functools.partial(jax.shard_map, mesh=mesh, check_vma=False,
                       in_specs=(spec, spec, spec), out_specs=spec)
    def mapped(q, k, v):
        rng = dropout_rng
        if dropout and rng is not None:
            # decorrelate the dropout masks across shards (the positional
            # hash would otherwise repeat per local batch/head index)
            for axis in (DATA, FSDP, MODEL):
                if shape.get(axis, 1) > 1:
                    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               dropout=dropout, dropout_rng=rng)

    return mapped(query, key, value)
