"""FSDP param-prefetch / grad-scatter hiding: the three-way sweep.

GSPMD lowers a ZeRO-3 layer to a *monolithic* parameter all-gather on
the critical path of every block and a *monolithic* gradient
reduce-scatter on its backward. The unified overlap scheduler
(`tpusystem/parallel/schedule.py`) decomposes both into the ring idiom
the TP collectives proved (`benchmarks/tp_overlap.py`). This benchmark
times the FSDP-sharded FFN's phases three ways at each shape — the
tp_overlap-style per-phase table:

  wg_mm[gspmd]       partitioner-inserted weight all-gather + matmul
  wg_mm[one-shot]    manual shard_map: lax.all_gather the kernel, matmul
  wg_mm[overlap cN]  decomposed ring gather (schedule.prefetched), N
                     ppermute chunks per hop
  ffn[gspmd]         the whole up -> gelu -> down block, GSPMD collectives
  ffn[one-shot]      manual monolithic kernel gathers inside shard_map
  ffn[overlap cN]    scheduled_ffn under OverlapSchedule(fsdp='prefetch')
  composed[...]      fsdp x model mesh: TP rings AND FSDP prefetch under
                     ONE schedule vs the all-GSPMD baseline (>= 4 devices)

All rows are fwd+bwd with the conv_ceiling data-chained discipline (the
loss is a sum of squares, every gradient folds back into the carried
inputs — nothing hoists or DCEs), so the backward's grad reduce-scatter
is timed too. `python benchmarks/fsdp_overlap.py` prints the table +
summary; `... headline` prints the single JSON line `bench.py` forwards
(`fsdp_overlap_speedup_vs_gspmd`).

Hardware: needs >= 2 accelerator chips; with fewer it exits non-zero.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import materialize as _materialize, require_chips

DEVICES = require_chips(2)
RING = max(size for size in (2, 4) if size <= len(DEVICES))
BATCH, SEQ, DIM, FFN, REPS = 8, 1024, 4096, 14336, 20
CHUNK_COUNTS = (1, 2, 4)


def _chain_scalar(tree):
    total = jnp.float32(0)
    for leaf in jax.tree.leaves(tree):
        total = total + leaf.reshape(-1)[0].astype(jnp.float32)
    return total


def time_fwd_bwd(fn, *args) -> float:
    """Seconds per fwd+bwd over REPS chained iterations (the
    benchmarks/README.md methodology: square loss, gradients folded back
    into the carry, completion forced by a host read)."""
    def loss_fn(*a):
        out = fn(*a)
        return jnp.sum(jnp.square(out.astype(jnp.float32))) * 1e-9

    vg = jax.value_and_grad(loss_fn, argnums=tuple(range(len(args))))

    def body(_, carry):
        loss, grads = vg(*carry)
        feedback = (loss + _chain_scalar(grads)) * 1e-7
        return tuple(a + feedback.astype(a.dtype) for a in carry)

    run = jax.jit(lambda *a: lax.fori_loop(0, REPS, body, a))
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


def _report(tag, seconds, note=None):
    entry = {'phase': tag, 'us': round(seconds * 1e6, 1)}
    if note:
        entry['note'] = note
    print(json.dumps(entry))
    return seconds


def _build(include_composed: bool = True):
    """The case table. ``include_composed=False`` skips the composed
    fsdp x model rows — their operands are a SECOND full device_put of
    every tensor onto the composed mesh (~300 MB of extra HBM +
    host-to-device at the real shapes), which ``headline`` never times."""
    from tpusystem.parallel.mesh import FSDP, MeshSpec
    from tpusystem.parallel.schedule import (OverlapSchedule, fsdp_plan,
                                             prefetched, scheduled_ffn)
    from tpusystem.parallel.sharding import fsdp_shard_dim

    mesh = MeshSpec(fsdp=RING).build(DEVICES[:RING])
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16
    x = jnp.asarray(rng.normal(size=(BATCH, SEQ, DIM)) * 0.1, dtype)
    w_up = jnp.asarray(rng.normal(size=(DIM, FFN)) * 0.02, dtype)
    b_up = jnp.asarray(rng.normal(size=(FFN,)) * 0.02, dtype)
    w_down = jnp.asarray(rng.normal(size=(FFN, DIM)) * 0.02, dtype)
    b_down = jnp.asarray(rng.normal(size=(DIM,)) * 0.02, dtype)

    def put(value, spec):
        return jax.device_put(value, NamedSharding(mesh, spec))

    def constrained(value, spec):
        return lax.with_sharding_constraint(value, NamedSharding(mesh, spec))

    # operands pre-placed the ZeRO-3 way: batch over fsdp, each kernel
    # sharded on the dimension the placement policy would pick (the
    # fsdp_shard_dim single source of truth); biases replicated so the
    # rows time the KERNEL collectives, not a rounding-error gather
    up_dim = fsdp_shard_dim(w_up.shape, RING)
    down_dim = fsdp_shard_dim(w_down.shape, RING)
    up_spec = P(*(FSDP if d == up_dim else None for d in range(2)))
    down_spec = P(*(FSDP if d == down_dim else None for d in range(2)))
    x_rows = put(x, P(FSDP, None, None))
    up_sharded = put(w_up, up_spec)
    b_up_repl = put(b_up, P(None))
    down_sharded = put(w_down, down_spec)
    b_down_repl = put(b_down, P(None))

    def manual(body, in_specs, out_specs):
        return jax.shard_map(body, mesh=mesh, check_vma=False,
                             in_specs=in_specs, out_specs=out_specs)

    cases = {}

    # --- weight all-gather + matmul (the up-projection) -----------------
    cases['wg_mm[gspmd]'] = (
        lambda xs, ws: constrained(jnp.matmul(xs, ws), P(FSDP, None, None)),
        (x_rows, up_sharded), 'partitioner-inserted monolithic gather')
    cases['wg_mm[one-shot]'] = (
        manual(lambda xs, ws: jnp.matmul(
            xs, lax.all_gather(ws, FSDP, axis=up_dim, tiled=True)),
            (P(FSDP, None, None), up_spec), P(FSDP, None, None)),
        (x_rows, up_sharded), 'manual all_gather of the kernel, then matmul')
    for chunks in CHUNK_COUNTS:
        plan = fsdp_plan(w_up.shape, RING, chunks=chunks, min_size=1)
        cases[f'wg_mm[overlap c{chunks}]'] = (
            manual(lambda xs, ws, plan=plan: jnp.matmul(
                xs, prefetched(ws, plan)),
                (P(FSDP, None, None), up_spec), P(FSDP, None, None)),
            (x_rows, up_sharded),
            'ring gather custom_vjp, scatter deferred in bwd')

    # --- the whole FFN block --------------------------------------------
    def ffn_gspmd(xs, wu, bu, wd, bd):
        grown = nn.gelu(jnp.matmul(xs, wu) + bu)
        return constrained(jnp.matmul(grown, wd) + bd, P(FSDP, None, None))

    cases['ffn[gspmd]'] = (
        ffn_gspmd, (x_rows, up_sharded, b_up_repl, down_sharded, b_down_repl),
        'monolithic param gathers + grad scatters from the partitioner')

    def ffn_one_shot(xs, wu, bu, wd, bd):
        wu = lax.all_gather(wu, FSDP, axis=up_dim, tiled=True)
        wd = lax.all_gather(wd, FSDP, axis=down_dim, tiled=True)
        grown = nn.gelu(jnp.matmul(xs, wu) + bu)
        return jnp.matmul(grown, wd) + bd

    cases['ffn[one-shot]'] = (
        manual(ffn_one_shot,
               (P(FSDP, None, None), up_spec, P(None), down_spec, P(None)),
               P(FSDP, None, None)),
        (x_rows, up_sharded, b_up_repl, down_sharded, b_down_repl),
        'manual monolithic kernel gathers inside shard_map')

    for chunks in CHUNK_COUNTS:
        schedule = OverlapSchedule(fsdp='prefetch', chunks=chunks,
                                   fsdp_min_size=1)
        cases[f'ffn[overlap c{chunks}]'] = (
            functools.partial(scheduled_ffn, mesh=mesh, schedule=schedule),
            (x_rows, up_sharded, b_up_repl, down_sharded, b_down_repl),
            'both kernel gathers at FFN entry, grad scatters deferred')

    # --- composed: TP rings AND FSDP prefetch under one schedule --------
    if include_composed and RING >= 4:
        from tpusystem.parallel.mesh import MODEL
        composed = MeshSpec(fsdp=2, model=RING // 2).build(DEVICES[:RING])
        xc = jax.device_put(x, NamedSharding(composed, P(FSDP, None, None)))
        wu_c = jax.device_put(w_up, NamedSharding(composed, P(FSDP, MODEL)))
        bu_c = jax.device_put(b_up, NamedSharding(composed, P(MODEL)))
        wd_c = jax.device_put(w_down, NamedSharding(composed, P(MODEL, FSDP)))
        bd_c = jax.device_put(b_down, NamedSharding(composed, P(None)))

        def composed_gspmd(xs, wu, bu, wd, bd):
            grown = lax.with_sharding_constraint(
                nn.gelu(jnp.matmul(xs, wu) + bu),
                NamedSharding(composed, P(FSDP, None, MODEL)))
            return lax.with_sharding_constraint(
                jnp.matmul(grown, wd) + bd,
                NamedSharding(composed, P(FSDP, None, None)))

        cases['composed[gspmd]'] = (
            composed_gspmd, (xc, wu_c, bu_c, wd_c, bd_c),
            'fsdp x model mesh, every collective monolithic')
        schedule = OverlapSchedule(tp='overlap', fsdp='prefetch', chunks=2,
                                   fsdp_min_size=1)
        cases['composed[schedule c2]'] = (
            functools.partial(scheduled_ffn, mesh=composed,
                              schedule=schedule),
            (xc, wu_c, bu_c, wd_c, bd_c),
            'TP rings + FSDP prefetch in ONE manual region')

    return cases


def sweep() -> dict[str, float]:
    times = {}
    for tag, (fn, args, note) in _build().items():
        times[tag] = _report(tag, time_fwd_bwd(fn, *args), note=note)
    best_chunks, best = min(
        ((chunks, times[f'ffn[overlap c{chunks}]']) for chunks in CHUNK_COUNTS),
        key=lambda pair: pair[1])
    summary = {
        'mesh': f"{DEVICES[0].platform} fsdp={RING}",
        'batch': BATCH, 'seq': SEQ, 'dim': DIM, 'ffn': FFN,
        'ffn_us': {tag.split('[')[1][:-1]: round(times[tag] * 1e6, 1)
                   for tag in times if tag.startswith('ffn[')},
        'best_overlap_chunks': best_chunks,
        'overlap_vs_gspmd': round(times['ffn[gspmd]'] / best, 3),
        'overlap_vs_one_shot': round(times['ffn[one-shot]'] / best, 3),
    }
    if 'composed[schedule c2]' in times:
        summary['composed_schedule_vs_gspmd'] = round(
            times['composed[gspmd]'] / times['composed[schedule c2]'], 3)
    print(json.dumps({'summary': summary}))
    return times


def headline() -> None:
    """The single JSON line bench.py forwards as its fsdp_overlap row."""
    cases = _build(include_composed=False)
    picks = ['ffn[gspmd]'] + [f'ffn[overlap c{c}]' for c in CHUNK_COUNTS]
    times = {tag: time_fwd_bwd(cases[tag][0], *cases[tag][1])
             for tag in picks}
    best_chunks, best = min(
        ((chunks, times[f'ffn[overlap c{chunks}]']) for chunks in CHUNK_COUNTS),
        key=lambda pair: pair[1])
    speedup = times['ffn[gspmd]'] / best
    print(json.dumps({
        'metric': 'fsdp_overlap_speedup_vs_gspmd',
        'value': round(speedup, 4),
        'unit': 'x',
        'mesh': f"{DEVICES[0].platform} fsdp={RING}",
        'chunks': best_chunks,
        'gspmd_us': round(times['ffn[gspmd]'] * 1e6, 1),
        'overlap_us': round(best * 1e6, 1),
    }))


if __name__ == '__main__':
    if 'headline' in sys.argv[1:]:
        headline()
    else:
        sweep()
