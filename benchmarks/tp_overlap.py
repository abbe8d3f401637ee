"""Latency-hiding TP collectives: the three-way decomposition sweep.

BASELINE.md's 8B projection subtracts ICI collective time because every
Megatron TP layer lets GSPMD emit a monolithic all-gather before the
up-projection and a monolithic reduce-scatter after the down-projection,
serializing transfer against the MXU. This benchmark times the
sequence-sharded TP FFN's phases three ways at each shape — the
moe_ceiling-style per-phase table:

  ag_mm[gspmd]       partitioner-inserted all-gather + matmul
  ag_mm[one-shot]    manual shard_map: lax.all_gather, then the matmul
  ag_mm[overlap cN]  decomposed ring (parallel/overlap.py), N ppermute
                     chunks per hop
  mm_rs[...]         the reduce-scatter dual, same three ways
  ffn[...]           the whole up -> gelu -> down block, same three ways

All rows are fwd+bwd with the conv_ceiling data-chained discipline (the
loss is a sum of squares, every gradient folds back into the carried
inputs — nothing hoists or DCEs). `python benchmarks/tp_overlap.py`
prints the table + summary; `... headline` prints the single JSON line
`bench.py` forwards (`tp_ffn_overlap_speedup_vs_gspmd`).

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
TOKENS, DIM, FFN, REPS = 8192, 4096, 14336, 20
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


def _build():
    from tpusystem.parallel.mesh import MODEL, MeshSpec
    from tpusystem.parallel.overlap import (allgather_matmul,
                                            matmul_reducescatter)

    mesh = MeshSpec(model=RING).build(DEVICES[:RING])
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16
    x = jnp.asarray(rng.normal(size=(TOKENS, DIM)) * 0.1, dtype)
    grown_ref = jnp.asarray(rng.normal(size=(TOKENS, FFN)) * 0.1, dtype)
    w_up = jnp.asarray(rng.normal(size=(DIM, FFN)) * 0.02, dtype)
    w_down = jnp.asarray(rng.normal(size=(FFN, DIM)) * 0.02, dtype)

    def put(value, spec):
        return jax.device_put(value, NamedSharding(mesh, spec))

    def constrained(value, spec):
        return lax.with_sharding_constraint(value, NamedSharding(mesh, spec))

    # operands pre-placed the Megatron way: activations sequence-sharded
    # over model rows, up kernel column-split, down kernel row-split
    x_rows = put(x, P(MODEL, None))
    grown_cols = put(grown_ref, P(None, MODEL))
    up_cols = put(w_up, P(None, MODEL))
    down_rows = put(w_down, P(MODEL, None))

    def manual(body, in_specs, out_specs):
        return jax.shard_map(body, mesh=mesh, check_vma=False,
                             in_specs=in_specs, out_specs=out_specs)

    cases = {}

    # --- all-gather + matmul (the up-projection) ------------------------
    cases['ag_mm[gspmd]'] = (
        lambda xs, ws: constrained(jnp.matmul(xs, ws), P(None, MODEL)),
        (x_rows, up_cols), 'partitioner-inserted monolithic all-gather')
    cases['ag_mm[one-shot]'] = (
        manual(lambda xs, ws: jnp.matmul(
            lax.all_gather(xs, MODEL, axis=0, tiled=True), ws),
            (P(MODEL, None), P(None, MODEL)), P(None, MODEL)),
        (x_rows, up_cols), 'manual all_gather, then the matmul')
    for chunks in CHUNK_COUNTS:
        cases[f'ag_mm[overlap c{chunks}]'] = (
            manual(functools.partial(allgather_matmul, axis=MODEL,
                                     chunks=chunks),
                   (P(MODEL, None), P(None, MODEL)), P(None, MODEL)),
            (x_rows, up_cols), 'ring partials, transfers under matmuls')

    # --- matmul + reduce-scatter (the down-projection) ------------------
    cases['mm_rs[gspmd]'] = (
        lambda gs, ws: constrained(jnp.matmul(gs, ws), P(MODEL, None)),
        (grown_cols, down_rows), 'partitioner-inserted reduce-scatter')
    cases['mm_rs[one-shot]'] = (
        manual(lambda gs, ws: lax.psum_scatter(
            jnp.matmul(gs, ws), MODEL, scatter_dimension=0, tiled=True),
            (P(None, MODEL), P(MODEL, None)), P(MODEL, None)),
        (grown_cols, down_rows), 'matmul, then monolithic psum_scatter')
    for chunks in CHUNK_COUNTS:
        cases[f'mm_rs[overlap c{chunks}]'] = (
            manual(functools.partial(matmul_reducescatter, axis=MODEL,
                                     chunks=chunks),
                   (P(None, MODEL), P(MODEL, None)), P(MODEL, None)),
            (grown_cols, down_rows), 'ring-shifted running sum under matmuls')

    # --- the whole FFN block --------------------------------------------
    def ffn_gspmd(xs, wu, wd):
        grown = constrained(nn.gelu(jnp.matmul(xs, wu)), P(None, MODEL))
        return constrained(jnp.matmul(grown, wd), P(MODEL, None))

    cases['ffn[gspmd]'] = (ffn_gspmd, (x_rows, up_cols, down_rows),
                           'monolithic collectives at both ends')

    def ffn_one_shot(xs, wu, wd):
        grown = nn.gelu(jnp.matmul(
            lax.all_gather(xs, MODEL, axis=0, tiled=True), wu))
        return lax.psum_scatter(jnp.matmul(grown, wd), MODEL,
                                scatter_dimension=0, tiled=True)

    cases['ffn[one-shot]'] = (
        manual(ffn_one_shot, (P(MODEL, None), P(None, MODEL),
                              P(MODEL, None)), P(MODEL, None)),
        (x_rows, up_cols, down_rows), 'manual monolithic collectives')

    def ffn_overlap(chunks):
        def body(xs, wu, wd):
            grown = nn.gelu(allgather_matmul(xs, wu, MODEL, chunks=chunks))
            return matmul_reducescatter(grown, wd, MODEL, chunks=chunks)
        return body

    for chunks in CHUNK_COUNTS:
        cases[f'ffn[overlap c{chunks}]'] = (
            manual(ffn_overlap(chunks),
                   (P(MODEL, None), P(None, MODEL), P(MODEL, None)),
                   P(MODEL, None)),
            (x_rows, up_cols, down_rows),
            'both rings, transfers hidden under partial matmuls')

    return cases


def sweep() -> dict[str, float]:
    times = {}
    for tag, (fn, args, note) in _build().items():
        times[tag] = _report(tag, time_fwd_bwd(fn, *args), note=note)
    best_chunks, best = min(
        ((chunks, times[f'ffn[overlap c{chunks}]']) for chunks in CHUNK_COUNTS),
        key=lambda pair: pair[1])
    print(json.dumps({'summary': {
        'mesh': f"{DEVICES[0].platform} model={RING}",
        'tokens': TOKENS, 'dim': DIM, 'ffn': FFN,
        'ffn_us': {tag.split('[')[1][:-1]: round(times[tag] * 1e6, 1)
                   for tag in times if tag.startswith('ffn[')},
        'best_overlap_chunks': best_chunks,
        'overlap_vs_gspmd': round(times['ffn[gspmd]'] / best, 3),
        'overlap_vs_one_shot': round(times['ffn[one-shot]'] / best, 3),
    }}))
    return times


def headline() -> None:
    """The single JSON line bench.py forwards as its tp_overlap row."""
    cases = _build()
    picks = ['ffn[gspmd]'] + [f'ffn[overlap c{c}]' for c in CHUNK_COUNTS]
    times = {tag: time_fwd_bwd(cases[tag][0], *cases[tag][1])
             for tag in picks}
    best_chunks, best = min(
        ((chunks, times[f'ffn[overlap c{chunks}]']) for chunks in CHUNK_COUNTS),
        key=lambda pair: pair[1])
    speedup = times['ffn[gspmd]'] / best
    print(json.dumps({
        'metric': 'tp_ffn_overlap_speedup_vs_gspmd',
        'value': round(speedup, 4),
        'unit': 'x',
        'mesh': f"{DEVICES[0].platform} model={RING}",
        'chunks': best_chunks,
        'gspmd_us': round(times['ffn[gspmd]'] * 1e6, 1),
        'overlap_us': round(best * 1e6, 1),
    }))


if __name__ == '__main__':
    if 'headline' in sys.argv[1:]:
        headline()
    else:
        sweep()
