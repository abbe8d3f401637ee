"""Pipeline p2p hiding: skewed-overlap GPipe ticks vs the classic tick.

The classic GPipe tick sends a stage's output AFTER the compute that
produced it — inside a sequential ``lax.scan``, that ``ppermute`` sits on
the critical path between every pair of ticks. The ``pp='overlap'`` arm
of the unified scheduler (`tpusystem/parallel/schedule.py`) skews the
schedule one tick per hop so each send is issued UNDER the next
microbatch's stage compute (`tpusystem/parallel/pipeline.py`;
`collectives.pp_hop` carries the custom_vjp so the backward's reversed
sends hide the same way). This benchmark times a stacked-matmul pipe
fwd+bwd both ways at each shape:

  pipe[classic]        post-compute sends (pp='gspmd', the default tick)
  pipe[overlap cN]     skewed double-buffered ticks, N ppermute chunks
                       per hop

All rows are fwd+bwd with the conv_ceiling data-chained discipline (the
loss is a sum of squares, every gradient folds back into the carried
inputs — nothing hoists or DCEs). ``python benchmarks/pp_overlap.py``
prints the table + summary; ``... headline`` prints the single JSON line
`bench.py` forwards (`pp_overlap_speedup_vs_gspmd`).

Hardware: needs >= 2 accelerator chips; with fewer it exits non-zero.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import materialize as _materialize, require_chips

DEVICES = require_chips(2)
STAGES = max(size for size in (2, 4) if size <= len(DEVICES))
LAYERS, BATCH, DIM, MICRO, REPS = STAGES * 2, 8, 4096, 8, 20
CHUNK_COUNTS = (1, 2)


def time_fwd_bwd(fn, *args) -> float:
    """Seconds per fwd+bwd over REPS chained iterations (the
    benchmarks/README.md methodology)."""
    def loss_fn(*a):
        out = fn(*a)
        return jnp.sum(jnp.square(out.astype(jnp.float32))) * 1e-9

    vg = jax.value_and_grad(loss_fn, argnums=tuple(range(len(args))))

    def chain(tree):
        total = jnp.float32(0)
        for leaf in jax.tree.leaves(tree):
            total = total + leaf.reshape(-1)[0].astype(jnp.float32)
        return total

    def body(_, carry):
        loss, grads = vg(*carry)
        feedback = (loss + chain(grads)) * 1e-7
        return tuple(a + feedback.astype(a.dtype) for a in carry)

    run = jax.jit(lambda *a: lax.fori_loop(0, REPS, body, a))
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


def _build():
    from tpusystem.parallel import (MeshSpec, OverlapSchedule,
                                    pipeline_apply, pp_plan)

    mesh = MeshSpec(stage=STAGES, data=len(DEVICES) // STAGES).build(DEVICES)
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16
    weights = jnp.asarray(
        rng.normal(size=(LAYERS, DIM, DIM)) * (1.0 / np.sqrt(DIM)), dtype)
    inputs = jnp.asarray(rng.normal(size=(BATCH * MICRO
                                          * mesh.shape['data'], DIM)) * 0.1,
                         dtype)
    block_fn = lambda lp, x: jnp.tanh(x @ lp)
    micro_rows = inputs.shape[0] // mesh.shape['data'] // MICRO

    cases = {}
    cases['pipe[classic]'] = (
        lambda w, x: pipeline_apply(block_fn, w, x, mesh, microbatches=MICRO,
                                    remat=False),
        (weights, inputs), 'post-compute sends on the tick critical path')
    for chunks in CHUNK_COUNTS:
        plan = pp_plan(micro_rows, STAGES, chunks=chunks)
        if plan.path != 'overlap':
            continue
        schedule = OverlapSchedule(pp='overlap', chunks=chunks)
        cases[f'pipe[overlap c{chunks}]'] = (
            lambda w, x, schedule=schedule: pipeline_apply(
                block_fn, w, x, mesh, microbatches=MICRO, remat=False,
                schedule=schedule),
            (weights, inputs),
            'skewed ticks: sends ride under the next microbatch compute')
    return cases


def sweep() -> dict[str, float]:
    times = {}
    for tag, (fn, args, note) in _build().items():
        seconds = time_fwd_bwd(fn, *args)
        times[tag] = seconds
        print(json.dumps({'phase': tag, 'us': round(seconds * 1e6, 1),
                          'note': note}))
    overlaps = {tag: t for tag, t in times.items() if 'overlap' in tag}
    best_tag, best = min(overlaps.items(), key=lambda pair: pair[1])
    print(json.dumps({'summary': {
        'mesh': f"{DEVICES[0].platform} stage={STAGES}",
        'layers': LAYERS, 'batch': BATCH, 'dim': DIM, 'microbatches': MICRO,
        'best_overlap': best_tag,
        'overlap_vs_classic': round(times['pipe[classic]'] / best, 3),
    }}))
    return times


def headline() -> None:
    """The single JSON line bench.py forwards as its pp_overlap row."""
    times = {tag: time_fwd_bwd(fn, *args)
             for tag, (fn, args, _) in _build().items()}
    overlaps = {tag: t for tag, t in times.items() if 'overlap' in tag}
    best_tag, best = min(overlaps.items(), key=lambda pair: pair[1])
    print(json.dumps({
        'metric': 'pp_overlap_speedup_vs_gspmd',
        'value': round(times['pipe[classic]'] / best, 4),
        'unit': 'x',
        'mesh': f"{DEVICES[0].platform} stage={STAGES}",
        'chunks': int(best_tag.split('c')[-1].rstrip(']')),
        'classic_us': round(times['pipe[classic]'] * 1e6, 1),
        'overlap_us': round(best * 1e6, 1),
    }))


if __name__ == '__main__':
    if 'headline' in sys.argv[1:]:
        headline()
    else:
        sweep()
