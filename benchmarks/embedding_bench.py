"""Embedding lookup throughput: the fused Pallas row-gather vs the
``jnp.take`` fallback, swept over table size x batch (id count).

The recommender hot path is row movement, not FLOPs: a lookup streams
``ids * dim * itemsize`` bytes of table rows (plus the grad scatter-add
on the way back), so the metric is **looked-up rows per second** and the
interesting lever is whether the fused kernel's scalar-prefetched DMAs
beat XLA's gather at each shape. One JSON line per row (the
moe_dispatch convention); ``headline`` mode prints the single
``embedding_lookup_speedup`` row bench.py forwards (fwd+bwd at the
headline shape, fused over take).

On TPU the fused rows run the real kernels; off-TPU they run in
interpreter mode — numerics-true but orders of magnitude slower, so CPU
numbers are parity smoke, not performance (the speedup row says which).
REPS drop 50 -> 2 off-TPU for the same reason.
"""

import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from tpusystem.ops.pallas.embedding_lookup import embedding_lookup
from tpusystem.parallel.mesh import on_tpu

ON_TPU = on_tpu()
REPS = 50 if ON_TPU else 2
TRIALS = 3
# off-TPU the fused rows run interpreter-mode kernels (numerics smoke,
# not performance) — the sequential grad scatter interprets one row at a
# time, so the smoke sweep shrinks to stay in seconds
SWEEP_TABLES = (65536, 1048576) if ON_TPU else (1024, 4096)
SWEEP_COUNTS = (4096, 32768) if ON_TPU else (256, 1024)
HEADLINE = (1048576, 128, 32768) if ON_TPU else (4096, 128, 1024)


def materialize(value) -> None:
    float(jnp.sum(jax.tree.leaves(value)[0].astype(jnp.float32)))


def _case(table_rows: int, dim: int, count: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((table_rows, dim)), jnp.float32)
    # Zipf-flavored ids: the duplicate-heavy regime real click logs have
    pmf = 1.0 / np.arange(1, table_rows + 1) ** 1.3
    pmf /= pmf.sum()
    ids = jnp.asarray(rng.choice(table_rows, size=count, p=pmf), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.5, 1.5, (count,)), jnp.float32)
    return table, ids, weights


def _timed(run, *operands) -> float:
    run(*operands)
    materialize(run(*operands))                      # warm + compile
    trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        materialize(run(*operands))
        trials.append(time.perf_counter() - start)
    return sorted(trials)[len(trials) // 2]


def lookup_row(table_rows: int, dim: int, count: int, *,
               grad: bool = False) -> dict:
    table, ids, weights = _case(table_rows, dim, count)

    def chain(impl):
        def once(tab, wts):
            out = embedding_lookup(tab, ids, wts, impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        # the carry perturbs the weights each iteration: a data
        # dependency defeats loop-invariant code motion (the
        # conv_ceiling.py lesson — a hoisted take path would time ~1
        # lookup amortized over REPS), at 1e-30 numeric cost
        if not grad:
            return jax.jit(lambda tab, wts: jax.lax.fori_loop(
                0, REPS,
                lambda i, acc: acc + once(tab, wts + acc * 1e-30),
                jnp.float32(0)))
        grad_fn = jax.grad(once)
        return jax.jit(lambda tab, wts: jax.lax.fori_loop(
            0, REPS,
            lambda i, acc: acc + jnp.sum(
                grad_fn(tab, wts + acc * 1e-30)[:1, :1]),
            jnp.float32(0)))

    take_s = _timed(chain('take'), table, weights)
    fused_s = _timed(chain('fused'), table, weights)
    to_rows = lambda seconds: count * REPS / seconds
    return {
        'metric': 'embedding_lookup',
        'phase': 'fwd+bwd' if grad else 'fwd',
        'table_rows': table_rows,
        'dim': dim,
        'batch_ids': count,
        'take_rows_per_s': round(to_rows(take_s)),
        'fused_rows_per_s': round(to_rows(fused_s)),
        'fused_speedup_vs_take': round(take_s / fused_s, 3),
        'backend': jax.default_backend(),
    }


def sweep() -> None:
    for table_rows in SWEEP_TABLES:
        for count in SWEEP_COUNTS:
            print(json.dumps(lookup_row(table_rows, 128, count)))
    print(json.dumps(lookup_row(*HEADLINE, grad=True)))


def headline() -> None:
    table_rows, dim, count = HEADLINE
    row = lookup_row(table_rows, dim, count, grad=True)
    print(json.dumps({
        'metric': 'embedding_lookup_speedup',
        'value': row['fused_speedup_vs_take'],
        'unit': (f'x (fused vs jnp.take, fwd+bwd, '
                 f'{table_rows} x {dim} table, {count} ids)'),
        'fused_rows_per_s': row['fused_rows_per_s'],
        'take_rows_per_s': row['take_rows_per_s'],
        'note': None if ON_TPU else ('CPU smoke: fused runs in interpreter '
                                     'mode — parity, not performance'),
    }))


if __name__ == '__main__':
    if 'headline' in sys.argv[1:]:
        headline()
    else:
        sweep()
        headline()
