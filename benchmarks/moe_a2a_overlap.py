"""MoE expert all-to-all hiding: pipelined dispatch vs the one-shot exchange.

The sharded sparse MoE (`tpusystem/ops/moe.py`, quota formulation)
classically exchanges the WHOLE local batch's routed rows over the
expert axis before any expert matmul runs — dispatch, FFN, and return
exchange serialize. The ``moe='overlap'`` arm of the unified scheduler
splits the local rows into microbatch pieces and issues piece k+1's
dispatch ``all_to_all`` under the expert matmuls of piece k (the return
exchange of k rides under the matmuls of k+1). This benchmark times the
MoE layer fwd+bwd both ways:

  moe[one-shot]        single whole-batch exchange (moe='gspmd')
  moe[overlap]         pipelined pieces (moe='overlap', moe_plan-pinned)

All rows are fwd+bwd with the conv_ceiling data-chained discipline.
``python benchmarks/moe_a2a_overlap.py`` prints the table + summary;
``... headline`` prints the single JSON line `bench.py` forwards
(`moe_a2a_overlap_speedup`).

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
EXPERT_AX = max(size for size in (2, 4) if size <= len(DEVICES))
TOKENS, DIM, EXPERTS, REPS = 8192, 2048, 16, 20


def time_fwd_bwd(fn, *args) -> float:
    """Seconds per fwd+bwd over REPS chained iterations (the
    benchmarks/README.md methodology)."""
    def loss_fn(*a):
        out, aux = fn(*a)
        return (jnp.sum(jnp.square(out.astype(jnp.float32))) * 1e-9
                + aux * 1e-9)

    vg = jax.value_and_grad(loss_fn, argnums=tuple(range(len(args))))

    def chain(tree):
        total = jnp.float32(0)
        for leaf in jax.tree.leaves(tree):
            total = total + leaf.reshape(-1)[0].astype(jnp.float32)
        return total

    def body(_, carry):
        loss, grads = vg(*carry)
        feedback = (loss + chain(grads)) * 1e-7
        return tuple(jax.tree.map(
            lambda leaf: leaf + feedback.astype(leaf.dtype), a)
            for a in carry)

    run = jax.jit(lambda *a: lax.fori_loop(0, REPS, body, a))
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


def _build():
    from tpusystem.ops.moe import MoEMLP
    from tpusystem.parallel import (MeshSpec, OverlapSchedule, ShardingPolicy,
                                    batch_sharding, moe_plan)

    data = len(DEVICES) // EXPERT_AX
    mesh = MeshSpec(data=data, expert=EXPERT_AX).build(DEVICES)
    rng = np.random.default_rng(0)
    dtype = jnp.bfloat16
    hidden = jnp.asarray(rng.normal(size=(TOKENS, DIM)) * 0.1, jnp.float32)
    local_rows = TOKENS // (data * EXPERT_AX)
    assert moe_plan(local_rows, EXPERT_AX).path == 'overlap', (
        'shape must pipeline for the A/B to mean anything')

    def layer(schedule):
        module = MoEMLP(EXPERTS, dtype=dtype, mesh=mesh,
                        capacity_factor=2.0, schedule=schedule)
        params = module.init(jax.random.PRNGKey(0), hidden[:8])['params']
        from tpusystem.ops.moe import moe_partition_rules
        params = ShardingPolicy(rules=tuple(
            (pattern.replace('moe/', ''), spec)
            for pattern, spec in moe_partition_rules())).place(params, mesh)
        placed = jax.device_put(hidden, batch_sharding(mesh))

        def fn(x, params):
            return module.apply({'params': params}, x)
        return fn, (placed, params)

    cases = {}
    fn, args = layer(None)
    cases['moe[one-shot]'] = (fn, args,
                              'whole-batch exchange before any expert matmul')
    fn, args = layer(OverlapSchedule(moe='overlap'))
    cases['moe[overlap]'] = (fn, args,
                             'piece k+1 dispatch under expert matmuls of k')
    return cases


def sweep() -> dict[str, float]:
    times = {}
    for tag, (fn, args, note) in _build().items():
        seconds = time_fwd_bwd(fn, *args)
        times[tag] = seconds
        print(json.dumps({'phase': tag, 'us': round(seconds * 1e6, 1),
                          'note': note}))
    print(json.dumps({'summary': {
        'mesh': f"{DEVICES[0].platform} expert={EXPERT_AX}",
        'tokens': TOKENS, 'dim': DIM, 'experts': EXPERTS,
        'overlap_vs_one_shot': round(times['moe[one-shot]']
                                     / times['moe[overlap]'], 3),
    }}))
    return times


def headline() -> None:
    """The single JSON line bench.py forwards as its moe_a2a row."""
    times = {tag: time_fwd_bwd(fn, *args)
             for tag, (fn, args, _) in _build().items()}
    print(json.dumps({
        'metric': 'moe_a2a_overlap_speedup',
        'value': round(times['moe[one-shot]'] / times['moe[overlap]'], 4),
        'unit': 'x',
        'mesh': f"{DEVICES[0].platform} expert={EXPERT_AX}",
        'one_shot_us': round(times['moe[one-shot]'] * 1e6, 1),
        'overlap_us': round(times['moe[overlap]'] * 1e6, 1),
    }))


if __name__ == '__main__':
    if 'headline' in sys.argv[1:]:
        headline()
    else:
        sweep()
