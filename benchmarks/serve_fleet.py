"""Fleet recovery cost: kill a replica mid-stream → first rerouted token.

The fleet tier's promise (``tpusystem/serve/fleet.py``) measured: 3
replicas serve a mixed workload, one is "killed" mid-stream (its handle's
kill seam — the in-process stand-in for SIGKILL; the journal lives in a
supervisor-side :class:`~tpusystem.checkpoint.memstore.MemStore` that
outlives it), and recovery is timed from the kill to the **first token a
rerouted request emits on a surviving replica**, two ways:

1. ``hot``  — the router recovers the dead replica's journal through the
             preference chain and redistributes: seated rows re-prefill
             ``prompt + emitted prefix`` on a survivor and resume;
2. ``cold`` — no recoverable journal: the router's own routing table
             re-submits every open request raw (what the handoff costs
             without the journal — the cadence-gap path).

Both arms pay the same redistribution plumbing; the hot arm's rerouted
rows resume mid-budget while the cold arm re-decodes every
already-delivered token before the fleet drains — ``drain_seconds``
shows that tail. Greedy decode is deterministic, so both arms finish
token-exact against an uninterrupted fleet (asserted every trial).

Every row is one machine-readable JSON line (the ``serve_recovery.py``
convention); the LAST line is the ``fleet_recovery_seconds`` headline
``bench.py`` forwards (value = hot first-token seconds, cold arm
alongside). CPU numbers are smoke; the TPU protocol rides the same
script (BASELINE.md "serve protocol" sizing caveats apply).

Run: ``python benchmarks/serve_fleet.py [headline]``.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpusystem.checkpoint.memstore import MemStore
from tpusystem.models import GPT2, gpt2_tiny
from tpusystem.parallel.mesh import on_tpu
from tpusystem.serve import (Engine, ReplicaHandle, Request, Router,
                             Scheduler, ServingReplica)

TRIALS = 3
REPLICAS = 3
ROWS = 2
KILL_TICK = 3
ON_TPU = on_tpu()


def recipe():
    """Model + workload (the ``serve_recovery.py`` sizing discipline):
    more requests than the fleet's rows, so the killed replica holds
    seated AND queued work — both handoff flavors exercised."""
    if ON_TPU:
        module = GPT2(dropout=0.0, vocab_size=50304, max_seq=512)
        lengths, vocab = (16, 32, 64, 96), 50257
        budgets = (24, 24, 24, 96, 24, 24, 24, 96, 24)
    else:
        module = gpt2_tiny(dtype='float32', layers=4, dim=256, heads=8,
                           vocab_size=1024, max_seq=256)
        lengths, vocab = (4, 8, 16, 24), 1024
        budgets = (12, 12, 12, 48, 12, 12, 12, 48, 12)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (lengths[i % len(lengths)],))
               .astype(np.int32).tolist() for i in range(len(budgets))]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray([prompts[0]], jnp.int32))['params']
    return module, params, prompts, list(budgets)


def build_fleet(module, params, *, journaled):
    """3 replicas, each journaling every tick into its supervisor-RAM
    store (hot arm) or not at all (cold arm: the router's routing table
    is the only survivor of a kill)."""
    handles = []
    for i in range(REPLICAS):
        store = MemStore() if journaled else None
        build = lambda: Scheduler(Engine(module, params, rows=ROWS,
                                         block_size=16 if ON_TPU else 8))
        handles.append(ReplicaHandle(ServingReplica(
            build, identity=f'rep{i}', client=store, cadence=1)))
    return Router(handles), handles


def submit_all(router, prompts, budgets):
    for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
        router.submit(Request(f'r{index}', prompt, budget))


def trial(module, params, prompts, budgets, reference, *, journaled):
    """One kill-mid-stream run: returns (first rerouted token seconds,
    drain seconds, hot reroutes, cold reroutes), token-exactness of the
    WHOLE workload asserted against the uninterrupted reference."""
    router, handles = build_fleet(module, params, journaled=journaled)
    submit_all(router, prompts, budgets)
    killed_at = None
    rerouted_ids: set = set()
    first = drained = None
    hot = cold = 0
    for _ in range(10_000):
        if router.idle:
            break
        if router.ticks + 1 == KILL_TICK:
            handles[0].kill()
            killed_at = time.perf_counter()
        tick = router.step()
        for event in tick.rerouted:
            rerouted_ids.add(event.id)
            hot += event.where == 'hot'
            cold += event.where == 'cold'
        if (first is None and killed_at is not None
                and rerouted_ids & set(tick.emitted)):
            first = time.perf_counter() - killed_at
    drained = time.perf_counter() - killed_at
    assert router.idle and rerouted_ids, 'the kill rerouted nothing'
    for rid, completion in router.results.items():
        expected = reference[rid].tokens
        assert completion.tokens == expected, (
            f'{rid} diverged across the handoff: {completion.tokens} vs '
            f'{expected}')
    return first, drained, hot, cold


def main() -> None:
    module, params, prompts, budgets = recipe()

    # the uninterrupted fleet: every request's full greedy output
    router, _ = build_fleet(module, params, journaled=True)
    submit_all(router, prompts, budgets)
    reference = router.run_until_idle()

    hot_firsts, hot_drains = [], []
    cold_firsts, cold_drains = [], []
    flavors = None
    for _ in range(TRIALS):
        first, drain, hot, cold = trial(module, params, prompts, budgets,
                                        reference, journaled=True)
        hot_firsts.append(first)
        hot_drains.append(drain)
        flavors = (hot, cold)
        first, drain, _hot, _cold = trial(module, params, prompts, budgets,
                                          reference, journaled=False)
        cold_firsts.append(first)
        cold_drains.append(drain)

    median = lambda times: sorted(times)[len(times) // 2]
    workload = (f'{len(prompts)} reqs over {REPLICAS} replicas, 1 killed '
                f'at tick {KILL_TICK}')
    print(json.dumps({'metric': 'fleet_recovery_cold_seconds',
                      'value': round(median(cold_firsts), 4),
                      'unit': 's kill -> first rerouted token (no journal:'
                              ' routing-table cold re-submit)',
                      'drain_seconds': round(median(cold_drains), 4)}))
    print(json.dumps({
        'metric': 'fleet_recovery_seconds',
        'value': round(median(hot_firsts), 4),
        'unit': f's kill -> first rerouted token ({workload}; journal '
                f'handoff {flavors[0]} hot / {flavors[1]} cold)'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'cold_seconds': round(median(cold_firsts), 4),
        'hot_drain_seconds': round(median(hot_drains), 4),
        'cold_drain_seconds': round(median(cold_drains), 4),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: every section prints anyway
