"""Router failover MTTR: kill the active Router → first completed token.

The crash-recoverable Router's promise (``tpusystem/serve/fleet.py`` +
the journaled state of ``tpusystem/serve/failover.py``) measured: a
three-replica fleet is serving a mixed workload when the active Router
is abandoned mid-stream (the in-process stand-in for SIGKILL — the
replicas and the memstore plane outlive it, exactly what a real router
crash leaves behind), and a warm standby takes over. Recovery is timed
from the kill to the **first completed token under the standby** two
ways:

1. ``hot``  — the router journal is recovered from the plane: seated
             rows re-attach and keep streaming, queued rows re-place,
             settled results survive;
2. ``cold`` — no journal (the plane lost it): the health sweep alone
             rebuilds the tables from the replicas' own request
             journals and results — what takeover costs when the
             journal cadence lost the race.

Both arms fence the lease term first (the split-brain guard is part of
the measured path) and both drain token-exact vs an uninterrupted
fleet (asserted every trial — greedy decode is deterministic).

Every row is one machine-readable JSON line (the ``decode_roofline.py``
convention); the LAST line is the ``router_failover_seconds`` headline
``bench.py`` forwards (value = hot takeover-to-first-completion
seconds, with the cold arm alongside). CPU numbers are smoke; the TPU
protocol rides the same script (BASELINE.md "router failover protocol").

Run: ``python benchmarks/serve_failover.py [headline]``.
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
                             RouterJournal, RouterLease, Scheduler,
                             ServingReplica)

TRIALS = 3
REPLICAS = 3
ROWS = 2
KILL_TICK = 4
ON_TPU = on_tpu()


def recipe():
    """Model + workload (the ``serve_recovery.py`` sizing discipline)."""
    if ON_TPU:
        module = GPT2(dropout=0.0, vocab_size=50304, max_seq=512)
        lengths, vocab = (16, 32, 64, 96), 50257
        budgets = (24, 24, 24, 96) * 2
    else:
        module = gpt2_tiny(dtype='float32', layers=4, dim=256, heads=8,
                           vocab_size=1024, max_seq=256)
        lengths, vocab = (4, 8, 16, 24), 1024
        budgets = (12, 12, 12, 48) * 2
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (lengths[i % len(lengths)],))
               .astype(np.int32).tolist() for i in range(len(budgets))]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray([prompts[0]], jnp.int32))['params']
    return module, params, prompts, list(budgets)


def build_fleet(module, params, plane, *, holder='router'):
    """Three journaled replicas under a leased, journaled Router whose
    authoritative state replicates to ``plane`` every tick."""
    handles = []
    for index in range(REPLICAS):
        def build():
            return Scheduler(Engine(module, params, rows=ROWS,
                                    block_size=16 if ON_TPU else 8))
        handles.append(ReplicaHandle(ServingReplica(
            build, identity=f'rep{index}', client=MemStore(), cadence=1)))
    lease = RouterLease(client=plane, holder=holder)
    router = Router(handles, journal=RouterJournal(client=plane, cadence=1),
                    lease=lease)
    lease.acquire()
    return router


def run_to_kill(module, params, prompts, budgets, plane):
    """Serve up to KILL_TICK under the incumbent, then abandon it (the
    kill). Returns the fleet's surviving pieces: the replica handles
    and the results already settled before the kill."""
    router = build_fleet(module, params, plane)
    for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
        router.submit(Request(f'r{index}', prompt, budget))
    for _ in range(KILL_TICK):
        router.step()
    return router.handles, dict(router.results)


def takeover(module, params, handles, plane, journal_plane, reference):
    """Time kill -> first completed token under the standby, for one
    arm: ``journal_plane`` holding the router journal (hot) or an empty
    one (cold sweep). The lease fence and the recovery replay are both
    inside the timed window — this IS the MTTR the client sees."""
    start = time.perf_counter()
    lease = RouterLease(client=plane, holder='standby')
    standby = Router(handles, journal=RouterJournal(client=journal_plane,
                                                    cadence=1), lease=lease)
    lease.acquire()                 # fence the old term: split-brain guard
    report = standby.recover((journal_plane,))
    first_completion = None
    while not standby.idle:
        tick = standby.step()
        if first_completion is None and tick.completed:
            first_completion = time.perf_counter() - start
    drained = time.perf_counter() - start
    if first_completion is None:    # everything settled pre-kill/recover
        first_completion = drained
    for rid, completion in standby.results.items():
        expected = reference[rid].tokens
        assert completion.tokens == expected, (
            f'{rid} diverged across the takeover: {completion.tokens} vs '
            f'{expected}')
    return first_completion, drained, report['source']


def main() -> None:
    module, params, prompts, budgets = recipe()

    # the uninterrupted reference: the same fleet, never killed
    router = build_fleet(module, params, MemStore())
    for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
        router.submit(Request(f'r{index}', prompt, budget))
    reference = router.run_until_idle()

    hot_firsts, hot_drains = [], []
    cold_firsts, cold_drains = [], []
    for _ in range(TRIALS):
        plane = MemStore()
        handles, _pre = run_to_kill(module, params, prompts, budgets, plane)
        first, drained, source = takeover(
            module, params, handles, plane, plane, reference)
        assert source == 'journal', f'hot arm recovered via {source!r}'
        hot_firsts.append(first)
        hot_drains.append(drained)

        plane = MemStore()
        handles, _pre = run_to_kill(module, params, prompts, budgets, plane)
        first, drained, source = takeover(
            module, params, handles, plane, MemStore(), reference)
        assert source == 'sweep', f'cold arm recovered via {source!r}'
        cold_firsts.append(first)
        cold_drains.append(drained)

    median = lambda times: sorted(times)[len(times) // 2]
    workload = (f'{len(prompts)} reqs, {REPLICAS} replicas, router killed '
                f'at tick {KILL_TICK}')
    print(json.dumps({'metric': 'router_failover_cold_seconds',
                      'value': round(median(cold_firsts), 4),
                      'unit': 's kill -> first completion (cold sweep)',
                      'drain_seconds': round(median(cold_drains), 4)}))
    print(json.dumps({
        'metric': 'router_failover_seconds',
        'value': round(median(hot_firsts), 4),
        'unit': f's kill -> first completion under the standby ({workload})'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'cold_seconds': round(median(cold_firsts), 4),
        'hot_drain_seconds': round(median(hot_drains), 4),
        'cold_drain_seconds': round(median(cold_drains), 4),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: every section prints anyway
