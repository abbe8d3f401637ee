"""Serving recovery cost: kill → relaunch → first replayed token.

The failover layer's promise (``tpusystem/serve/failover.py``) measured:
a serving replica mid-workload is "killed" (its engine and scheduler
abandoned — the in-process stand-in for SIGKILL; the journal lives in
the supervisor-side :class:`~tpusystem.checkpoint.memstore.MemStore`,
exactly where a real worker's pushes land), then recovery is timed from
the kill to the **first replayed token** two ways:

1. ``hot``  — the journal is recovered and each in-flight request
             re-prefills ``prompt + emitted prefix``, resuming decode
             where it died;
2. ``cold`` — no journal: every request re-submits from scratch and
             re-decodes its whole budget (what recovery costs without
             the journal — the re-submit path a truncated-replication
             outage degrades to).

Both arms pay the same engine rebuild (fresh jit of the decode step, the
bucketized prefill programs are process-cached); the hot arm's first
token arrives after ONE re-prefill per row, the cold arm additionally
re-decodes every already-delivered token before the workload finishes —
``drain_seconds`` shows that tail. Greedy decode is deterministic, so
both arms finish token-exact (asserted every trial).

Every row is one machine-readable JSON line (the ``decode_roofline.py``
convention); the LAST line is the ``serve_recovery_seconds`` headline
``bench.py`` forwards (value = hot first-token seconds, with the cold
arm alongside). CPU numbers are smoke; the TPU protocol rides the same
script (BASELINE.md "serve protocol" sizing caveats apply).

Run: ``python benchmarks/serve_recovery.py [headline]``.
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
from tpusystem.serve import Engine, Request, Scheduler, ServingReplica

TRIALS = 3
ROWS = 4
KILL_TICK = 6
ON_TPU = on_tpu()


def recipe():
    """Model + workload (the ``serve_bench.py`` sizing discipline)."""
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


def run_to_kill(module, params, prompts, budgets, store):
    """Serve the workload up to KILL_TICK with per-tick journal pushes,
    then abandon the replica (the kill). Returns the completions already
    delivered before the kill (reference material for the parity check)."""
    build = lambda: Scheduler(Engine(module, params, rows=ROWS,
                                     block_size=16 if ON_TPU else 8))
    replica = ServingReplica(build, identity='bench', client=store,
                             cadence=1)
    for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
        replica.submit(Request(f'r{index}', prompt, budget))
    for _ in range(KILL_TICK):
        replica.step()
    return dict(replica.results)


def recover(module, params, prompts, budgets, store, reference):
    """Time kill -> first replayed token and kill -> fully drained, for
    one recovery arm: ``store`` holding the journal (hot) or an empty
    one (cold — the requests re-submit raw). Asserts the union of
    pre-kill and post-recovery completions is token-exact vs the
    uninterrupted reference."""
    build = lambda: Scheduler(Engine(module, params, rows=ROWS,
                                     block_size=16 if ON_TPU else 8))
    start = time.perf_counter()
    replica = ServingReplica(build, identity='bench', client=store,
                             cadence=1)
    if not replica.recovered:       # the cold arm: every request still
        # open at the kill re-submits raw (already-completed ones were
        # delivered before the kill and have nothing to recover)
        for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
            if f'r{index}' in reference:
                replica.submit(Request(f'r{index}', prompt, budget))
    first_token = None
    while not replica.idle:
        tick = replica.step()
        if first_token is None and tick is not None and (
                tick.emitted or tick.admitted):
            first_token = time.perf_counter() - start
    drained = time.perf_counter() - start
    for rid, completion in replica.results.items():
        expected = reference[rid].tokens
        assert completion.tokens == expected, (
            f'{rid} diverged after recovery: {completion.tokens} vs '
            f'{expected}')
    return first_token, drained, replica.recovered


def main() -> None:
    module, params, prompts, budgets = recipe()

    # the uninterrupted reference: every request's full greedy output
    engine = Engine(module, params, rows=ROWS,
                    block_size=16 if ON_TPU else 8)
    scheduler = Scheduler(engine)
    for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
        scheduler.submit(Request(f'r{index}', prompt, budget))
    reference = scheduler.run()

    hot_firsts, hot_drains = [], []
    cold_firsts, cold_drains = [], []
    for _ in range(TRIALS):
        store = MemStore()
        pre_kill = run_to_kill(module, params, prompts, budgets, store)
        open_reference = {rid: completion for rid, completion
                          in reference.items() if rid not in pre_kill}
        first, drained, recovered = recover(
            module, params, prompts, budgets, store, open_reference)
        assert recovered, 'hot arm found no journal'
        hot_firsts.append(first)
        hot_drains.append(drained)
        first, drained, recovered = recover(
            module, params, prompts, budgets, MemStore(), open_reference)
        assert not recovered, 'cold arm unexpectedly found a journal'
        cold_firsts.append(first)
        cold_drains.append(drained)

    median = lambda times: sorted(times)[len(times) // 2]
    workload = (f'{len(prompts)} reqs, killed at tick {KILL_TICK}, rows '
                f'{ROWS}')
    print(json.dumps({'metric': 'serve_recovery_cold_seconds',
                      'value': round(median(cold_firsts), 4),
                      'unit': 's kill -> first token (cold re-submit)',
                      'drain_seconds': round(median(cold_drains), 4)}))
    hot = median(hot_firsts)
    cold = median(cold_firsts)
    print(json.dumps({
        'metric': 'serve_recovery_seconds',
        'value': round(hot, 4),
        'unit': f's kill -> first replayed token ({workload})'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'cold_seconds': round(cold, 4),
        'hot_drain_seconds': round(median(hot_drains), 4),
        'cold_drain_seconds': round(median(cold_drains), 4),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: every section prints anyway
