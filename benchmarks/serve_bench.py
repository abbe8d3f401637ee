"""Serving throughput: continuous batching vs static padded batching.

The serving engine's reason to exist, measured: a mixed-length synthetic
workload (short and long generations interleaved, the shape real traffic
has) served two ways —

1. ``static``     — classic padded batching: requests grouped in arrival
                    order into fixed batches of ``ROWS``, prompts padded
                    to the workload's widest bucket, every row decoded to
                    its group's LONGEST request (the whole batch waits on
                    the straggler; short rows burn steps on tokens nobody
                    asked for). One ``generate()`` call per group — all
                    groups share one compiled program.
2. ``continuous`` — the paged engine (`tpusystem/serve/`): iteration-
                    level scheduling admits a queued request the moment a
                    row frees, so a retired short request's row is
                    immediately producing a new request's tokens instead
                    of padding out the straggler.

Tokens/sec counts only **delivered** tokens (what each request asked
for) over wall time, so the static arm pays for its dead rows. Per-phase
rows decompose the continuous arm (prefill / admit / decode dispatch
time from the engine's own counters).

A third arm measures **radix prefix sharing** (``shared``): N requests
that open with one long system prompt and differ only in a short user
suffix — the shape RAG/chat traffic has — served with
``share_prefix=True`` vs without. With sharing, admission adopts the
cached prefix blocks and prefills only the uncached suffix, so the
prefill cost per request collapses from ``bucket(prefix + suffix)`` to
``bucket(suffix)``; the ``prefix_hit_rate`` row reports the fraction of
prompt tokens adopted and every completion is asserted token-exact
against standalone ``generate()``.

Every row is one machine-readable JSON line (the ``decode_roofline.py``
convention); the LAST line is the ``serve_tok_s`` headline ``bench.py``
forwards, and the ``serve_shared_prefix_speedup`` row is forwarded as
its own ``bench.py`` line. On CPU the numbers are smoke (documented in
BASELINE.md "serve protocol" and "shared-prefix serve protocol" — the
TPU protocol uses the 125M decode config); the *ratios* are the
architectural claims: continuous batching >= 2x static, and sharing
>= 1.5x no-sharing delivered tok/s on the shared-prompt workload.

A fourth arm measures **seeded sampling** (``sampled``): the same mixed
workload served greedy vs with per-request seeded top-k/top-p
``SamplingParams`` on ONE engine (one compiled trace for both arms) —
the cost of counter-based sampling inside the compiled step, with
determinism asserted bitwise every trial (each timed pass is re-run
with the same seeds and compared token-for-token).

Run: ``python benchmarks/serve_bench.py [headline|shared|sampled]`` —
``shared`` prints only the prefix-sharing section (its last line is the
``serve_shared_prefix_speedup`` row ``bench.py`` forwards); ``sampled``
prints only the sampling section (last line ``serve_sampled_tok_s``).
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import materialize
from tpusystem.models import GPT2, gpt2_tiny
from tpusystem.parallel.mesh import on_tpu
from tpusystem.serve import Engine, Request, SamplingParams, Scheduler
from tpusystem.train import generate

TRIALS = 3
ROWS = 4
ON_TPU = on_tpu()


def recipe():
    """Model + workload. TPU: the BASELINE decode config (125M). CPU:
    tiny GPT-2 — smoke numbers, real ratio."""
    if ON_TPU:
        module = GPT2(dropout=0.0, vocab_size=50304, max_seq=512)
        lengths, vocab = (16, 32, 64, 96), 50257
        budgets = (16, 16, 16, 96) * 3          # short x3 : 1 straggler
    else:
        # big enough that a decode step is compute-bound, not dispatch-
        # bound (the tiny preset hides the batching win behind CPU
        # per-dispatch overhead — measured 1.2 ms/step static scan vs
        # 3 ms/step engine dispatch at dim 64)
        module = gpt2_tiny(dtype='float32', layers=4, dim=256, heads=8,
                           vocab_size=1024, max_seq=256)
        lengths, vocab = (4, 8, 16, 24), 1024
        budgets = (8, 8, 8, 64) * 3
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (lengths[i % len(lengths)],))
               .astype(np.int32) for i in range(len(budgets))]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray(prompts[0][None]))['params']
    return module, params, prompts, list(budgets)


def static_arm(module, params, prompts, budgets) -> tuple[float, int]:
    """Median wall seconds for the whole workload, padded-batch style,
    plus delivered tokens. All groups pad prompts to the workload's
    widest prompt and decode to the group's longest budget."""
    width = max(len(p) for p in prompts)
    groups = [slice(i, i + ROWS) for i in range(0, len(prompts), ROWS)]

    def run_once() -> None:
        for group in groups:
            batch_prompts = prompts[group]
            batch_budgets = budgets[group]
            padded = np.zeros((len(batch_prompts), width), np.int32)
            for row, prompt in enumerate(batch_prompts):
                padded[row, :len(prompt)] = prompt
            out = generate(module, params, jnp.asarray(padded),
                           steps=max(batch_budgets))
            materialize(out)

    run_once()                                   # warm/compile
    trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        run_once()
        trials.append(time.perf_counter() - start)
    return sorted(trials)[len(trials) // 2], sum(budgets)


def continuous_arm(module, params, prompts, budgets) -> tuple[float, int, dict]:
    """Median wall seconds through the paged engine + scheduler, plus
    delivered tokens and the engine's per-phase dispatch seconds from
    the LAST trial (fresh counters per trial)."""
    engine = Engine(module, params, rows=ROWS,
                    block_size=16 if ON_TPU else 8)

    def run_once() -> dict:
        engine.timings = {'prefill': 0.0, 'admit': 0.0, 'step': 0.0}
        scheduler = Scheduler(engine)
        for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
            scheduler.submit(Request(f'r{index}', list(prompt), budget))
        results = scheduler.run()
        delivered = sum(len(c.tokens) for c in results.values())
        assert delivered == sum(budgets), (delivered, sum(budgets))
        return dict(engine.timings)

    run_once()                                   # warm/compile
    trials, phases = [], {}
    for _ in range(TRIALS):
        start = time.perf_counter()
        phases = run_once()
        trials.append(time.perf_counter() - start)
    return sorted(trials)[len(trials) // 2], sum(budgets), phases


def shared_recipe():
    """Model + shared-prompt workload: one long system prefix, short
    per-request suffixes. TPU: the BASELINE decode config. CPU: the
    dim-256 preset (same reasoning as :func:`recipe` — dispatch-bound
    tiny models hide the prefill win)."""
    if ON_TPU:
        module = GPT2(dropout=0.0, vocab_size=50304, max_seq=512)
        prefix_len, vocab, max_new = 384, 50257, 16
    else:
        module = gpt2_tiny(dtype='float32', layers=4, dim=256, heads=8,
                           vocab_size=1024, max_seq=256)
        prefix_len, vocab, max_new = 192, 1024, 8
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.integers(0, vocab, (8,))
                               .astype(np.int32)]) for _ in range(8)]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray(prompts[0][None]))['params']
    return module, params, prompts, max_new


def shared_arm(module, params, prompts, max_new,
               share: bool) -> tuple[float, int, float]:
    """Median wall seconds for the shared-prompt workload through the
    scheduler with prefix sharing on or off, plus delivered tokens and
    the engine-lifetime prefix hit rate. ONE engine per arm: the warmup
    run compiles AND (sharing arm) populates the radix tree, so the
    timed trials measure the steady state a long-lived replica serves
    from — every trial's prefix blocks adopted, only suffixes
    prefilled."""
    engine = Engine(module, params, rows=ROWS, block_size=16,
                    share_prefix=share)

    def run_once() -> None:
        scheduler = Scheduler(engine)
        for index, prompt in enumerate(prompts):
            scheduler.submit(Request(f's{index}', list(prompt), max_new))
        results = scheduler.run()
        delivered = sum(len(c.tokens) for c in results.values())
        assert delivered == max_new * len(prompts)

    run_once()                                   # warm/compile + warm tree
    trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        run_once()
        trials.append(time.perf_counter() - start)
    tokens = max_new * len(prompts)
    return (sorted(trials)[len(trials) // 2], tokens,
            engine.prefix_hit_rate() if share else 0.0)


def check_shared_parity(module, params, prompts, max_new) -> None:
    """Every sharing-arm completion must be exactly generate()'s."""
    engine = Engine(module, params, rows=ROWS, block_size=16,
                    share_prefix=True)
    scheduler = Scheduler(engine)
    for index, prompt in enumerate(prompts):
        scheduler.submit(Request(f's{index}', list(prompt), max_new))
    results = scheduler.run()
    for index, prompt in enumerate(prompts):
        ref = generate(module, params, jnp.asarray(prompt)[None],
                       steps=max_new)
        expect = [int(t) for t in np.asarray(ref)[0, len(prompt):]]
        got = list(results[f's{index}'].tokens)
        assert got == expect, (index, got, expect)


def shared_section() -> None:
    module, params, prompts, max_new = shared_recipe()
    check_shared_parity(module, params, prompts, max_new)
    cold_seconds, tokens, _ = shared_arm(module, params, prompts, max_new,
                                         share=False)
    warm_seconds, _, hit_rate = shared_arm(module, params, prompts, max_new,
                                           share=True)
    cold_tok_s = tokens / cold_seconds
    warm_tok_s = tokens / warm_seconds
    workload = (f'{len(prompts)} reqs, shared prefix '
                f'{len(prompts[0]) - 8}, suffix 8, max_new {max_new}, '
                f'rows {ROWS}')
    print(json.dumps({'metric': 'serve_prefix_hit_rate',
                      'value': round(hit_rate, 3),
                      'unit': 'shared/prompt tokens', 'workload': workload}))
    print(json.dumps({
        'metric': 'serve_shared_prefix_speedup',
        'value': round(warm_tok_s / cold_tok_s, 2),
        'unit': 'x delivered tok/s vs no-sharing'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'shared_tok_s': round(warm_tok_s, 1),
        'unshared_tok_s': round(cold_tok_s, 1),
        'workload': workload}))


def sampled_arm(engine, prompts, budgets, sampling) -> tuple[float, int]:
    """Median wall seconds for the workload with ``sampling(index)``
    per request (None entries = greedy), plus delivered tokens. EVERY
    trial runs the workload twice and asserts the two passes bitwise-
    identical — the determinism contract is measured under the clock,
    not assumed (the second pass is outside the timed window)."""

    def run_once() -> dict:
        scheduler = Scheduler(engine)
        for index, (prompt, budget) in enumerate(zip(prompts, budgets)):
            scheduler.submit(Request(f'r{index}', list(prompt), budget,
                                     sampling=sampling(index)))
        return {rid: list(c.tokens) for rid, c in scheduler.run().items()}

    run_once()                                   # warm/compile
    trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        first = run_once()
        trials.append(time.perf_counter() - start)
        again = run_once()                       # same seeds -> same bits
        assert first == again, 'sampled decode was not deterministic'
    return sorted(trials)[len(trials) // 2], sum(budgets)


def sampled_section() -> None:
    """Sampled vs greedy delivered tok/s on the mixed workload — the
    cost of per-row seeded top-k/top-p sampling inside the one compiled
    step (same engine, same trace), with determinism asserted every
    trial. LAST line = ``serve_sampled_tok_s`` (``bench.py`` forwards
    it)."""
    module, params, prompts, budgets = recipe()
    engine = Engine(module, params, rows=ROWS,
                    block_size=16 if ON_TPU else 8)
    greedy_seconds, tokens = sampled_arm(engine, prompts, budgets,
                                         lambda index: None)
    sampled_seconds, _ = sampled_arm(
        engine, prompts, budgets,
        lambda index: SamplingParams(seed=100 + index, temperature=0.9,
                                     top_k=64, top_p=0.95))
    assert engine.trace_count == 1, engine.trace_count
    greedy_tok_s = tokens / greedy_seconds
    sampled_tok_s = tokens / sampled_seconds
    workload = (f'{len(prompts)} reqs, prompts '
                f'{sorted(set(len(p) for p in prompts))}, budgets '
                f'{sorted(set(budgets))}, rows {ROWS}')
    print(json.dumps({
        'metric': 'serve_sampled_tok_s',
        'value': round(sampled_tok_s, 1),
        'unit': f'tok/s delivered, seeded top-k/top-p ({workload})'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'greedy_tok_s': round(greedy_tok_s, 1),
        'sampled_over_greedy': round(sampled_tok_s / greedy_tok_s, 2),
        'determinism': 'asserted bitwise every trial'}))


def main() -> None:
    if 'shared' in sys.argv[1:]:
        shared_section()         # LAST line = serve_shared_prefix_speedup
        return
    if 'sampled' in sys.argv[1:]:
        sampled_section()        # LAST line = serve_sampled_tok_s
        return
    shared_section()
    sampled_section()
    module, params, prompts, budgets = recipe()
    static_seconds, tokens = static_arm(module, params, prompts, budgets)
    continuous_seconds, _, phases = continuous_arm(module, params, prompts,
                                                   budgets)
    static_tok_s = tokens / static_seconds
    continuous_tok_s = tokens / continuous_seconds
    workload = (f'{len(prompts)} reqs, prompts '
                f'{sorted(set(len(p) for p in prompts))}, budgets '
                f'{sorted(set(budgets))}, rows {ROWS}')
    print(json.dumps({'metric': 'serve_static_tok_s',
                      'value': round(static_tok_s, 1), 'unit': 'tok/s',
                      'seconds': round(static_seconds, 3),
                      'workload': workload}))
    for phase, seconds in phases.items():
        print(json.dumps({'metric': f'serve_phase_{phase}_s',
                          'value': round(seconds, 4),
                          'unit': 's (continuous arm, one workload)'}))
    print(json.dumps({
        'metric': 'serve_tok_s',
        'value': round(continuous_tok_s, 1),
        'unit': f'tok/s delivered ({workload})'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'static_tok_s': round(static_tok_s, 1),
        'speedup_vs_static': round(continuous_tok_s / static_tok_s, 2),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: every section prints anyway
