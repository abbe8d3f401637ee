"""Disaggregated prefill/decode vs colocated serving: head-of-line TTFT.

The DistServe claim (``tpusystem/serve/disagg.py``) measured on a mixed
long:short workload — a few LONG prompts whose admission prefill is the
compute-bound phase, interleaved with many SHORT chat-style prompts.
Two fleets of the same replica count:

1. ``colocated`` — every replica serves both phases (``role='both'``):
   each long prefill runs on the same engine loop that co-batched
   decoders are waiting on, so short requests queued behind it eat the
   prefill's latency (head-of-line blocking);
2. ``disagg``   — one prefill-role replica admits every prompt and
   exports KV strips (``Engine.export_prefill``), the router ships them
   digest-verified over the blob plane (``kv:{request}``), and
   decode-role replicas seat them through ``admit_prefilled`` — decode
   steps never wait on a prefill.

Measured per arm: TTFT p50/p99 over the SHORT requests (the
head-of-line tail the split exists to fix), delivered tok/s, and
token-exactness — greedy decode is deterministic, so both arms must
produce identical completions (asserted every trial).

Every row is one machine-readable JSON line (the ``serve_fleet.py``
convention); the LAST line is the ``serve_disagg_ttft_p99`` headline
``bench.py`` forwards (value = disagg p99 short-request TTFT, colocated
alongside). CPU numbers are smoke; the TPU protocol rides the same
script (BASELINE.md "disaggregated serve protocol").

Run: ``python benchmarks/serve_disagg.py [headline]``.
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpusystem.models import GPT2, gpt2_tiny
from tpusystem.parallel.mesh import on_tpu
from tpusystem.parallel.multihost import Loopback
from tpusystem.serve import (Engine, ReplicaHandle, Request, Router,
                             Scheduler, ServingReplica)

TRIALS = 3
REPLICAS = 3                         # 1 prefill + 2 decode when split
ROWS = 2
ON_TPU = on_tpu()


def recipe():
    """Model + a long:short mixed workload: the long prompts are the
    head-of-line hazard (their prefill stalls a colocated engine loop),
    the short ones are the requests whose TTFT tail we report."""
    if ON_TPU:
        module = GPT2(dropout=0.0, vocab_size=50304, max_seq=1024)
        vocab, long_len, short_len = 50257, 384, 24
        longs, shorts, budget = 3, 12, 24
    else:
        module = gpt2_tiny(dtype='float32', layers=4, dim=256, heads=8,
                           vocab_size=1024, max_seq=256)
        vocab, long_len, short_len = 1024, 96, 8
        longs, shorts, budget = 2, 8, 10
    rng = np.random.default_rng(0)
    requests = []                    # (id, prompt, budget, is_short)
    for index in range(longs + shorts):
        short = index % (1 + shorts // max(longs, 1)) != 0 \
            if longs else True
        length = short_len if short else long_len
        prompt = rng.integers(0, vocab, (length,)).astype(np.int32).tolist()
        requests.append((f'r{index}', prompt, budget, short))
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray([requests[0][1][:8]],
                                     jnp.int32))['params']
    return module, params, requests


def build_fleet(module, params, *, split):
    """Same replica count both arms: ``split`` carves one replica into
    the prefill tier (its strips travel the Loopback blob plane), the
    colocated arm keeps every replica ``role='both'``."""
    wire = Loopback() if split else None
    handles = []
    for index in range(REPLICAS):
        role = ('prefill' if index == 0 else 'decode') if split else 'both'

        def build(role=role):
            return Scheduler(
                Engine(module, params, rows=ROWS,
                       block_size=16 if ON_TPU else 8),
                prefill_only=(role == 'prefill'))
        handles.append(ReplicaHandle(
            ServingReplica(build, identity=f'rep{index}', role=role),
            transport=wire, rank=0))
    return Router(handles), handles


def trial(module, params, requests, *, split, reference=None):
    """One drained run; returns (results, short TTFTs, elapsed).
    TTFT = submit -> the request's first emitted token crossing a
    FleetTick, the latency a caller actually observes."""
    router, _ = build_fleet(module, params, split=split)
    submitted, firsts = {}, {}
    started = time.perf_counter()
    for rid, prompt, budget, _short in requests:
        submitted[rid] = time.perf_counter()
        router.submit(Request(rid, list(prompt), budget))
    for _ in range(100_000):
        if router.idle:
            break
        tick = router.step()
        now = time.perf_counter()
        for rid in tick.emitted:
            firsts.setdefault(rid, now - submitted[rid])
    elapsed = time.perf_counter() - started
    assert router.idle, 'fleet never drained'
    if reference is not None:
        for rid, completion in router.results.items():
            expected = reference[rid].tokens
            assert completion.tokens == expected, (
                f'{rid} diverged across the disaggregation split: '
                f'{completion.tokens} vs {expected}')
    ttfts = [firsts[rid] for rid, _p, _b, short in requests if short]
    return router.results, sorted(ttfts), elapsed


def percentile(sorted_values, q):
    index = min(len(sorted_values) - 1,
                int(round(q * (len(sorted_values) - 1))))
    return sorted_values[index]


def main() -> None:
    module, params, requests = recipe()
    tokens_out = sum(budget for _rid, _p, budget, _s in requests)
    workload = (f'{len(requests)} reqs '
                f'({sum(1 for r in requests if not r[3])} long / '
                f'{sum(1 for r in requests if r[3])} short) over '
                f'{REPLICAS} replicas')

    colo_p99s, colo_p50s, colo_toks = [], [], []
    disagg_p99s, disagg_p50s, disagg_toks = [], [], []
    reference = None
    for _ in range(TRIALS):
        results, ttfts, elapsed = trial(module, params, requests,
                                        split=False, reference=reference)
        reference = reference or results
        colo_p50s.append(percentile(ttfts, 0.50))
        colo_p99s.append(percentile(ttfts, 0.99))
        colo_toks.append(tokens_out / elapsed)
        _results, ttfts, elapsed = trial(module, params, requests,
                                         split=True, reference=reference)
        disagg_p50s.append(percentile(ttfts, 0.50))
        disagg_p99s.append(percentile(ttfts, 0.99))
        disagg_toks.append(tokens_out / elapsed)

    median = lambda values: sorted(values)[len(values) // 2]
    print(json.dumps({
        'metric': 'serve_colocated_ttft_p99',
        'value': round(median(colo_p99s), 4),
        'unit': 's submit -> first token, short requests (colocated: '
                'long prefills share the decode loop)',
        'p50': round(median(colo_p50s), 4),
        'tok_s': round(median(colo_toks), 2)}))
    print(json.dumps({
        'metric': 'serve_disagg_ttft_p99',
        'value': round(median(disagg_p99s), 4),
        'unit': f's submit -> first token, short requests ({workload}; '
                'prefill tier + KV handoff over the blob plane, '
                'token-exact vs colocated)'
                + ('' if ON_TPU else ' [CPU smoke]'),
        'p50': round(median(disagg_p50s), 4),
        'tok_s': round(median(disagg_toks), 2),
        'colocated_p99': round(median(colo_p99s), 4),
        'colocated_p50': round(median(colo_p50s), 4),
        'colocated_tok_s': round(median(colo_toks), 2),
    }))


if __name__ == '__main__':
    main()        # 'headline' arg tolerated: every section prints anyway
