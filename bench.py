"""Headline benchmark: GPT-2 125M training MFU on one chip.

Runs the ``benchmarks/`` scripts in :data:`CHILD_ROWS` first, each in its
own process while this one stays off JAX (a chip belongs to one process
at a time), then the in-process rows, then the headline as the LAST JSON
line: ``{"metric": ..., "value": N, "spread": N, "unit": ...,
"vs_baseline": N}``. Every row carries the run manifest. A row that
fails, fails the run; a machine without a known accelerator fails it too.

``value`` is the **median of TRIALS (>= 3) timed runs** after a shared
warmup/compile, and ``spread`` is the max-min range across those runs.

``vs_baseline`` is measured MFU against the north-star target of 0.50 MFU
(BASELINE.json). Model FLOPs use the standard 6*N*T approximation
(fwd+bwd) plus exact attention term 12*L*H*S^2*D_head*B.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

TRIALS = 3   # timed runs per report (median printed, max-min as spread)

_MANIFEST: dict | None = None


def run_manifest() -> dict:
    """The environment stamp every JSON row carries: a value moved
    because the code moved, or because jax/jaxlib/the backend did — the
    manifest says which. Touches the backend, so the first call must come
    after every child row has exited."""
    global _MANIFEST
    if _MANIFEST is None:
        try:
            import jaxlib
            jaxlib_version = getattr(jaxlib, '__version__', None)
        except ImportError:
            jaxlib_version = None
        _MANIFEST = {
            'jax': jax.__version__,
            'jaxlib': jaxlib_version,
            'backend': jax.default_backend(),
            'device_count': jax.device_count(),
            'host_count': jax.process_count(),
        }
    return _MANIFEST


def emit(row: dict) -> None:
    """Print one benchmark row as a JSON line, stamped with the run
    manifest."""
    print(json.dumps({**row, 'manifest': run_manifest()}))


# bf16 peak FLOP/s per chip by device kind substring
PEAKS = {
    'v5 lite': 197e12,  # v5e
    'v5e': 197e12,
    'v5p': 459e12,
    'v4': 275e12,
    'v6': 918e12,
}


def materialize(tree) -> None:
    """Force completion with a host read of one scalar — the fence every
    benchmark in this repo times with."""
    leaf = jax.tree.leaves(tree)[0]
    float(jnp.sum(leaf.astype(jnp.float32)))


def peak_flops(device) -> float:
    kind = device.device_kind.lower()
    for key, value in PEAKS.items():
        if key in kind:
            return value
    raise ValueError(f'no peak FLOP/s on record for device kind '
                     f'{device.device_kind!r}; add it to PEAKS')


def require_chips(count: int):
    """The accelerator devices of this process; exits non-zero when there
    are fewer than ``count`` (a multi-chip row never falls back to a
    virtual CPU mesh)."""
    devices = jax.devices()
    if devices[0].platform == 'cpu' or len(devices) < count:
        sys.exit(f'{sys.argv[0]} needs {count} accelerator chips, found '
                 f'{len(devices)} x {devices[0].platform}')
    return devices


# (script under benchmarks/, argument): each prints its row as its last
# JSON line
CHILD_ROWS = (
    ('tp_overlap.py', 'headline'),        # tp_ffn_overlap_speedup_vs_gspmd
    ('fsdp_overlap.py', 'headline'),      # fsdp_overlap_speedup_vs_gspmd
    ('pp_overlap.py', 'headline'),        # pp_overlap_speedup_vs_gspmd
    ('moe_a2a_overlap.py', 'headline'),   # moe_a2a_overlap_speedup
    ('elastic_resize.py', 'headline'),    # resize_seconds
    ('serve_bench.py', 'headline'),       # serve_tok_s
    ('serve_bench.py', 'shared'),         # serve_shared_prefix_speedup
    ('serve_bench.py', 'sampled'),        # serve_sampled_tok_s
    ('serve_recovery.py', 'headline'),    # serve_recovery_seconds
    ('serve_fleet.py', 'headline'),       # fleet_recovery_seconds
    ('serve_failover.py', 'headline'),    # router_failover_seconds
    ('arbitration.py', 'headline'),       # arbitration_seconds
    ('serve_disagg.py', 'headline'),      # serve_disagg_ttft_p99
    ('embedding_bench.py', 'headline'),   # embedding_lookup_speedup
)


def child_row(script_name: str, arg: str) -> dict:
    """``benchmarks/<script> <arg>`` in a subprocess; returns the row it
    printed last. The caller must not have touched JAX yet: the child
    needs the chip. A child that fails, fails this run."""
    script = pathlib.Path(__file__).parent / 'benchmarks' / script_name
    probe = subprocess.run([sys.executable, str(script), arg],
                           capture_output=True, text=True, timeout=1800)
    lines = [line for line in probe.stdout.strip().splitlines()
             if line.startswith('{')]
    if probe.returncode != 0 or not lines:
        raise RuntimeError(
            f'{script_name} {arg} exited {probe.returncode}: '
            f'{probe.stderr.strip()[-400:] or "no output"}')
    return json.loads(lines[-1])


def serve_ttft_row() -> None:
    """Print the serving TTFT percentile row: p50/p95/p99 submit→first-
    token over a staggered mixed-length workload on the tiny engine,
    measured through the mergeable log-bucketed histogram
    (``tpusystem.observe.metrics.Histogram`` — the same aggregation the
    fleet dashboard charts). Percentiles, not means: tail latency is the
    serving claim, and a mean TTFT hides exactly the overload the
    watermark/brownout machinery exists for. Printed BEFORE the MFU
    headline."""
    from tpusystem.models import gpt2_tiny
    from tpusystem.observe.metrics import Histogram
    from tpusystem.serve import Engine, Request, Scheduler

    module = gpt2_tiny(dtype='float32')
    rng = np.random.default_rng(3)
    lengths = (5, 9, 7, 4, 11, 6, 8, 5, 10, 7, 6, 9)
    budgets = (8, 6, 10, 5, 7, 9, 6, 10, 7, 8, 5, 6)
    prompts = [rng.integers(0, 256, (n,)).tolist() for n in lengths]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray([prompts[0]], jnp.int32))['params']
    engine = Engine(module, params, rows=4, block_size=8)
    pending = list(zip(prompts, budgets))

    def run_workload() -> Histogram:
        scheduler = Scheduler(engine)
        ttft = Histogram()
        index = 0
        for step in range(10_000):
            # staggered arrivals: a new burst every other tick, so
            # later requests genuinely queue behind seated rows
            if step % 2 == 0 and index < len(pending):
                for prompt, budget in pending[index:index + 2]:
                    scheduler.submit(Request(f'r{index}', prompt,
                                             budget))
                    index += 1
            tick = scheduler.step()
            for _request, _admission, seconds in tick.admitted:
                ttft.add(seconds)
            if index >= len(pending) and scheduler.idle:
                break
        return ttft

    run_workload()    # warm every prefill bucket + the decode step:
    # without this, p99 charts one-time XLA compiles, not queueing
    ttft = run_workload()
    summary = ttft.summary()
    emit({
        'metric': 'serve_ttft_p50_p99',
        'value': round(summary['p50'], 4),
        'unit': 's (tiny engine, staggered mixed workload, p50)',
        'p95': round(summary['p95'], 4),
        'p99': round(summary['p99'], 4),
        'count': summary['count'],
    })


def trace_overhead_row() -> None:
    """Print the tracer's serving-path cost: scheduler steps/s with a
    live ``observe.Tracer`` attached vs the default ``tracer=None``, the
    ``sentinel_overhead`` protocol (median of TRIALS per arm). The
    acceptance budget is < 0.02 for the DISABLED tracer — which shares
    the off arm's code path exactly (one ``is not None`` test per hook),
    so the printed value bounds it from above: even tracing ENABLED must
    stay cheap, because spans record only at lifecycle edges, never per
    token. Printed BEFORE the MFU headline."""
    from tpusystem.models import gpt2_tiny
    from tpusystem.observe import Tracer
    from tpusystem.serve import Engine, Request, Scheduler

    module = gpt2_tiny(dtype='float32')
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, (n,)).tolist() for n in (6, 8, 5, 7)]
    params = module.init(jax.random.PRNGKey(0),
                         jnp.asarray([prompts[0]], jnp.int32))['params']
    engine = Engine(module, params, rows=4, block_size=8)

    def run_once(tracer) -> float:
        scheduler = Scheduler(engine, tracer=tracer)
        for index, prompt in enumerate(prompts):
            scheduler.submit(Request(f'r{index}', prompt, 48))
        start = time.perf_counter()
        scheduler.run()
        return scheduler.steps / (time.perf_counter() - start)

    run_once(None)               # warm the decode/prefill compiles
    # interleave the arms (off, on, off, on, ...) so machine-load
    # drift lands on both equally; report the median paired rates
    pairs = [(run_once(None), run_once(Tracer('bench')))
             for _ in range(max(TRIALS, 5))]
    ratios = sorted(on / off for off, on in pairs)
    middle = ratios[len(ratios) // 2]
    off = sorted(off for off, _ in pairs)[len(pairs) // 2]
    on = off * middle
    emit({
        'metric': 'trace_overhead',
        'value': round(1.0 - on / off, 4),
        'unit': 'fraction of serve steps/s (tracer on vs off)',
        'tracer_on_steps_per_sec': round(on, 2),
        'tracer_off_steps_per_sec': round(off, 2),
    })


BATCH, SEQ = 16, 1024


def bench_recipe():
    """The headline 125M recipe, shared by every row that measures it.

    Perf recipe (each measured on a v5e chip):
    - vocab padded 50257 -> 50304 (x128): the unpadded table mis-tiles the
      MXU on the head matmul (~10% whole-step MFU);
    - Pallas flash attention for the single-chip run (1024/1024 tiles);
    - fused chunked LM loss (return_features): the [B*S, vocab] f32 logits
      tensor is never materialized (~5% MFU, and unlocks batch >= 32);
    - many steps per jit call (lax.fori_loop), so host dispatch and the
      final host sync are paid once per loop. The flash kernels stay
      seedless at dropout=0 (the in-kernel dropout path wires its seed
      input only when active).
    """
    from tpusystem.models import GPT2
    from tpusystem.train import AdamW

    module = GPT2(dropout=0.0, attention='flash', vocab_size=50304,
                  return_features=True)
    optimizer = AdamW(lr=3e-4, grad_clip=1.0)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (BATCH, SEQ)),
        jnp.int32)
    return module, optimizer, tokens


def looped_runner(step, steps: int):
    """``steps`` train steps per dispatch, state donated in place in HBM."""
    @partial(jax.jit, donate_argnums=0)
    def run(state, tokens):
        return jax.lax.fori_loop(
            0, steps, lambda i, st: step(st, tokens, tokens)[0], state)
    return run


def timed_trials(run, state, tokens):
    """Shared timing protocol: one warmup/compile dispatch, then TRIALS
    timed runs — completion forced by :func:`materialize` every time.
    Returns ``(state, elapsed_trials)``; report the median and the
    max-min spread so a delta can be told from run-to-run noise."""
    state = run(state, tokens)
    materialize(state.params)
    elapsed_trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        state = run(state, tokens)
        materialize(state.params)
        elapsed_trials.append(time.perf_counter() - start)
    return state, elapsed_trials


def sentinel_overhead_row() -> None:
    """Print the in-graph guard's cost: steps/s with ``guard=`` on vs off
    on the bench model (same 125M recipe and timing protocol as the
    headline, fewer steps per arm), as ``{"metric": "sentinel_overhead",
    "value": <fractional slowdown>}`` — the acceptance budget is < 0.02
    (2%). Printed BEFORE the MFU headline so the driver's parsed last-line
    metric is unchanged."""
    from tpusystem.train import (ChunkedNextTokenLoss, Guard,
                                 build_train_step, flax_apply, init_state)

    steps = 12
    module, optimizer, tokens = bench_recipe()
    guard = Guard()

    def arm_rate(guarded: bool) -> float:
        step = build_train_step(
            flax_apply(module), ChunkedNextTokenLoss(chunks=8), optimizer,
            jit=False, guard=guard if guarded else None)
        state = init_state(module, optimizer, tokens[:1, :8])
        if guarded:
            state = guard.arm(state)
        _, elapsed = timed_trials(looped_runner(step, steps), state,
                                  tokens)
        return steps / sorted(elapsed)[len(elapsed) // 2]

    off, on = arm_rate(False), arm_rate(True)
    emit({
        'metric': 'sentinel_overhead',
        'value': round(1.0 - on / off, 4),
        'unit': 'fraction of steps/s',
        'guard_on_steps_per_sec': round(on, 4),
        'guard_off_steps_per_sec': round(off, 4),
    })


def recovery_seconds_row() -> None:
    """Print the hot-vs-disk restore cost on the tiny model: wall seconds
    to materialize a resumable ``TrainState`` from the supervisor's
    in-memory store (``hot_resume`` via a local ``MemStore``) vs from the
    newest committed Orbax checkpoint — the per-recovery saving the
    Supervisor's memstore tier buys (``value`` is the hot time; both
    medians of TRIALS). Printed BEFORE the MFU headline."""
    import tempfile
    import jax.numpy as jnp

    from tpusystem.checkpoint import (Checkpointer, MemStore, hot_resume,
                                      serialize_state)
    from tpusystem.models import gpt2_tiny
    from tpusystem.train import (AdamW, NextTokenLoss, build_train_step,
                                 flax_apply, init_state)

    module = gpt2_tiny()
    optimizer = AdamW(lr=1e-3)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (4, 32)), jnp.int32)
    state = init_state(module, optimizer, tokens[:1])
    step = build_train_step(flax_apply(module), NextTokenLoss(),
                            optimizer)
    state, _ = step(state, tokens, tokens)
    identity = 'bench-recovery'
    with tempfile.TemporaryDirectory() as root, \
            Checkpointer(root, async_save=False) as checkpointer:
        checkpointer.save(identity, 1, state, extras={'step': 1})
        store = MemStore()
        store.put(identity, 1, serialize_state(state),
                  extras={'step': 1})

        def timed(client):
            times = []
            for _ in range(TRIALS):
                start = time.perf_counter()
                restored, _, _, source = hot_resume(
                    checkpointer, identity, state, client)
                materialize(restored.params)
                times.append(time.perf_counter() - start)
            return source, sorted(times)[len(times) // 2]

        hot_source, hot = timed(store)
        disk_source, disk = timed(None)
    assert (hot_source, disk_source) == ('hot', 'disk')
    emit({
        'metric': 'recovery_seconds',
        'value': round(hot, 4),
        'unit': 's (hot restore, tiny model)',
        'disk_seconds': round(disk, 4),
        'hot_speedup_vs_disk': round(disk / hot, 2) if hot else None,
    })


def decode_rows() -> None:
    """Print the serving-path decode rows: ``decode_tok_s`` (greedy
    generate at GPT-2 125M, batch 8,
    prefill 128, decode 128, ``stream_dtype='auto'``) and
    ``decode_stream_bytes`` (per-step streamed weight bytes of that
    tree, with the int8-quantized tree's bytes alongside — the
    roofline lever, ``benchmarks/decode_roofline.py``). Printed BEFORE
    the MFU headline so the driver's parsed last-line metric is
    unchanged."""
    from tpusystem.models import GPT2
    from tpusystem.train.generate import generate, streamed_bytes

    batch, prefill, decode = 8, 128, 128
    module = GPT2(dropout=0.0, vocab_size=50304, max_seq=512)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (batch, prefill)),
        jnp.int32)
    params = module.init(jax.random.PRNGKey(0),
                         prompt[:1, :8])['params']

    out = generate(module, params, prompt, steps=decode)   # warm/compile
    materialize(out)
    elapsed_trials = []
    for _ in range(TRIALS):
        start = time.perf_counter()
        out = generate(module, params, prompt, steps=decode)
        materialize(out)
        elapsed_trials.append(time.perf_counter() - start)
    elapsed = sorted(elapsed_trials)[len(elapsed_trials) // 2]
    to_tok = lambda secs: batch * decode / secs
    emit({
        'metric': 'decode_tok_s',
        'value': round(to_tok(elapsed)),
        'spread': round(to_tok(min(elapsed_trials))
                        - to_tok(max(elapsed_trials))),
        'unit': 'tok/s (125M, batch 8, prefill 128, decode 128)',
    })
    auto_bytes = streamed_bytes(module, params, 'auto')
    int8_bytes = streamed_bytes(module, params, 'int8')
    emit({
        'metric': 'decode_stream_bytes',
        'value': auto_bytes,
        'unit': 'bytes/step (streamed param tree, stream_dtype=auto)',
        'int8_bytes': int8_bytes,
        'int8_reduction': round(auto_bytes / int8_bytes, 2),
    })


def main() -> None:
    from tpusystem.train import (ChunkedNextTokenLoss, build_train_step,
                                 flax_apply, init_state)

    peak = peak_flops(jax.devices()[0])   # an unknown device fails here
    batch, seq = BATCH, SEQ
    module, optimizer, tokens = bench_recipe()
    state = init_state(module, optimizer, tokens[:1, :8])
    params_count = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    step = build_train_step(flax_apply(module), ChunkedNextTokenLoss(chunks=8),
                            optimizer, jit=False)

    steps = 90
    state, elapsed_trials = timed_trials(looped_runner(step, steps), state,
                                         tokens)
    elapsed = sorted(elapsed_trials)[len(elapsed_trials) // 2]

    tokens_per_step = batch * seq
    head_dim = module.dim // module.heads
    # 12*L*H*S^2*Dh*B covers fwd (4*S^2*Dh per head: QK^T + AV at 2 FLOPs/MAC)
    # plus bwd at 2x fwd
    attention_flops = 12 * module.layers * module.heads * seq * seq * head_dim * batch
    step_flops = 6 * params_count * tokens_per_step + attention_flops

    to_mfu = lambda secs: step_flops * steps / secs / peak
    mfu = to_mfu(elapsed)
    emit({
        'metric': 'gpt2_125m_train_mfu_1chip',
        'value': round(mfu, 4),
        'spread': round(to_mfu(min(elapsed_trials))
                        - to_mfu(max(elapsed_trials)), 4),
        'unit': 'MFU',
        'vs_baseline': round(mfu / 0.5, 4),
    })


if __name__ == '__main__':
    from tpusystem.runtime import compile_cache
    compile_cache()      # exported, so the child rows share the cache
    rows = [child_row(script, arg) for script, arg in CHILD_ROWS]
    for row in rows:     # stamped only now: the manifest touches the chip
        emit(row)
    sentinel_overhead_row()
    recovery_seconds_row()
    decode_rows()
    serve_ttft_row()
    trace_overhead_row()
    main()
