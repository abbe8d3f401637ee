"""Whole-model 8B rehearsal: a MEASURED step, not a component composite.

Replaces the round-3 methodology for BASELINE row 5 (one block + head timed
separately, composite modeled over 32 layers) with two whole-model runs:

* ``chip`` (default): the deepest Llama-8B-dim stack that fits one 16 GB
  chip — dim 4096, GQA 32/8, SwiGLU 14336, seq 8192, remat + flash +
  fused chunked loss + AdamW — fwd+bwd+update timed end-to-end over
  repeated dispatches (no steps-loop around the scanned stack; see
  scan_compile_probe.py).
  The vocab shrinks to 16384 (x128) so the untied head + embedding fit
  next to the blocks (32768 overflows HBM by ~100 MB at 4 layers); FLOPs
  are counted from the actual parameter count, so MFU is honest for the
  measured program.

* ``virtual``: the full composition rehearsal on an 8-device CPU mesh —
  scan+TP+FSDP+flash at dim 4096, >=8 layers — recording AOT compile
  time and the per-layer collective count from the optimized HLO (the
  number that predicts ICI time on a pod).

Run: ``python benchmarks/llama8b_rehearsal.py [chip|virtual] [layers=N]``
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np


def build(layers: int, vocab: int, mesh=None, scan: bool = False,
          attention: str = 'flash', ffn: int = 14336):
    from tpusystem.models import Llama
    return Llama(vocab_size=vocab, layers=layers, dim=4096, heads=32,
                 kv_heads=8, ffn_dim=ffn, max_seq=8192,
                 attention=attention, mesh=mesh, remat=True,
                 scan_layers=scan, scan_unit=4 if scan and layers % 4 == 0
                 else 1, return_features=True)


def chip(layers: int, scan: bool = False) -> None:
    from bench import peak_flops
    from tpusystem.train import (AdamW, ChunkedNextTokenLoss,
                                 build_train_step, flax_apply, init_state)

    batch, seq, vocab = 1, 8192, 16384  # 32768 exceeds the
    # 16 GB chip by ~100 MB next to 4 blocks; FLOPs count actual params
    module = build(layers, vocab, scan=scan)
    optimizer = AdamW(lr=3e-4, grad_clip=1.0)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, seq)), jnp.int32)
    state = init_state(module, optimizer, tokens[:1, :8])
    params = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    step = build_train_step(flax_apply(module),
                            ChunkedNextTokenLoss(chunks=8, tied=False),
                            optimizer)

    t0 = time.perf_counter()
    state, (_, loss) = step(state, tokens, tokens)
    float(loss)
    compile_s = time.perf_counter() - t0

    repeats = 10
    t0 = time.perf_counter()
    for _ in range(repeats):
        state, (_, loss) = step(state, tokens, tokens)
    float(loss)
    elapsed = (time.perf_counter() - t0) / repeats

    head_dim = 4096 // 32
    attention_flops = 12 * layers * 32 * seq * seq * head_dim * batch
    step_flops = 6 * params * batch * seq + attention_flops
    mfu = step_flops / elapsed / peak_flops(jax.devices()[0])
    print(json.dumps({
        'mode': 'chip', 'layers': layers, 'scan': scan, 'params': params,
        'seq': seq, 'compile_s': round(compile_s, 1),
        'ms_per_step': round(elapsed * 1e3, 1), 'mfu': round(mfu, 4),
        'tok_per_s': round(batch * seq / elapsed),
    }))


def virtual(layers: int, ffn: int = 14336, execute: bool = True) -> None:
    import os
    os.environ.setdefault('XLA_FLAGS',
                          '--xla_force_host_platform_device_count=8')
    jax.config.update('jax_platforms', 'cpu')
    # O0 like the driver dryrun: the default pipeline's large fused thunks
    # starve XLA:CPU's shared-pool collective rendezvous (40 s timeout) at
    # these matmul sizes; the sharding/collective structure is unchanged
    jax.config.update('jax_optimization_level', 'O0')
    from tpusystem.parallel import MeshSpec, TensorParallel, batch_sharding
    from tpusystem.train import (ChunkedNextTokenLoss, SGD, build_train_step,
                                 flax_apply, init_state)

    # seq kept small: XLA:CPU runs all 8 virtual devices on one shared
    # thread pool, and matmuls much larger than this starve collective
    # participants past the backend's fixed 40 s rendezvous timeout
    # (rendezvous.cc termination) — the sharding/collective structure
    # being validated is seq-independent
    batch, seq, vocab = 4, 128, 16384
    mesh = MeshSpec(data=2, fsdp=2, model=2).build(jax.devices('cpu')[:8])
    module = build(layers, vocab, mesh=mesh, scan=True, ffn=ffn)
    # SGD + bf16 params: the rehearsal validates sharding/collectives and
    # compile time at real dims on host memory, not optimizer math
    optimizer = SGD(lr=1e-3)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, (batch, seq)), jnp.int32)
    print('phase: init_state', flush=True)
    t0 = time.perf_counter()
    # eval_shape + zeros instead of init_state: actually sampling 1.75B
    # params eagerly on the CPU backend takes >15 minutes; the rehearsal
    # validates the compiled program's sharding/collective structure,
    # which is value-independent (zero weights still give a finite
    # log-uniform loss and execute every collective)
    from tpusystem.train.state import TrainState
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                            tokens[:1, :8])
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.bfloat16),
                         shapes['params'])
    transform = optimizer.transform()
    state = TrainState.create(zeros, transform.init(zeros),
                              jax.random.PRNGKey(1))
    params = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    print('phase: place', flush=True)
    state = TensorParallel(module.partition_rules(), fsdp=True).place(
        state, mesh)
    init_s = time.perf_counter() - t0
    placed = jax.device_put(tokens, batch_sharding(mesh))
    step = build_train_step(flax_apply(module),
                            ChunkedNextTokenLoss(chunks=4, tied=False),
                            optimizer, jit=False)

    jitted = jax.jit(step, donate_argnums=0)
    print('phase: lower', flush=True)
    t0 = time.perf_counter()
    lowered = jitted.lower(state, placed, placed)
    lower_s = time.perf_counter() - t0
    print('phase: compile', flush=True)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    hlo = compiled.as_text()
    collectives = {
        kind: len(re.findall(rf'\b{kind}[-.\w]*\(', hlo))
        for kind in ('all-reduce', 'all-gather', 'reduce-scatter',
                     'all-to-all', 'collective-permute')}
    print(json.dumps({
        'mode': 'virtual', 'layers': layers, 'ffn': ffn, 'params': params,
        'mesh': {'data': 2, 'fsdp': 2, 'model': 2},
        'init_s': round(init_s, 1), 'lower_s': round(lower_s, 1),
        'compile_s': round(compile_s, 1),
        'collectives_total': collectives,
        'collectives_per_layer': {k: round(v / layers, 2)
                                  for k, v in collectives.items()},
    }), flush=True)
    if not execute:
        # full-ffn leg records the compile + collective structure only:
        # XLA:CPU's in-process collectives carry a hard 40 s rendezvous
        # timeout that GB-scale per-device matmul work overruns (the
        # collective COUNT — the pod-relevant number — is ffn-independent)
        return
    t0 = time.perf_counter()
    state, (_, loss) = compiled(state, placed, placed)
    loss = float(loss)
    exec_s = time.perf_counter() - t0
    assert np.isfinite(loss), loss
    print(json.dumps({'mode': 'virtual-exec', 'ffn': ffn,
                      'exec_s': round(exec_s, 1), 'loss': round(loss, 4)}))


if __name__ == '__main__':
    layers = next((int(a.split('=')[1]) for a in sys.argv[1:]
                   if a.startswith('layers=')), None)
    if 'virtual' in sys.argv[1:]:
        # leg 1: full 8B ffn — compile + per-layer collective count;
        # leg 2: ffn shrunk 14336 -> 4096 — same collective structure,
        # light enough for XLA:CPU to execute inside its rendezvous window
        virtual(layers or 8, execute=False)
        virtual(layers or 8, ffn=4096)
    else:
        chip(layers or 4, scan='scan' in sys.argv[1:])
