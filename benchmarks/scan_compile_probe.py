"""Probe: where does the scan+Pallas compile time go?

The headline bench keeps the unrolled stack. This probe times ``lower()``
and ``compile()`` separately for one composition so the slow axis (scan,
flash kernel, remat, steps-loop) can be bisected.

Run (one composition per process — a hung compile shouldn't block the
rest): ``python benchmarks/scan_compile_probe.py [scan] [flash] [remat]
[loop] [layers=N]``
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def main(argv: list[str]) -> None:
    from tpusystem.models import GPT2
    from tpusystem.train import (AdamW, ChunkedNextTokenLoss,
                                 build_train_step, flax_apply, init_state)

    scan = 'scan' in argv
    flash = 'flash' in argv
    remat = 'remat' in argv
    loop = 'loop' in argv           # steps-loop like bench.py
    layers = next((int(a.split('=')[1]) for a in argv
                   if a.startswith('layers=')), 12)
    steps = next((int(a.split('=')[1]) for a in argv
                  if a.startswith('steps=')), 90)
    outer = next((a.split('=')[1] for a in argv
                  if a.startswith('outer=')), 'fori')
    unit = next((int(a.split('=')[1]) for a in argv
                 if a.startswith('unit=')), 1)

    module = GPT2(dropout=0.0, vocab_size=50304, return_features=True,
                  layers=layers, scan_layers=scan, scan_unit=unit,
                  attention='flash' if flash else 'xla', remat=remat)
    optimizer = AdamW(lr=3e-4, grad_clip=1.0)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (16, 1024)), jnp.int32)
    state = init_state(module, optimizer, tokens[:1, :8])
    step = build_train_step(flax_apply(module),
                            ChunkedNextTokenLoss(chunks=8), optimizer,
                            jit=False)

    if loop and outer == 'scan':
        @partial(jax.jit, donate_argnums=0)
        def target(state, tokens):
            final, _ = jax.lax.scan(
                lambda st, _: (step(st, tokens, tokens)[0], None),
                state, None, length=steps)
            return final
    elif loop:
        @partial(jax.jit, donate_argnums=0)
        def target(state, tokens):
            return jax.lax.fori_loop(
                0, steps, lambda i, st: step(st, tokens, tokens)[0], state)
    else:
        target = jax.jit(step, donate_argnums=0)

    t0 = time.perf_counter()
    lowered = target.lower(state, tokens, tokens) if not loop \
        else target.lower(state, tokens)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    del compiled
    print(f'scan={scan} flash={flash} remat={remat} loop={loop} '
          f'steps={steps} outer={outer} layers={layers} unit={unit}: '
          f'lower {t1 - t0:7.1f}s  compile {t2 - t1:7.1f}s')


if __name__ == '__main__':
    main(sys.argv[1:])
