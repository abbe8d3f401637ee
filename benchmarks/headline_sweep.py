"""Headline-recipe sweep: GPT-2 125M train MFU variants on one chip.

Same methodology as bench.py (donated fori_loop, materialized completion);
each variant prints one JSON line. Used to pick the recipe bench.py pins.
"""
import sys, time, json, pathlib
sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))
from functools import partial

import jax, jax.numpy as jnp, numpy as np

from bench import peak_flops
from tpusystem.models import GPT2
from tpusystem.train import (AdamW, ChunkedNextTokenLoss, build_train_step,
                             flax_apply, init_state)


def variant(tag, batch=16, seq=1024, chunks=8, steps=60, **model_overrides):
    """One timed recipe; prints MFU + ms/step (+ tok/s for long context)."""
    config = dict(dropout=0.0, attention='flash', vocab_size=50304,
                  return_features=True)
    config.update(model_overrides)
    module = GPT2(**config)
    optimizer = AdamW(lr=3e-4, grad_clip=1.0)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 50257, (batch, seq)), jnp.int32)
    state = init_state(module, optimizer, tokens[:1, :8])
    params_count = sum(leaf.size for leaf in jax.tree.leaves(state.params))
    step = build_train_step(flax_apply(module),
                            ChunkedNextTokenLoss(chunks=chunks),
                            optimizer, jit=False)

    @partial(jax.jit, donate_argnums=0)
    def run(state, tokens):
        return jax.lax.fori_loop(
            0, steps, lambda i, st: step(st, tokens, tokens)[0], state)

    state = run(state, tokens)
    float(jax.tree.leaves(state.params)[0].sum())
    start = time.perf_counter()
    state = run(state, tokens)
    float(jax.tree.leaves(state.params)[0].sum())
    elapsed = time.perf_counter() - start

    head_dim = module.dim // module.heads
    attention_flops = (12 * module.layers * module.heads * seq * seq
                       * head_dim * batch)
    step_flops = 6 * params_count * batch * seq + attention_flops
    mfu = step_flops * steps / elapsed / peak_flops(jax.devices()[0])
    print(json.dumps({'variant': tag, 'mfu': round(mfu, 4),
                      'ms_per_step': round(elapsed / steps * 1e3, 1),
                      'tok_per_s': round(batch * seq * steps / elapsed)}))
    return mfu


def safe(tag, **kw):
    try:
        variant(tag, **kw)
    except Exception as error:
        print(json.dumps({'variant': tag, 'error': str(error)[:120]}))


def flash_bwd(batch: int, seq: int, backward: str) -> float:
    """Seconds per fwd+bwd of a flash-attention loss with the given
    backward impl — the retired ``flash_backward_ab.py`` A/B, kept as a
    section here now that the fused single-pass backward is the default
    with working-set auto-routing (`ops/pallas/flash.py`)."""
    from tpusystem.ops.pallas.flash import flash_attention

    heads, head_dim, repeats = 12, 64, 20
    rng = np.random.default_rng(0)
    shape = (batch, seq, heads, head_dim)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, backward=backward)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def run(q, k, v):
        def body(i, carry):
            dq, dk, dv = grad(q + carry[0] * 0, k, v)  # defeat hoisting
            return dq, dk, dv
        return jax.lax.fori_loop(0, repeats, body, (q, k, v))

    out = run(q, k, v)
    float(out[0].astype(jnp.float32).sum())  # force completion
    start = time.perf_counter()
    out = run(q, k, v)
    float(out[0].astype(jnp.float32).sum())
    return (time.perf_counter() - start) / repeats


def flash_bwd_section():
    """Split-vs-fused flash backward at the headline + long-context
    shapes; one JSON line per shape."""
    for batch, seq in [(16, 1024), (4, 4096), (2, 8192), (1, 16384)]:
        try:
            split = flash_bwd(batch, seq, 'split')
            fused = flash_bwd(batch, seq, 'fused')
            print(json.dumps({
                'variant': f'flash_bwd b{batch} s{seq}',
                'split_ms': round(split * 1e3, 3),
                'fused_ms': round(fused * 1e3, 3),
                'fused_speedup': round(split / fused, 3)}))
        except Exception as error:
            print(json.dumps({'variant': f'flash_bwd b{batch} s{seq}',
                              'error': str(error)[:120]}))


def set_flash_tiles(block_q: int, block_kv: int):
    """Point the module-level kernel entry at a tile-pinned wrapper (the
    model families call ``flash_attention`` with defaults; ``attend``
    re-imports the module attribute per call, so swapping it here reaches
    every variant)."""
    from tpusystem.ops.pallas import flash
    original = getattr(flash, '_sweep_original', flash.flash_attention)
    flash._sweep_original = original

    def pinned(*args, **kwargs):
        kwargs.setdefault('block_q', block_q)
        kwargs.setdefault('block_kv', block_kv)
        return original(*args, **kwargs)
    flash.flash_attention = pinned


if __name__ == '__main__':
    if 'r5grid' in sys.argv[1:]:
        # round-5 re-sweep (VERDICT r4 #5): the round-2 recipe (b16,
        # 1024/1024, s90, c8) was tuned against the SPLIT backward; the
        # fused kernel shifts the compute/memory balance. Full grid under
        # backward='fused' (the default).
        for block_q, block_kv in [(1024, 1024), (512, 1024)]:
            set_flash_tiles(block_q, block_kv)
            for batch in (16, 24, 32):
                for steps in (90, 120):
                    for chunks in (8, 4):
                        safe(f'b{batch} t{block_q}/{block_kv} '
                             f's{steps} c{chunks}',
                             batch=batch, steps=steps, chunks=chunks)
    elif 'flash_bwd' in sys.argv[1:]:
        # the retired flash_backward_ab.py A/B: fused single-pass
        # dq+dk+dv backward vs the split dq/dkv pair, headline +
        # long-context shapes on the real chip
        flash_bwd_section()
    elif 'long' in sys.argv[1:]:
        # long-context ladder (BASELINE.md): 125M body, remat + fused loss
        # + flash, constant 16k tokens per step
        for batch, seq in [(4, 4096), (2, 8192), (1, 16384)]:
            safe(f'long b{batch} s{seq}', batch=batch, seq=seq, steps=30,
                 max_seq=seq, remat=True)
    else:
        safe('baseline b16 c8 s60')
        safe('repeat   b16 c8 s60')
        safe('batch 24', batch=24)
        safe('chunks 4', chunks=4)
        safe('steps 90', steps=90)
        # scan_layers stays out of the default sweep (its compile time
        # is measured in compile_time.py and scan_compile_probe.py)
        safe('steps 120', steps=120)
