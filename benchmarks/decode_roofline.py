"""Decode roofline: what does one greedy-decode token-step *have* to cost?

BASELINE's decode row (GPT-2 125M, batch 8, prefill 128, decode 128) is
2310 tok/s = 3.46 ms per token-step on 1x v5e. This file writes the
weight-streaming roofline next to it and decomposes the gap:

1. ``bandwidth``    — big-copy effective HBM bandwidth of the chip
2. ``stream_f32``   — the exact decode matmul chain (12 layers qkv/out/
                      fc/proj + LM head) with float32 master weights, the
                      layout ``generate()`` historically streamed
3. ``stream_bf16``  — identical chain with pre-cast bfloat16 weights
                      (identical matmul numerics — the bf16 cast happens
                      per-use anyway; only the HBM bytes halve)
4. ``stream_int8``/``stream_fp8`` — identical chain with per-channel
                      symmetric quantized weights (`ops/precision.py`):
                      the narrow values are the streamed operand, the f32
                      scale multiplies the accumulator — weight bytes
                      halve AGAIN vs bf16
5. ``fused_*``      — the same chain through the Pallas fused decode
                      kernels (`ops/pallas/decode_matmul.py`): activation
                      VMEM-resident, weights streamed tile-by-tile,
                      int8 tiles dequantized in-kernel, fc→gelu→proj in
                      one kernel
6. ``generate[*]``  — the real ``generate()`` under every streaming mode
                      and the fused decode impl

Roofline: 125M params x 4 B (f32) = ~500 MB/step → ~0.61 ms at the v5e's
~819 GB/s; bf16 halves it to ~0.31 ms, int8/fp8 to ~0.15 ms. The
measured chain vs the measured copy bandwidth separates "medium-matmul
streaming is below copy bandwidth" (platform) from "the decode loop adds
overhead on top" (framework).

Every row is one machine-readable JSON line (the `moe_dispatch.py`
convention). ``weight_stream_bytes`` is the per-step streamed weight
bytes (the roofline quantity); quantized rows list their per-channel
scale bytes separately (``scale_stream_bytes`` — ~0.5% overhead, also
streamed per step) and ``bytes_vs_bf16`` is the weight-stream reduction
(exactly 2x for int8/fp8 vs bf16).

Run: ``python benchmarks/decode_roofline.py [chain|fused|generate|scaling]``
(no arg = all sections; on CPU the fused section runs the kernels in
interpret mode — parity smoke, not a timing).
"""

from __future__ import annotations

import sys
sys.path.insert(0, str(__import__('pathlib').Path(__file__).parent.parent))

import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import materialize as _materialize
from tpusystem.ops.precision import (QuantizedLeaf, fp8_unsupported_reason,
                                     quantize_leaf)
from tpusystem.parallel.mesh import on_tpu

BATCH, DIM, LAYERS, VOCAB = 8, 768, 12, 50304
# Off-TPU the chain runs at emulated-bf16 CPU speed — enough reps for a
# stable median would take tens of minutes, and the numbers are smoke
# anyway. TPU keeps the real count.
REPS = 200 if on_tpu() else 10


def _time(run, *args) -> float:
    out = run(*args)
    _materialize(out)
    t0 = time.perf_counter()
    out = run(*args)
    _materialize(out)
    return (time.perf_counter() - t0) / REPS


# v5e paper HBM bandwidth. Measured probes mislead here: a fori_loop of
# per-slice reductions reports 20 GB/s (loop overhead) and one giant
# fused multiply-reduce reports 33 GB/s (reduction lowering), while the
# decode matmul chain itself sustains ~280 GB/s — the matmul chain IS
# the honest streaming measurement; the paper number anchors the floor.
PAPER_HBM_GBS = 819.0

CHAIN_SHAPES = [(DIM, 3 * DIM), (DIM, DIM), (DIM, 4 * DIM), (4 * DIM, DIM)]


def _chain_weights(mode: str):
    """The exact decode chain's weights in one streaming mode:
    ``'f32'``/``'bf16'`` plain, ``'int8'``/``'fp8'`` per-channel
    quantized. Returns (layers, head, weight_bytes, scale_bytes)."""
    rng = np.random.default_rng(0)

    def make(shape):
        wide = jnp.asarray(rng.normal(size=shape) * 0.02, jnp.float32)
        if mode == 'f32':
            return wide
        if mode == 'bf16':
            return wide.astype(jnp.bfloat16)
        return quantize_leaf(wide, mode)

    layers = [tuple(make(shape) for shape in CHAIN_SHAPES)
              for _ in range(LAYERS)]
    head = make((DIM, VOCAB))
    flat = [w for ws in layers for w in ws] + [head]
    weight_bytes = sum(w.values.nbytes if isinstance(w, QuantizedLeaf)
                       else w.nbytes for w in flat)
    scale_bytes = sum(w.scales.nbytes for w in flat
                      if isinstance(w, QuantizedLeaf))
    return layers, head, weight_bytes, scale_bytes


def _mm(x, w):
    """One chain matmul in the mode's streamed form: plain weights cast
    to bf16 per use (as the model's Dense layers do); quantized weights
    contract their narrow values and scale the result — qdot's math,
    chain-dtype flavored."""
    if isinstance(w, QuantizedLeaf):
        return ((x @ w.values.astype(jnp.bfloat16))
                * w.scales).astype(jnp.bfloat16)
    return x @ w.astype(jnp.bfloat16)


def stream_chain(weights, fused: bool = False) -> float:
    """ms per step of the exact decode matmul chain over prebuilt
    ``_chain_weights`` output; ``fused=True`` routes the per-layer sweep
    through the Pallas decode kernels instead of plain einsums. Every
    weight — the LM head included — is threaded through the jitted
    runner's ARGUMENTS: a closed-over array is a compile-time constant
    XLA would happily cast/dequantize once outside the scan, un-streaming
    the very bytes this file measures."""
    layers, head, _, _ = weights
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.normal(size=(BATCH, DIM)), jnp.bfloat16)
    if fused:
        from tpusystem.ops.pallas.decode_matmul import (decode_ffn,
                                                        decode_matmul)
        zero_hidden = jnp.zeros((4 * DIM,), jnp.float32)
        zero_dim = jnp.zeros((DIM,), jnp.float32)

        def sweep(x, qkv, out, fc, proj):
            h = decode_matmul(x, qkv)
            x = x + decode_matmul(h[:, :DIM], out)
            return x + decode_ffn(x, fc, zero_hidden, proj, zero_dim)

        def logits_of(x, head):
            return decode_matmul(x, head)
    else:
        def sweep(x, qkv, out, fc, proj):
            h = _mm(x, qkv)
            x = x + _mm(h[:, :DIM], out)
            return x + _mm(jax.nn.gelu(_mm(x, fc)), proj)

        def logits_of(x, head):
            return _mm(x, head)

    @jax.jit
    def run(x0, layers, head):
        def step(carry, _):
            x = carry
            for qkv, out, fc, proj in layers:
                x = sweep(x, qkv, out, fc, proj)
            logits = logits_of(x, head)
            # argmax feedback: the next step depends on this one (no
            # hoisting), like real greedy decode
            x = x0 + (jnp.argmax(logits, -1)[:, None] % 7).astype(
                jnp.bfloat16) * 1e-3
            return x, logits[0, 0]
        _, ys = jax.lax.scan(step, x0, None, length=REPS)
        return ys

    return _time(run, x0, tuple(layers), head) * 1e3


def chain_row(mode: str, bf16_bytes: int | None, fused: bool = False) -> int:
    """Print one chain row; returns the row's weight-stream bytes."""
    weights = _chain_weights(mode)       # built ONCE per row (~0.5 GB)
    _, _, weight_bytes, scale_bytes = weights
    ms = stream_chain(weights, fused=fused)
    total = weight_bytes + scale_bytes
    floor = total / (PAPER_HBM_GBS * 1e9) * 1e3
    row = {'ms_per_step': round(ms, 3),
           'weight_stream_bytes': weight_bytes,
           'weight_mb': round(total / 2**20),
           'effective_gbs': round(total / ms * 1e-6, 1),
           'paper_bw_floor_ms': round(floor, 3),
           'vs_floor': round(ms / floor, 2)}
    if scale_bytes:
        row['scale_stream_bytes'] = scale_bytes
    if bf16_bytes is not None:
        row['bytes_vs_bf16'] = round(bf16_bytes / weight_bytes, 2)
    tag = f'fused_{mode}' if fused else f'stream_{mode}'
    print(json.dumps({tag: row}))
    return weight_bytes


def chain_section() -> None:
    bf16_bytes = None
    for mode in ('f32', 'bf16', 'int8', 'fp8'):
        if mode == 'fp8':
            reason = fp8_unsupported_reason()
            if reason is not None:
                print(json.dumps({'stream_fp8': {'skipped': reason}}))
                continue
        bytes_now = chain_row(mode, bf16_bytes)
        if mode == 'bf16':
            bf16_bytes = bytes_now


def fused_section() -> None:
    """The chain through the Pallas fused decode kernels. On TPU this is
    the streamed-tile timing; on CPU the kernels run in interpret mode —
    a parity smoke whose ms column is meaningless."""
    # bf16 chain bytes are shape arithmetic — no need to build the arrays
    bf16_bytes = 2 * (LAYERS * sum(rows * cols for rows, cols in CHAIN_SHAPES)
                      + DIM * VOCAB)
    for mode in ('bf16', 'int8'):
        chain_row(mode, bf16_bytes if mode != 'bf16' else None, fused=True)


def measured_generate(stream_dtype: str, decode_impl: str = 'auto') -> None:
    """tok/s of the real generate() at the BASELINE row's config."""
    from tpusystem.models import GPT2
    from tpusystem.train.generate import generate, streamed_bytes

    module = GPT2(dropout=0.0, vocab_size=VOCAB, max_seq=512)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, VOCAB, (BATCH, 128)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), prompt[:1, :8])['params']

    run = partial(generate, module, params, prompt, steps=128,
                  stream_dtype=stream_dtype, decode_impl=decode_impl)
    np.asarray(run())
    t0 = time.perf_counter()
    np.asarray(run())
    elapsed = time.perf_counter() - t0
    tok = BATCH * 128 / elapsed
    tag = (f'generate[{stream_dtype}]' if decode_impl == 'auto'
           else f'generate[{stream_dtype},{decode_impl}]')
    print(json.dumps({tag: {
        'tok_per_s': round(tok),
        'ms_per_token_step': round(BATCH * 1e3 / tok, 3),
        'stream_bytes_per_step': streamed_bytes(module, params,
                                                stream_dtype)}}))


def generate_section() -> None:
    modes = ['float32', 'auto', 'bfloat16', 'int8']
    if fp8_unsupported_reason() is None:
        modes.append('fp8')
    for mode in modes:
        measured_generate(mode)
    # the fused decode impl (Pallas chain inside the compiled loop) —
    # forced, so CPU runs exercise interpret-mode parity too
    measured_generate('int8', decode_impl='fused')


def scaling() -> None:
    """tok/s vs cache capacity (bucketed reads) and batch (weight-stream
    amortization) — the two levers the roofline exposes."""
    from tpusystem.models import GPT2
    from tpusystem.train.generate import generate

    for batch, max_seq in [(8, 256), (8, 512), (8, 1024), (32, 512),
                           (64, 512)]:
        module = GPT2(dropout=0.0, vocab_size=VOCAB, max_seq=max_seq)
        rng = np.random.default_rng(0)
        prompt = jnp.asarray(rng.integers(0, VOCAB, (batch, 128)), jnp.int32)
        params = module.init(jax.random.PRNGKey(0), prompt[:1, :8])['params']
        out = generate(module, params, prompt, steps=128)
        np.asarray(out)
        t0 = time.perf_counter()
        out = generate(module, params, prompt, steps=128)
        np.asarray(out)
        elapsed = time.perf_counter() - t0
        print(json.dumps({'batch': batch, 'max_seq': max_seq,
                          'tok_per_s': round(batch * 128 / elapsed),
                          'ms_per_step': round(elapsed / 128 * 1e3, 3)}))


def main() -> None:
    sections = {'chain': chain_section, 'fused': fused_section,
                'generate': generate_section, 'scaling': scaling}
    picked = [arg for arg in sys.argv[1:] if arg in sections]
    for name, section in sections.items():
        if not picked or name in picked:
            section()


if __name__ == '__main__':
    main()
