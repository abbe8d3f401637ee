"""Convolution ceiling: what MFU can ResNet-50's conv shapes reach at all?

Times every distinct convolution in ResNet-50 (bf16 NHWC, fwd only, the
MXU-friendly layout) in isolation, plus an equal-FLOPs square matmul as
the platform's best case. The FLOPs-weighted composite of the per-shape
rates is the convolution ceiling for the whole network: if the train-step
MFU (ladder row 3) sits near the composite, the gap to the transformer
headline is the platform's conv lowering, not the training recipe.

Run: ``python benchmarks/conv_ceiling.py [batch]``
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

from bench import peak_flops

# (spatial, cin, cout, kernel, stride, count) — every conv in ResNet-50
# (stem + 4 stages of bottlenecks with their 1x1/3x3/1x1 + projections)
RESNET50_CONVS = [
    (224, 3, 64, 7, 2, 1),      # stem
    (56, 64, 64, 1, 1, 3),      # stage1 1x1 in
    (56, 64, 64, 3, 1, 3),      # stage1 3x3
    (56, 64, 256, 1, 1, 4),     # stage1 1x1 out + proj
    (56, 256, 64, 1, 1, 2),     # stage1 1x1 in (later blocks)
    (56, 256, 128, 1, 2, 2),    # stage2 in + proj (strided)
    (28, 128, 128, 3, 1, 4),    # stage2 3x3 (first is stride-2 from 56)
    (28, 128, 512, 1, 1, 4),
    (28, 512, 128, 1, 1, 3),
    (28, 512, 256, 1, 2, 2),    # stage3 in + proj
    (14, 256, 256, 3, 1, 6),
    (14, 256, 1024, 1, 1, 6),
    (14, 1024, 256, 1, 1, 5),
    (14, 1024, 512, 1, 2, 2),   # stage4 in + proj
    (7, 512, 512, 3, 1, 3),
    (7, 512, 2048, 1, 1, 3),
    (7, 2048, 512, 1, 1, 2),
]
REPEATS = 1000


def time_op(fn, x, w) -> float:
    """Mean seconds per op over REPEATS data-DEPENDENT calls inside one
    ``fori_loop``: each iteration folds a scalar of the op's output back
    into the carried input (times 1e-7, not 0 — XLA folds multiplications
    by zero; a data dependency defeats CSE/hoisting), so every iteration
    really runs the op. The chain adds one x-sized broadcast-add per rep
    — the realistic inter-op condition inside a residual network. 1000
    reps keep the per-dispatch host overhead small even for the smallest
    conv."""
    def body(_, carry):
        y = fn(carry, w)
        feedback = y[(0,) * y.ndim].astype(carry.dtype)
        return carry + feedback * jnp.asarray(1e-7, carry.dtype)
    run = jax.jit(lambda x, w: jax.lax.fori_loop(0, REPEATS, body, x))
    out = run(x, w)
    float(jnp.sum(out.astype(jnp.float32)))
    t0 = time.perf_counter()
    out = run(x, w)
    float(jnp.sum(out.astype(jnp.float32)))
    return (time.perf_counter() - t0) / REPEATS


def main(batch: int) -> None:
    peak = peak_flops(jax.devices()[0])
    rng = np.random.default_rng(0)
    total_flops, total_time = 0.0, 0.0
    for spatial, cin, cout, k, stride, count in RESNET50_CONVS:
        x = jnp.asarray(rng.normal(size=(batch, spatial, spatial, cin)),
                        jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(k, k, cin, cout)), jnp.bfloat16)
        conv = partial(jax.lax.conv_general_dilated,
                       window_strides=(stride, stride), padding='SAME',
                       dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        seconds = time_op(conv, x, w)
        out_sp = spatial // stride
        flops = 2 * batch * out_sp * out_sp * k * k * cin * cout
        rate = flops / seconds
        total_flops += flops * count
        total_time += seconds * count
        print(json.dumps({
            'conv': f'{spatial}x{spatial} {cin}->{cout} k{k} s{stride}',
            'count': count, 'gflops': round(flops / 1e9, 2),
            'mfu': round(rate / peak, 3)}))

    composite = total_flops / total_time / peak
    # equal-FLOPs best case: one square bf16 matmul sized to the average
    # per-conv FLOPs (the MXU rate the platform gives dense contraction)
    n = 4096
    a = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(n, n)), jnp.bfloat16)
    mm = time_op(lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32)
                 .astype(jnp.bfloat16), a, b)  # same chained harness
    mm_mfu = 2 * n ** 3 / mm / peak
    print(json.dumps({
        'composite_conv_mfu_fwd': round(composite, 4),
        'matmul_4096_mfu': round(mm_mfu, 4),
        'batch': batch,
        'note': 'composite = FLOPs-weighted fwd conv ceiling over all '
                'ResNet-50 shapes; train-step MFU also pays backward '
                '(input+filter grads, ~2x fwd at similar shapes), '
                'normalization + optimizer',
    }))


if __name__ == '__main__':
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 64)
