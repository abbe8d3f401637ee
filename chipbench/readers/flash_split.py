"""Flash attention's share of its roofline, forward and backward apart:
the arithmetic of ``flash_roofline.py`` over the same kernel events, split
by what JAX writes into an operation's scope path for the backward of a
``custom_vjp`` (``args.backward_markers``, e.g. ``transpose(``). ``args.
backward`` says which side this metric is. Prints the bound, and both
sides' seconds beside their sum (what ``flash_roofline.train`` divides
by). A kernel event that names no scope cannot be put on a side: None."""

import sys

from chipbench import flops, trace_reduce
from chipbench.readers import program_trace


def read(records, spec):
    program = program_trace.of(records)
    if program is None:
        return None
    start, end = trace_reduce.window_of(records['trace'])
    kernels = trace_reduce.matching(
        program_trace.scoped_in(program, start, end),
        spec['args']['kernel_patterns'])
    if not kernels or not all(event[3] for event in kernels):
        return None
    seconds = {False: 0.0, True: 0.0}
    for _, a, b, scope in kernels:
        backward = any(marker in scope
                       for marker in spec['args']['backward_markers'])
        seconds[backward] += b - a
    side = bool(spec['args']['backward'])
    if seconds[side] <= 0:
        return None
    config, mix = records['config'], records['traffic']
    ops, moved = flops.flash_ops_and_bytes(config, mix['batch'], mix['seq'],
                                           side)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    least *= flops.flash_layers(config) * records['traced']['steps']
    print(f'{spec["name"]}: bound by {bound}; least {least:.4f} s, kernels '
          f'{seconds[side]:.4f} s (forward {seconds[False]:.4f} + backward '
          f'{seconds[True]:.4f} = {seconds[False] + seconds[True]:.4f} s)',
          file=sys.stderr)
    return 100.0 * least / seconds[side]
