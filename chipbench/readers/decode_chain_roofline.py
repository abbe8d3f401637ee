"""The fused decode chain's share of its roofline: per decode tick in the
trace, the least time for the four matrix products of every layer
(streamed weights at the width the engine resolved, ``flops.
decode_chain_ops_and_bytes``), over the device time of the chain's kernels.
Silent where the engine did not resolve to the fused chain."""

import sys

from chipbench import flops, trace_reduce

WEIGHT_BYTES = {'int8': 1.0, 'fp8': 1.0, 'bfloat16': 2.0, 'auto': 2.0,
                'float32': 4.0}


def read(records, spec):
    if records['resolved']['decode_impl'] != 'fused':
        return None
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    if not trace.ops:
        return None
    chip = min(trace.ops)
    events = trace_reduce.clip(trace.ops[chip], start, end)
    spent = trace_reduce.kernel_seconds(events, spec['args']['kernel_patterns'])
    ticks = len(trace_reduce.matching(
        trace_reduce.clip(trace.modules.get(chip, []), start, end),
        spec['args']['module_patterns']))
    if spent <= 0 or not ticks:
        return None
    ops, moved = flops.decode_chain_ops_and_bytes(
        records['config'], records['engine']['rows'],
        WEIGHT_BYTES[records['resolved']['stream_dtype']])
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'decode_chain_roofline: bound by {bound}; {ticks} ticks, least '
          f'{least * ticks:.4f} s, kernels {spent:.4f} s', file=sys.stderr)
    return 100.0 * least * ticks / spent
