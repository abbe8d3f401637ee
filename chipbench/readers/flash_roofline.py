"""Flash attention's share of its roofline in the training step: the least
time the chip could take for the forward and backward kernels of every
layer and step in the trace (``flops.flash_ops_and_bytes``), over the
device time of the kernels' events. The bound is printed to stderr."""

import sys

from chipbench import flops, trace_reduce


def read(records, spec):
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    if not trace.ops:
        return None
    events = trace_reduce.clip(trace.ops[min(trace.ops)], start, end)
    spent = trace_reduce.kernel_seconds(events, spec['args']['kernel_patterns'])
    if spent <= 0:
        return None
    config, mix = records['config'], records['traffic']
    peak = flops.peaks(records['device_kind'])
    least, bounds = 0.0, []
    for backward in (False, True):
        ops, moved = flops.flash_ops_and_bytes(config, mix['batch'],
                                               mix['seq'], backward)
        seconds, bound = flops.roofline_seconds(ops, moved, peak)
        least += seconds
        bounds.append(bound)
    least *= flops.flash_layers(config) * records['traced']['steps']
    print(f'flash_roofline: forward bound by {bounds[0]}, backward by '
          f'{bounds[1]}; least {least:.4f} s, kernels {spent:.4f} s',
          file=sys.stderr)
    return 100.0 * least / spent
