"""The paged-attention kernel's share of its roofline: the bytes of keys
and values the decode steps of the traced window had to read — for every
token decoded inside it, the positions its row held (prompt + position, as
``step_mfu`` walks the requests) x the bytes one cached position holds
(``flops.kv_bytes_per_position``: the family's layers, K and V, width and
pool type) — at the chip's HBM bytes/s, over the device time of the kernel's
events in the same window. Only positions a row holds count, never the
reserved or the padded ones, so a kernel that skips what it need not read
cannot pass 100 %. Silent where the trace holds no such kernel."""

import sys

from chipbench import flops, trace_reduce


def read(records, spec):
    trace = records['trace']
    if not trace.ops or not records.get('traced_window'):
        return None
    start, end = trace_reduce.window_of(trace)
    events = trace_reduce.clip(trace.ops[min(trace.ops)], start, end)
    spent = trace_reduce.kernel_seconds(events, spec['args']['kernel_patterns'])
    if spent <= 0:
        return None
    config = records['config']
    lo, hi = records['traced_window']
    positions = sum(request['prompt'] + position
                    for request in records['requests']
                    for position, moment in enumerate(request['times'])
                    if position and lo <= moment < hi)
    if not positions:
        return None
    moved = positions * flops.kv_bytes_per_position(config)
    least = moved / flops.peaks(records['device_kind'])['hbm_bytes_per_s']
    print(f'kv_read_roofline: bound by memory; {positions} positions '
          f'attended, {moved / 1e9:.3f} GB, least {least:.4f} s, kernels '
          f'{spent:.4f} s', file=sys.stderr)
    return 100.0 * least / spent
