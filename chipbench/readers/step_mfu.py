"""The whole step's share of the chip's bf16 peak over the traced window:
operations the algorithm needs (``flops.py``) for the tokens the window
processed, over window seconds x chips x peak."""

from chipbench import flops, trace_reduce


def read(records, spec):
    config = records['config']
    peak = flops.peaks(records['device_kind'])['bf16_flops_per_s']
    start, end = trace_reduce.window_of(records['trace'])
    if spec['args']['kind'] == 'train':
        tokens = (records['traced']['steps'] * records['traffic']['batch']
                  * records['traffic']['seq'])
        ops = tokens * flops.train_ops_per_token(config,
                                                 records['traffic']['seq'])
    else:
        lo, hi = records['traced_window']
        ops = 0.0
        for request in records['requests']:
            times = request['times']
            if times and lo <= times[0] < hi:
                ops += flops.prefill_ops(config, request['prompt'])
            ops += sum(flops.decode_ops(config, request['prompt'] + position)
                       for position, moment in enumerate(times)
                       if position and lo <= moment < hi)
    if ops <= 0 or end <= start:
        return None
    return 100.0 * ops / ((end - start) * records['chips'] * peak)
