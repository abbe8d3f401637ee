"""Device time of one training step: the median duration of the
multi-step program's executions in the trace, over its steps."""

import statistics

from chipbench import trace_reduce


def read(records, spec):
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    chip = min(trace.modules) if trace.modules else None
    if chip is None:
        return None
    runs = [b - a for _, a, b in trace_reduce.matching(
        trace_reduce.clip(trace.modules[chip], start, end),
        spec['args']['module_patterns'])]
    if not runs:
        return None
    return 1e3 * statistics.median(runs) / records['traffic'][
        'steps_per_dispatch']
