"""Median host time of one decode dispatch (``Engine.timings['step']`` per
tick: the compiled step and the host read of its tokens)."""

import statistics

from chipbench.readers import traced_ticks


def read(records, spec):
    steps = [tick['decode_s'] for tick in traced_ticks(records)
             if tick['decode_s'] > 0]
    return 1e3 * statistics.median(steps) if steps else None
