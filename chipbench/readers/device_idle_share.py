"""Share of the traced window in which no operation ran on the device."""

from chipbench import trace_reduce


def read(records, spec):
    summary = trace_reduce.device_summary(records['trace'])
    if summary['window_s'] <= 0:
        return None
    return 100.0 * (1.0 - summary['busy_s'] / summary['window_s'])
