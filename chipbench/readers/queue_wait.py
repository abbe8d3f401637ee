"""Median of the scheduler's own ``queued`` spans (``observe.trace.Tracer``
handed to ``InferenceService``), for requests queued in the traced window."""

import statistics


def read(records, spec):
    window = records.get('traced_window')
    if not window:
        return None
    lo, hi = window
    waits = [event['dur'] * 1e-3 for event in records.get('spans', [])
             if event.get('name') == 'queued' and event.get('ph') == 'X'
             and not event['args'].get('open')
             and lo <= event['ts'] * 1e-6 < hi]
    return statistics.median(waits) if waits else None
