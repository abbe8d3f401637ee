"""Median length of one of the program's ``observe.trace.Tracer`` spans
(``args.span``, by name), over those begun in the traced window and closed:
the arithmetic of ``queue_wait.py`` for any span of the request's life."""

import statistics


def read(records, spec):
    window = records.get('traced_window')
    if not window:
        return None
    lo, hi = window
    spent = [event['dur'] * 1e-3 for event in records.get('spans', [])
             if event.get('name') == spec['args']['span']
             and event.get('ph') == 'X' and not event['args'].get('open')
             and lo <= event['ts'] * 1e-6 < hi]
    return statistics.median(spent) if spent else None
