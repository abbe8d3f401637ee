"""Per-layer readers: ``read(records, spec) -> float | None``, one module
per metric file's ``reader``. A reader that finds nothing to read returns
None and the metric is left out of the line; it never returns 0 for a
share of a roofline or of a peak.

``records`` is what the driver returned plus ``trace`` (a
``trace_reduce.Trace``); ``spec`` is the metric's own file, whose ``args``
hold what the reader needs (name patterns and the like).
"""


def traced_ticks(records: dict) -> list:
    """The serving ticks that ran inside the traced window (host clock)."""
    window = records.get('traced_window')
    if not window:
        return []
    start, end = window
    return [tick for tick in records.get('ticks', [])
            if not tick.get('drain') and start <= tick['start'] < end]
