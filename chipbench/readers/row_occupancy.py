"""Mean share of the engine's rows that held a request, over the ticks."""

from chipbench.readers import traced_ticks


def read(records, spec):
    ticks = traced_ticks(records)
    if not ticks:
        return None
    rows = records['engine']['rows']
    return 100.0 * sum(tick['active'] for tick in ticks) / (len(ticks) * rows)
