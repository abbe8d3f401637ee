"""Share of the traced window the engine spent in prefill and admission
(``Engine.timings['prefill'] + ['admit']``, each ending in a host read)."""

from chipbench.readers import traced_ticks


def read(records, spec):
    ticks = traced_ticks(records)
    if not ticks:
        return None
    lo, hi = records['traced_window']
    spent = sum(tick['prefill_s'] + tick['admit_s'] for tick in ticks)
    return 100.0 * spent / (hi - lo)
