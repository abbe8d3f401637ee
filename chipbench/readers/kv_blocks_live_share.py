"""Mean share of the paged pool's blocks that a seated row references."""

from chipbench.readers import traced_ticks


def read(records, spec):
    ticks = traced_ticks(records)
    if not ticks:
        return None
    blocks = records['engine']['blocks']
    return 100.0 * sum(tick['live_blocks'] for tick in ticks) / (
        len(ticks) * blocks)
