"""How unevenly the router loaded the experts held here: the mean over the
traced ticks of the largest number of assignments one held expert got over
the mean a held expert got (1 is even). Both are the program's counters,
summed over the expert layers, from its tracer's ``args.instant`` mark of
each tick (``largest``: the layers' largest; ``seated``: the assignments
seated here); the held count is the family's (``held_experts``)."""

import statistics

from chipbench import families
from chipbench.readers import expert_roofline


def read(records, spec):
    ticks = [tick for tick in expert_roofline.marks(records,
                                                    spec['args']['instant'])
             if tick['seated']]
    if not ticks:
        return None
    config = records['config']
    held = families.of(config).held_experts(config)[1]
    return statistics.fmean(tick['largest'] * held / tick['seated']
                            for tick in ticks)
