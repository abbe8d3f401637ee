"""The serving window of a model whose attention selects what it reads.

``drivers/serve.py``'s window, sample and comparison, with one more number
compared beside ``logit_gap_max``: ``selection_flip_share``. The plain
reference goes through the positions the program's sparse attention layers
selected (a near-tie at the last place flips on a rounding, and one flipped
position moves the logits as much as a rounding of every matrix), so the
logits alone do not see a selection that is wrong; this number does. It is
the share of the selected positions where the program's selection and the
reference's own differ, either way, over the sampled requests' positions
in every such layer, as the family's ``selection_reading(config)`` gives it
after ``served_gap``. A run whose sample is empty reads it as not a number,
as it reads ``logit_gap_max``.

The family reads the program's selections from a replay of the sample
after the window, through a service built as the window's was (the rows
and block size this driver states as ``as_run.service``), so the timed
window reads none of them off the device.

Its requests are ``drivers/serve.py``'s, with every seed given the same
work (:class:`EvenClients`): at multi-kilotoken prompts and kilotoken
answers a 40 s window admits about two and a half cycles of the pool, so
which sizes a seed's order put in the window's partial cycle, and which
share of its answer each of the fill's requests was cut to, moved the
window's prefill seconds, and with them its throughput, by several percent
from seed to seed.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers import serve

NAME = 'selection_flip_share'


class EvenClients(serve.Clients):
    """``serve.Clients`` with the same work for every seed.

    * The pool's sizes come in rounds of four, one from each quarter of the
      pool by prompt length, each quarter in the seed's order: any run of
      consecutive requests holds nearly the same mix of prefill buckets.
    * A client's first request (client ``c`` sends request ``c``: size
      ``c mod pool``, in round ``c // pool`` of the fill) is cut to a share
      fixed by its size and round, not dealt in the seed's order: the
      shares are ``serve.Clients``' own, evenly spaced over 0.05..1, each
      size given one from every round's band, so the fill's remaining work
      is the same for every seed. The seed still orders each quarter's
      requests and writes their prompts."""

    def __init__(self, seed: int, mix: dict, vocab: int) -> None:
        from chipbench import traffic
        super().__init__(seed, mix, vocab)
        count, clients = len(self.sizes), mix['clients']
        ordered = sorted(range(count), key=lambda index: self.sizes[index])
        quarters = [sorted(ordered[q * count // 4:(q + 1) * count // 4])
                    for q in range(4)]
        longest = max(map(len, quarters))
        self.sizes = [self.sizes[quarter[k]] for k in range(longest)
                      for quarter in quarters if k < len(quarter)]
        rank = {size: index for index, size in enumerate(sorted(self.sizes))}
        band = traffic.seeded(mix['pairing_seed'], 8).permutation(count)
        slots = np.array([(client // count) * count
                          + band[rank[self.sizes[client % count]]]
                          for client in range(clients)])
        # the shares of serve.Clients, in the order of the slots
        place = np.argsort(np.argsort(slots, kind='stable'))
        self.under_way = 0.05 + 0.95 * (place + 0.5) / clients


def run(run) -> dict:
    from chipbench import families
    config, mix = run.cell.config, run.cell.traffic
    # the service the window builds, which the family's replay builds again
    config['as_run']['service'] = {'rows': mix['rows'],
                                   'block_size': mix['block_size']}
    # serve.run makes its clients from the module's name
    plain, serve.Clients = serve.Clients, EvenClients
    try:
        records = serve.run(run)
    finally:
        serve.Clients = plain
    reading = (families.of(config).selection_reading(config)
               if records['sample'] else None)
    share = float('nan') if reading is None else reading['share']
    records['compared'].append((NAME, share, run.cell.limits[NAME]['limit']))
    if reading is not None:
        records['notes'].append(
            f'selections: {reading["flips"]} of {reading["selected"]} '
            f'selected positions differ from the reference\'s own; '
            f'{reading["unmatched"]} sampled requests not replayed token '
            f'for token')
    return records
