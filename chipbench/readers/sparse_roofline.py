"""A part of a learned sparse attention's decode read against its roofline:
the least time the chip could take for what the decode steps of the traced
window had to read in that part (``args.part``: ``indexer``, every held
position's indexer key scored in each full layer; ``sparse_read``, the
``min(depth, topk)`` selected latent rows attended there; ``window_read``,
the ``min(depth, window)`` rows of each sliding layer), over the device time
those steps spent under the program's ``args.scope`` in the program named
``args.program`` (the decode step).

Depths: for every token decoded inside the window, the positions its row
held (prompt + position, as ``latent_read_roofline`` walks the requests).
The family counts what they cost
(``families/<family>.py::sparse_ops_and_bytes``); each over the chip's
peak, the larger is the least time, and the bound is printed. Only
positions the algorithm reads count, never a padded window or a masked
row, so a read that fetches more than it needs reads under 100 %. Silent
where the family has no such count or the trace no operation under the
scope (a program without the scope, or a run without a trace)."""

import sys

from chipbench import families, flops
from chipbench.readers.latent_read_roofline import scope_seconds


def decoded_depths(records) -> list:
    lo, hi = records['traced_window']
    return [request['prompt'] + position
            for request in records['requests']
            for position, moment in enumerate(request['times'])
            if position and lo <= moment < hi]


def read(records, spec):
    if not records.get('traced_window'):
        return None
    config, args = records['config'], spec['args']
    counts = getattr(families.of(config), 'sparse_ops_and_bytes', None)
    if counts is None:
        return None
    spent = scope_seconds(records, args['scope'], args['program'])
    depths = decoded_depths(records)
    if not spent or not depths:
        return None
    ops, moved = counts(config, args['part'], depths)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'{spec["name"]}: bound by {bound}; {len(depths)} decoded tokens, '
          f'{moved / 1e9:.3f} GB, {ops / 1e12:.3f} TFLOP, least '
          f'{least:.4f} s, under {args["scope"]} {spent:.4f} s',
          file=sys.stderr)
    return 100.0 * least / spent
