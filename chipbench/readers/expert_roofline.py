"""The grouped expert products' share of their roofline over the traced
decode ticks: the least time the chip could take — the larger of the
matrices of the held experts that were hit, streamed once each a tick and a
layer, over the chip's HBM bytes/s, and the operations of the assignments
seated here over its bf16 peak (``families/<family>.py::
expert_ops_and_bytes``) — over the device time under the program's
``args.scope`` (``experts``) in the program named ``args.program`` (the
decode step), the ``args.kernels`` that run inside that program's
executions included (the compiler leaves the grouped products' Mosaic calls
without a scope path). The counts are the program's own: one ``args.instant`` mark of
its tracer a tick (``hit``, ``seated``: ``Engine.expert_load``'s), summed
over the marks inside the traced window. Silent where the program leaves no
such mark or the trace no operation under the scope."""

import sys

from chipbench import families, flops
from chipbench.readers import latent_read_roofline


def marks(records, name: str) -> list:
    """The ``args`` of the tracer's instants called ``name`` inside the
    traced window."""
    window = records.get('traced_window')
    if not window:
        return []
    lo, hi = window
    return [event['args'] for event in records.get('spans', [])
            if event.get('name') == name and event.get('ph') == 'i'
            and lo <= event['ts'] * 1e-6 < hi]


def read(records, spec):
    args = spec['args']
    ticks = marks(records, args['instant'])
    if not ticks:
        return None
    spent = latent_read_roofline.scope_seconds(
        records, args['scope'], args['program'], args.get('kernels', ()))
    hit = sum(tick['hit'] for tick in ticks)
    seated = sum(tick['seated'] for tick in ticks)
    if not spent or not hit:
        return None
    config = records['config']
    ops, moved = families.of(config).expert_ops_and_bytes(config, hit, seated)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'{spec["name"]}: bound by {bound}; {len(ticks)} ticks, {hit} '
          f'experts hit, {seated} assignments, {moved / 1e9:.3f} GB, '
          f'{ops / 1e12:.3f} TFLOP, least {least:.4f} s, under '
          f'{args["scope"]} {spent:.4f} s', file=sys.stderr)
    return 100.0 * least / spent
