"""Share of the device's busy time spent in one of the program's layers
whose cost lies partly in kernels the compiler leaves without a scope path:
the device seconds of the first chip's innermost operations whose scope
path holds any of ``args.scopes`` as a component (as ``scope_share`` counts
them), **and** of the operations whose name matches any of ``args.kernels``
(the Mosaic call a grouped product lowers to is named ``ragged-dot-none``
and carries no path; only the layer under ``args.scopes`` makes such calls),
over the busy seconds of the traced window, in every program alike. Prints
the seconds of each kind and the three largest kinds of operation it
counted."""

import re
import sys

from chipbench import trace_reduce
from chipbench.readers import program_trace


def read(records, spec):
    program = program_trace.of(records)
    if (not records.get('traced_window') or program is None
            or not program.scoped):
        return None
    start, end = trace_reduce.window_of(records['trace'])
    wanted = set(spec['args']['scopes'])
    kernels = spec['args']['kernels']
    events = program_trace.scoped_in(program, start, end)
    scoped = [event[:3] for event in events
              if wanted.intersection(program_trace.components(event[3]))]
    if not scoped:          # a program without the layer has no such kernel
        return None
    named = [event[:3] for event in events
             if not wanted.intersection(program_trace.components(event[3]))
             and any(re.search(pattern, event[0]) for pattern in kernels)]
    seconds = lambda group: sum(b - a for _, a, b in group)
    busy = trace_reduce.busy_seconds([event[:3] for event in events])
    if busy <= 0:
        return None
    largest = ', '.join(f'{name} {spent:.3f} s' for name, spent
                        in trace_reduce.top_ops(scoped + named, 3))
    print(f'{spec["name"]}: {seconds(scoped):.3f} s under {sorted(wanted)} '
          f'and {seconds(named):.3f} s in kernels {kernels}, of {busy:.3f} s '
          f'busy; most of it {largest}', file=sys.stderr)
    return 100.0 * (seconds(scoped) + seconds(named)) / busy
