"""Share of the device's busy time spent under one of the program's
``jax.named_scope`` names: the device seconds of the first chip's innermost
operations whose scope path holds any of ``args.scopes`` as a component
(forward and backward alike: ``transpose(jvp(loss))`` is ``loss``), over
the busy seconds of the traced window. A fusion counts under the one path
it carries (``program_trace``'s header). Prints the seconds and the three
largest kinds of operation it counted."""

import sys

from chipbench import trace_reduce
from chipbench.readers import program_trace


def read(records, spec):
    program = program_trace.of(records)
    if program is None or not program.scoped:
        return None
    start, end = trace_reduce.window_of(records['trace'])
    wanted = set(spec['args']['scopes'])
    events = program_trace.scoped_in(program, start, end)
    inside = [event[:3] for event in events
              if wanted.intersection(program_trace.components(event[3]))]
    under = sum(b - a for _, a, b in inside)
    busy = trace_reduce.busy_seconds([event[:3] for event in events])
    if under <= 0 or busy <= 0:
        return None
    largest = ', '.join(f'{name} {seconds:.3f} s' for name, seconds
                        in trace_reduce.top_ops(inside, 3))
    print(f'{spec["name"]}: {under:.3f} s of {busy:.3f} s busy; most of it '
          f'{largest}', file=sys.stderr)
    return 100.0 * under / busy
