"""Share of the traced window in which the host held the chip back: time
inside one of the program's own spans (``args.span``: the serving tick,
the training epoch) while no program (``XLA Modules`` event) ran on the
first chip. ``device_idle_share`` minus this is the idle time inside
programs. Also prints, where the spans carry a ``clock`` stat, the offset
``trace clock - program clock`` and its spread over the window: the number
that places ``records['ticks']`` and exported ``Tracer`` events on the
trace."""

import statistics
import sys

from chipbench.readers import program_trace


def read(records, spec):
    program = program_trace.of(records)
    if program is None or not records['trace'].modules:
        return None
    spans = [span for span in program.spans
             if span[0] == spec['args']['span']]
    if not spans:
        return None
    gaps, start, end = program_trace.module_gaps(records)
    offsets = [span[1] - span[3]['clock'] for span in spans
               if 'clock' in span[3] and start <= span[1] < end]
    if offsets:
        print(f'{spec["name"]}: trace clock - program clock = '
              f'{statistics.median(offsets):.6f} s over {len(offsets)} '
              f'spans, spread {1e3 * (max(offsets) - min(offsets)):.3f} ms',
              file=sys.stderr)
    covered = program_trace.overlap(gaps, [span[:3] for span in spans])
    return 100.0 * covered / (end - start)
