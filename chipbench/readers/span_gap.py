"""Idle time between programs, by what the host was doing: the stretches
of the traced window with no ``XLA Modules`` event on the first chip,
handed to ``trace_reduce.attribute_gaps`` with the program's own
``tpusystem.*`` spans (the innermost span wins), and the seconds that fall
to ``args.span`` over the ``args.per`` spans begun in the window, in ms.
A span that is there and holds no gap reads 0."""

from chipbench import trace_reduce
from chipbench.readers import program_trace


def read(records, spec):
    program = program_trace.of(records)
    if program is None or not records['trace'].modules:
        return None
    gaps, start, end = program_trace.module_gaps(records)
    inside = lambda name: [span for span in program_trace.named(program, name)
                           if start <= span[1] < end]
    per = inside(spec['args']['per'])
    if not per or not inside(spec['args']['span']):
        return None
    spans = [span[:3] for span in program.spans]
    spent = dict(trace_reduce.attribute_gaps(gaps, spans, count=len(spans)))
    return 1e3 * spent.get(spec['args']['span'], 0.0) / len(per)
