"""A looped stack's share of its roofline in the decode step: the least time
the chip could take to stream every pass's matrices once a tick and run the
seated rows' tokens through them, over the device time of the decode step's
executions (``XLA Modules`` events ``jit_<args.program>``) whose middle lies
inside the traced window.

The time is the whole execution's, and not the time of the operations under
the program's ``args.scope`` (``loop_pass``): the compiler streams the
matrices from HBM through copies of its own (``copy-start``/``copy-done``,
``slice-start``/``slice-done`` and a custom call into on-chip memory) that
carry no scope path, and the products under the scope read them from
on-chip memory; those copies run beside the paged read's kernel, so no
kernel's time can be taken out either. The time holds everything else the
step does (the paged read, the head, the token's selection), so the share
stays at or under 100 % however the compiler places the copies.

The tokens are those seated rows decoded inside the window. The family
counts what they cost (``families/<family>.py::<args.counts>(config, ticks,
tokens)`` -> operations and bytes), taking the number of passes from the
configuration and not from the program: a program that skipped a pass would
read over 100 %. Each over the chip's peak, the larger is the least time,
and the bound is printed. Silent where the trace holds no operation under
the scope in the program (a stack that does not loop)."""

import sys

from chipbench import families, flops, trace_reduce
from chipbench.readers import program_trace, ssm_roofline


def executions_in_window(records, program_name: str) -> list:
    """``(start, end)`` of the program's executions whose middle lies inside
    the traced window."""
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    return [(a, b) for name, a, b in trace.modules.get(min(trace.ops), [])
            if name.startswith(f'jit_{program_name}')
            and start <= (a + b) / 2 <= end]


def looped(records, scope: str, program_name: str) -> bool:
    program = program_trace.of(records)
    if program is None or not program.scoped:
        return False
    start, end = trace_reduce.window_of(records['trace'])
    return any(scope in parts and program_name in parts
               for parts in (program_trace.components(path) for _, _, _, path
                             in program_trace.scoped_in(program, start, end)))


def read(records, spec):
    if not records.get('traced_window') or not records['trace'].ops:
        return None
    args = spec['args']
    if not looped(records, args['scope'], args['program']):
        return None
    runs = executions_in_window(records, args['program'])
    spent = sum(b - a for a, b in trace_reduce.merged(runs))
    tokens = ssm_roofline.tokens_in_window(records, 'decoded')
    if not spent or not tokens:
        return None
    config = records['config']
    ops, moved = getattr(families.of(config), args['counts'])(
        config, len(runs), tokens)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'{spec["name"]}: bound by {bound}; {len(runs)} ticks, {tokens} '
          f'tokens decoded, {moved / 1e9:.3f} GB, {ops / 1e12:.3f} TFLOP, '
          f'least {least:.4f} s, {args["program"]} executions {spent:.4f} s',
          file=sys.stderr)
    return 100.0 * least / spent
