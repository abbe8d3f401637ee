"""The latent read's share of its roofline: the least time the chip could
take to attend the latent rows that the decode steps of the traced window
had to read, over the device time those steps spent under the program's
``args.scope`` (``kv_read``: the attention over the latent pool, its gather
included) in the program named ``args.program`` (the decode step, not the
prefill programs, which carry the same scope names).

Positions attended: for every token decoded inside the window, the positions
its row held (prompt + position, as ``step_mfu`` walks the requests). The
family counts what one of them costs
(``families/<family>.py::latent_read_ops_and_bytes``: bytes of one latent row
a layer, operations of the absorbed score and mix); each over the chip's
peak, the larger is the least time, and the bound is printed. Defined on the
scope and the positions, so it reads the same work whatever implements the
read later; only positions a row holds count, never the padded window, so a
read that skips what it need not touch cannot pass 100 %. Silent where the
trace holds no operation under the scope."""

import bisect
import re
import sys

from chipbench import families, flops, trace_reduce
from chipbench.readers import program_trace


def scope_seconds(records, scope: str, program_name: str, kernels=()):
    """Device seconds of the first chip's operations under ``scope`` in
    the program ``program_name``, inside the traced window; ``None`` where
    there is no trace or no such operation. ``kernels`` are patterns of
    operations the compiler leaves without a scope path (it names the Mosaic
    call a grouped product lowers to ``ragged-dot-none`` and drops the
    path): those count where they ran inside one of the program's own
    executions (its ``XLA Modules`` events, ``jit_<program_name>``)."""
    program = program_trace.of(records)
    if program is None or not program.scoped:
        return None
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    runs = trace_reduce.merged(
        (a, b) for name, a, b in trace.modules.get(min(trace.ops), [])
        if name.startswith(f'jit_{program_name}')) if trace.ops else []
    begins = [a for a, _ in runs]

    def in_program(a: float, b: float) -> bool:
        at = bisect.bisect_right(begins, (a + b) / 2) - 1
        return at >= 0 and (a + b) / 2 <= runs[at][1]

    spent = 0.0
    for name, a, b, path in program_trace.scoped_in(program, start, end):
        parts = program_trace.components(path)
        if (scope in parts and program_name in parts) or (
                any(re.search(pattern, name) for pattern in kernels)
                and in_program(a, b)):
            spent += b - a
    return spent or None


def attended_positions(records) -> int:
    lo, hi = records['traced_window']
    return sum(request['prompt'] + position
               for request in records['requests']
               for position, moment in enumerate(request['times'])
               if position and lo <= moment < hi)


def read(records, spec):
    if not records.get('traced_window'):
        return None
    args = spec['args']
    spent = scope_seconds(records, args['scope'], args['program'])
    attended = attended_positions(records)
    if not spent or not attended:
        return None
    config = records['config']
    ops, moved = families.of(config).latent_read_ops_and_bytes(config,
                                                              attended)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'{spec["name"]}: bound by {bound}; {attended} positions attended, '
          f'{moved / 1e9:.3f} GB, {ops / 1e12:.3f} TFLOP, least {least:.4f} s, '
          f'under {args["scope"]} {spent:.4f} s', file=sys.stderr)
    return 100.0 * least / spent
