"""A state-space scope's share of its roofline: the least time the chip
could take for the state-space work of the tokens that went through it
inside the traced window, over the device time under the program's
``args.scope`` in the program named ``args.program``.

``args.tokens`` says which tokens: ``decoded`` — every token a seated row
decoded inside the window (each one recurrence step a state-space layer:
scope ``ssm_update`` in ``step_fn``, the decode program) — or ``prefilled`` —
the prompt tokens, at their true lengths and not their buckets', of the
requests whose first token came inside it (scope ``ssm_scan`` in ``run``,
the prefill programs: both kinds of program carry the layer's scope names,
and the program's name in the scope path tells them apart). The family
counts what they cost (``families/<family>.py::<args.counts>(config,
tokens)`` -> operations and bytes over all its state-space layers); each
over the chip's peak, the larger is the least time, and the bound is
printed. Defined on the scope and the tokens, so it reads the same work
whatever implements it later; only tokens of seated rows count, never a
parked row's update or a bucket's padding, so it cannot pass 100 %. Silent
where the trace holds no operation under the scope."""

import sys

from chipbench import families, flops
from chipbench.readers import latent_read_roofline


def tokens_in_window(records, kind: str) -> int:
    lo, hi = records['traced_window']
    if kind == 'decoded':
        return sum(lo <= moment < hi for request in records['requests']
                   for moment in request['times'][1:])
    if kind == 'prefilled':
        return sum(request['prompt'] for request in records['requests']
                   if request['times'] and lo <= request['times'][0] < hi)
    raise ValueError(f'args.tokens is {kind!r}: decoded or prefilled')


def read(records, spec):
    if not records.get('traced_window'):
        return None
    args = spec['args']
    spent = latent_read_roofline.scope_seconds(records, args['scope'],
                                               args['program'])
    tokens = tokens_in_window(records, args['tokens'])
    if not spent or not tokens:
        return None
    config = records['config']
    ops, moved = getattr(families.of(config), args['counts'])(config, tokens)
    least, bound = flops.roofline_seconds(
        ops, moved, flops.peaks(records['device_kind']))
    print(f'{spec["name"]}: bound by {bound}; {tokens} tokens '
          f'{args["tokens"]}, {moved / 1e9:.3f} GB, {ops / 1e12:.3f} TFLOP, '
          f'least {least:.4f} s, under {args["scope"]} in {args["program"]} '
          f'{spent:.4f} s', file=sys.stderr)
    return 100.0 * least / spent
