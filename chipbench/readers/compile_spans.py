"""The program's own record of its set-up and its compiles: the
``observe.trace.Tracer`` spans ``compile.trace`` / ``compile.lower`` /
``compile.backend`` (one per stage JAX reports, ``args.fun`` naming the
function, ``args.cached`` on a backend compile that consulted the
persistent cache) and ``setup.engine`` (the serving engine's construction).

The window opens at ``records['traced_window'][0]`` and closes
``records['window_s']`` later; set-up is everything that ended before it.
A jitted function called inside another is traced inside the outer trace,
so seconds are the length of a union of intervals, never a sum.

``args.read`` says what to read:

- ``setup``: seconds in the union of the ``args.spans`` that ended before
  the window;
- ``self``: the length of ``args.span`` less the compile spans inside it;
- ``misses``: backend compiles before the window that missed the cache;
- ``window``: compiles begun inside the window, a trace nested in another
  trace counted with it.

None where the run has no compile span (the parent, or a run with no
``Tracer``), or for ``self`` no ``args.span``, or for ``misses`` no backend
compile that consulted the cache. ``setup`` also prints where its seconds
went, by function, to stderr, and ``window`` what compiled there.
"""

import sys

COMPILE = ('compile.trace', 'compile.lower', 'compile.backend')


def _interval(event) -> tuple[float, float]:
    start = event['ts'] * 1e-6
    return start, start + event['dur'] * 1e-6


def union(intervals) -> float:
    """Seconds covered by at least one of the ``(start, end)`` intervals."""
    covered, reach = 0.0, float('-inf')
    for start, end in sorted(intervals):
        if end > reach:
            covered += end - max(start, reach)
            reach = end
    return covered


def outermost(events) -> list:
    """The spans that lie inside no other of them."""
    kept, reach = [], float('-inf')
    for event in sorted(events, key=lambda event: (event['ts'],
                                                   -event['dur'])):
        end = _interval(event)[1]
        if end > reach:
            kept.append(event)
            reach = end
    return kept


def by_function(events, shown: int = 8) -> str:
    """The outermost spans' seconds by function (``jit(f)`` counted as
    ``f``), largest first."""
    spent: dict = {}
    for event in outermost(events):
        fun = event['args']['fun']
        if fun.startswith('jit(') and fun.endswith(')'):
            fun = fun[4:-1]
        spent[fun] = spent.get(fun, 0.0) + event['dur'] * 1e-6
    ranked = sorted(spent.items(), key=lambda item: -item[1])
    return ', '.join(f'{fun} {seconds:.2f}' for fun, seconds in
                     ranked[:shown]) + f'; {len(ranked)} functions'


def read(records, spec):
    window = records.get('traced_window')
    if not window:
        return None
    opened = window[0]
    closed = opened + records['window_s']
    spans = [event for event in records.get('spans', [])
             if event.get('ph') == 'X']
    compiles = [event for event in spans if event['name'] in COMPILE]
    if not compiles:
        return None
    args = spec['args']
    before = [event for event in compiles
              if _interval(event)[1] <= opened]
    if args['read'] == 'setup':
        chosen = [event for event in before if event['name'] in args['spans']]
        print(f'{spec["name"]}: by function, s: {by_function(chosen)}',
              file=sys.stderr)
        return union(map(_interval, chosen))
    if args['read'] == 'self':
        owner = [_interval(event) for event in spans
                 if event['name'] == args['span']]
        if not owner:
            return None
        start, end = owner[0]
        inside = [(max(start, lo), min(end, hi))
                  for lo, hi in map(_interval, compiles)
                  if lo < end and hi > start]
        return (end - start) - union(inside)
    if args['read'] == 'misses':
        consulted = [event for event in before
                     if event['name'] == 'compile.backend'
                     and 'cached' in event['args']]
        if not consulted:
            return None
        return sum(not event['args']['cached'] for event in consulted)
    if args['read'] == 'window':
        inside = [event for event in outermost(
            event for event in compiles if event['name'] == 'compile.trace')
            if opened <= _interval(event)[0] < closed]
        if inside:
            print(f'{spec["name"]}: {by_function(inside)}', file=sys.stderr)
        return len(inside)
    raise ValueError(f'{spec["name"]}: no reading {args["read"]!r}')
