"""From a profiler trace to numbers: busy union, idle gaps, kernel sums.

``jax.profiler`` writes an ``.xplane.pb``; ``read`` turns it into plain
tuples and everything below works on those, so the arithmetic is tested on
a synthetic trace with no chip. Times are seconds on the trace's own clock.

What a v5e trace looks like (read by hand, PR 23): one plane per chip named
``/device:TPU:<n>``. Its line ``XLA Modules`` holds one event per executed
program (``jit_<name>(<fingerprint>)``). Its line ``XLA Ops`` holds one
event per executed HLO instruction, named by the instruction's whole text
(``%fusion.5302 = bf16[50304,1024]{...} fusion(...)``); a Pallas kernel is
a ``custom-call`` whose text carries ``custom_call_target="tpu_custom_call"``
and whose instruction is named after the flax scope it ran in
(``%attn.649``); loops are events too (``%while.86``) and contain the events
of their bodies, so sums and unions are taken over the innermost events.
Host threads are lines of the plane ``/host:CPU``; several share the name
``python``, one of them the main thread with the ``TraceAnnotation`` spans.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'
WINDOW_SPAN = 'chipbench.window'
SPAN_PREFIX = 'chipbench.'


@dataclasses.dataclass
class Trace:
    """One run's trace as plain data. ``ops`` and ``modules`` map a chip's
    index to ``(name, start, end)`` tuples; ``host`` holds the benchmark's
    own annotation spans from every host thread."""
    ops: dict
    modules: dict
    host: list


_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_RESULT = re.compile(r'^\(?([a-z0-9]+\[[0-9,]*\])')


@functools.lru_cache(maxsize=None)
def short_name(text: str) -> str:
    """An HLO instruction's text as a name to match and to print:
    ``fusion.5302 bf16[50304,1024]``, ``attn.649 [tpu_custom_call]``."""
    name, _, rest = text.partition(' = ')
    name = name.lstrip('%')
    target = _TARGET.search(rest)
    if target:
        return f'{name} [{target.group(1)}]'
    result = _RESULT.match(rest)
    return f'{name} {result.group(1)}' if result else name


def innermost(events) -> list:
    """The events that contain no other event of the same line: a loop's
    event covers its body's, and only the body's are work."""
    ordered = sorted(events, key=lambda event: (event[1], -event[2]))
    leaves = []
    for index, event in enumerate(ordered):
        follows = ordered[index + 1] if index + 1 < len(ordered) else None
        if follows is None or follows[1] >= event[2] or follows[2] > event[2]:
            leaves.append(event)
    return leaves


def newest_xplane(directory) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob('*.xplane.pb'),
                   key=lambda path: path.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {directory}')
    return found[-1]


def read(directory) -> Trace:
    """Parse the newest trace under ``directory`` with nothing but JAX."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(newest_xplane(directory)))
    ops, modules, host = {}, {}, []
    for plane in profile.planes:
        device = DEVICE_PLANE.match(plane.name)
        if device:
            chip = int(device.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(chip, []).extend(
                        (event.name, event.start_ns * 1e-9,
                         (event.start_ns + event.duration_ns) * 1e-9)
                        for event in line.events)
                elif line.name == OPS_LINE:
                    ops.setdefault(chip, []).extend(innermost([
                        (short_name(event.name), event.start_ns * 1e-9,
                         (event.start_ns + event.duration_ns) * 1e-9)
                        for event in line.events]))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (event.name, event.start_ns * 1e-9,
                     (event.start_ns + event.duration_ns) * 1e-9)
                    for event in line.events
                    if event.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, host)


# ------------------------------------------------------------- arithmetic

def clip(events, start: float, end: float) -> list:
    """Events cut to ``[start, end]``; those wholly outside are dropped."""
    return [(name, max(a, start), min(b, end)) for name, a, b in events
            if b > start and a < end]


def merged(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same time."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(events) -> float:
    """Seconds in which at least one of ``events`` ran."""
    return sum(b - a for a, b in merged((a, b) for _, a, b in events))


def idle_gaps(events, start: float, end: float) -> list:
    """The ``(start, end)`` stretches of the window with nothing running."""
    gaps, cursor = [], start
    for a, b in merged((a, b) for _, a, b in clip(events, start, end)):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if end > cursor:
        gaps.append((cursor, end))
    return gaps


def kernel_seconds(events, patterns) -> float:
    """Summed device time of the events whose name matches any pattern."""
    return sum(b - a for _, a, b in matching(events, patterns))


def matching(events, patterns) -> list:
    """The events whose name matches any of the patterns."""
    compiled = [re.compile(pattern) for pattern in patterns]
    return [event for event in events
            if any(pattern.search(event[0]) for pattern in compiled)]


_NUMBER = re.compile(r'\.\d+(?= |$)')


def top_ops(events, count: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations that took most time,
    instances of one kind and result shape summed (``fusion.5302
    bf16[8,1024,4096]`` and its twins in the other layers are one row)."""
    totals: dict = {}
    for name, a, b in events:
        kind = _NUMBER.sub('', name, count=1)
        totals[kind] = totals.get(kind, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda item: -item[1])
    return [[name, seconds] for name, seconds in ranked[:count]]


def attribute_gaps(gaps, host_spans, count: int = 10) -> list:
    """``[[name, seconds], ...]``: idle time by what the host was doing.
    Each gap's time goes to the benchmark's annotation spans that overlap
    it — the shortest span wins where spans nest, ``chipbench.window``
    only where nothing else does — and the rest is ``unattributed``.

    A serving trace holds some 400 000 gaps and some hundreds of spans, so
    a gap is shown only the spans that can overlap it: of the spans in
    order of their start, those that start before the gap ends and lie
    past the first one whose end (or an earlier span's) reaches the gap.
    Within a gap they are taken shortest first, as before."""
    totals: dict = {}
    ranked_spans = sorted((span for span in host_spans
                           if span[0] != WINDOW_SPAN),
                          key=lambda span: span[2] - span[1])
    by_start = sorted(enumerate(ranked_spans),
                      key=lambda ranked: ranked[1][1])
    starts = [span[1] for _, span in by_start]
    reach, furthest = [], float('-inf')     # the latest end up to each span
    for _, span in by_start:
        furthest = max(furthest, span[2])
        reach.append(furthest)
    for start, end in gaps:
        near = by_start[bisect.bisect_right(reach, start):
                        bisect.bisect_left(starts, end)]
        free = [(start, end)]
        for _, (name, a, b) in sorted(near):
            rest = []
            for lo, hi in free:
                cut_lo, cut_hi = max(lo, a), min(hi, b)
                if cut_hi <= cut_lo:
                    rest.append((lo, hi))
                    continue
                totals[name] = totals.get(name, 0.0) + (cut_hi - cut_lo)
                if lo < cut_lo:
                    rest.append((lo, cut_lo))
                if cut_hi < hi:
                    rest.append((cut_hi, hi))
            free = rest
        left = sum(hi - lo for lo, hi in free)
        if left > 0:
            totals['unattributed'] = totals.get('unattributed', 0.0) + left
    ranked = sorted(totals.items(), key=lambda item: -item[1])
    return [[name, seconds] for name, seconds in ranked[:count]]


def window_of(trace: Trace) -> tuple[float, float]:
    """The traced window: the ``chipbench.window`` span where the driver
    wrote one, else the extent of the device's operations."""
    spans = [(a, b) for name, a, b in trace.host if name == WINDOW_SPAN]
    if spans:
        return min(a for a, _ in spans), max(b for _, b in spans)
    every = [event for events in trace.ops.values() for event in events]
    if not every:
        raise ValueError('the trace holds no device operation')
    return min(a for _, a, _ in every), max(b for _, _, b in every)


def device_summary(trace: Trace) -> dict:
    """``busy_s`` (mean over the chips), ``window_s`` and the breakdown the
    result line carries."""
    start, end = window_of(trace)
    if not trace.ops:
        raise ValueError('the trace holds no device plane')
    busy = [busy_seconds(clip(events, start, end))
            for events in trace.ops.values()]
    first = trace.ops[min(trace.ops)]
    inside = clip(first, start, end)
    gaps = idle_gaps(first, start, end)
    return {'busy_s': sum(busy) / len(busy), 'window_s': end - start,
            'breakdown': {'device_ops': top_ops(inside),
                          'idle_gaps': attribute_gaps(gaps, trace.host)}}
