"""What the program itself wrote into the trace: its ``tpusystem.*`` host
spans (``tpusystem.observe.profile.annotate``) with their stats, and the
``jax.named_scope`` path of every device operation. ``trace_reduce.read``
keeps neither (it keeps the benchmark's own ``chipbench.*`` spans and cuts
an instruction's text down to a short name), so the readers of the
program's spans and scopes open the same ``.xplane.pb`` once more, here,
once a run.

Where a v5e trace carries the scope (read by hand, PR 24). Not on the
event: an ``XLA Ops`` event's name is the instruction's text with no
``metadata={op_name=...}`` tail, and its stats are three device times.
The profiler writes the path once per instruction, as the ``tf_op`` stat
of the event's *metadata* record (``XEventMetadata``), in the form
``jit(multi)/while/body/closed_call/transpose(jvp(loss))/loss_head/
dot_general:`` (the path, a colon, an operation type that is empty here).
``jax.profiler.ProfileData`` does not hand out metadata stats, so
``metadata_scopes`` reads that one table from the file's bytes with a
few lines of protobuf wire format, skipping the event lines unparsed; the
events themselves still come through ``ProfileData``. A Pallas kernel
reads ``.../GPT2/h_3/attn/pallas_call:``; its backward
``.../transpose(jvp(model))/GPT2/h_3/attn/pallas_call:``. A fusion carries
the path of one of its instructions only: what XLA fused into it from
another scope is counted with it. About a tenth of the fusions (and every
``copy-start``/``copy-done``) have no ``tf_op``: their path is ``''``.
The path's components are scope names, some wrapped by the transformation
that made the operation; ``components`` peels the wrappers off.

A program with no such span or scope (the parent of PR 24) gives no span
and no path with the scope in it, and every reader on top returns ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import sys
import time

from chipbench import trace_reduce

SPAN_PREFIX = 'tpusystem.'
SCOPE_STAT = 'tf_op'
_WRAPPED = re.compile(r'^(?:\w+\()+|\)+$')


@dataclasses.dataclass
class ProgramTrace:
    """``spans``: ``(name, start, end, stats)`` for every ``tpusystem.*``
    host span, seconds on the trace's clock (the clock of
    ``records['trace']``). ``scoped``: ``(short name, start, end, scope
    path)`` for the first chip's innermost operations; the path is ``''``
    where the trace names none."""
    spans: list
    scoped: list


@functools.lru_cache(maxsize=None)
def components(path: str) -> tuple:
    """``'jit(multi)/transpose(jvp(loss))/loss_head/mul:'`` ->
    ``('multi', 'loss', 'loss_head', 'mul')``. Cached: a trace holds
    hundreds of thousands of events and a few thousand paths."""
    return tuple(_WRAPPED.sub('', part)
                 for part in path.rstrip(':').split('/') if part)


# ------------------------------------------- protobuf wire format, read-only

def _varint(data, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(data):
    """``(field number, value)`` for each field of one protobuf message:
    an int for a varint, a ``memoryview`` for a length-delimited field;
    fixed-width fields are skipped."""
    at, end = 0, len(data)
    while at < end:
        key, at = _varint(data, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(data, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(data, at)
            yield number, data[at:at + size]
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f'wire type {wire} in an .xplane.pb')


def _entry(data) -> tuple:
    """One entry of a protobuf map: ``(key, value bytes)``."""
    found = dict(fields(data))
    return found.get(1, 0), found.get(2, b'')


def metadata_scopes(data) -> dict:
    """``{instruction text: scope path}`` from the first chip's plane of
    a serialized ``XSpace``: ``XSpace.planes = 1``; ``XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5`` (maps: key 1, value 2);
    ``XEventMetadata.name = 2, .stats = 5``; ``XStatMetadata.name = 2``;
    ``XStat.metadata_id = 1, .str_value = 5, .ref_value = 7`` (a
    reference to another stat metadata's name)."""
    planes = []
    for number, plane in fields(memoryview(data)):
        if number != 1:
            continue
        name = next((bytes(value).decode() for field, value in fields(plane)
                     if field == 2), '')
        device = trace_reduce.DEVICE_PLANE.match(name)
        if device:
            planes.append((int(device.group(1)), plane))
    if not planes:
        return {}
    _, plane = min(planes, key=lambda found: found[0])
    stat_names, events = {}, []
    for number, value in fields(plane):
        if number == 5:
            key, meta = _entry(value)
            stat_names[key] = next(
                (bytes(text).decode() for field, text in fields(meta)
                 if field == 2), '')
        elif number == 4:
            events.append(_entry(value)[1])
    wanted = {key for key, name in stat_names.items() if name == SCOPE_STAT}
    scopes = {}
    for meta in events:
        text, scope = '', ''
        for number, value in fields(meta):
            if number == 2:
                text = bytes(value).decode()
            elif number == 5:
                stat = dict(fields(value))
                if stat.get(1) in wanted:
                    scope = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7), ''))
        if scope:
            scopes[text] = scope
    return scopes


# ------------------------------------------------------------------ loading

@functools.lru_cache(maxsize=2)
def _load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    began = time.perf_counter()
    with open(path, 'rb') as handle:
        data = handle.read()
    scopes = metadata_scopes(data)
    spans, scoped, first = [], [], None
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (event.name, event.start_ns * 1e-9,
                     (event.start_ns + event.duration_ns) * 1e-9,
                     dict(event.stats))
                    for event in line.events
                    if event.name.startswith(SPAN_PREFIX))
        elif device and (first is None or int(device.group(1)) < first):
            first, scoped = int(device.group(1)), []
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    scoped.extend(
                        (trace_reduce.short_name(event.name),
                         event.start_ns * 1e-9,
                         (event.start_ns + event.duration_ns) * 1e-9,
                         scopes.get(event.name, ''))
                        for event in line.events)
    # trace_reduce's arithmetic reads an event's first three fields by
    # position, so it takes these four-field events as they are
    program = ProgramTrace(sorted(spans, key=lambda span: span[1]),
                           trace_reduce.innermost(scoped))
    pathed = sum(bool(event[3]) for event in program.scoped)
    print(f'program trace: {len(data)} bytes of .xplane.pb, '
          f'{len(program.spans)} {SPAN_PREFIX}* spans, {len(program.scoped)} '
          f'innermost operations, {pathed} of them with a scope path; read '
          f'in {time.perf_counter() - began:.1f} s', file=sys.stderr)
    return program


def of(records: dict) -> ProgramTrace | None:
    """The run's program trace: ``records['program_trace']`` where a test
    put one, else read from the newest ``.xplane.pb`` under
    ``records['trace_dir']``; ``None`` where there is no trace to read."""
    if 'program_trace' in records:
        return records['program_trace']
    try:
        path = trace_reduce.newest_xplane(records['trace_dir'])
    except (KeyError, FileNotFoundError):
        return None
    return _load(str(path))


# ------------------------------------------------- shared by the readers

def named(program: ProgramTrace, name: str) -> list:
    """The spans called ``name`` as ``(name, start, end)``."""
    return [span[:3] for span in program.spans if span[0] == name]


def scoped_in(program: ProgramTrace, start: float, end: float) -> list:
    """The scoped operations cut to ``[start, end]`` (``trace_reduce.clip``
    for events that carry their path)."""
    return [(name, max(a, start), min(b, end), path)
            for name, a, b, path in program.scoped if b > start and a < end]


def module_gaps(records: dict) -> tuple[list, float, float]:
    """The stretches of the traced window in which no program (``XLA
    Modules`` event) ran on the first chip, with the window's two ends."""
    trace = records['trace']
    start, end = trace_reduce.window_of(trace)
    modules = trace.modules[min(trace.modules)]
    return trace_reduce.idle_gaps(modules, start, end), start, end


def overlap(gaps: list, spans: list) -> float:
    """Seconds of ``gaps`` that lie inside any of ``spans``."""
    cover = trace_reduce.merged((a, b) for _, a, b in spans)
    return sum(max(0.0, min(hi, b) - max(lo, a))
               for lo, hi in gaps for a, b in cover)
