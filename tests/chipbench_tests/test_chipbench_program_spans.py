"""The readers of the program's own spans and scopes, on a synthetic trace
and synthetic program spans: no chip, no profiler."""

from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import trace_reduce
from chipbench.readers import (flash_roofline, flash_split, module_gap,
                               program_trace, scope_share, span_gap,
                               tracer_span)
from chipbench.readers.program_trace import ProgramTrace

ROOT = pathlib.Path(__file__).resolve().parents[2]
TICK, DISPATCH = 'tpusystem.serve.tick', 'tpusystem.engine.dispatch'
READ, ROWS = 'tpusystem.engine.read', 'tpusystem.engine.rows'
NARRATE = 'tpusystem.service.narrate'
NEW = ['host_gap_share.serve', 'host_gap_share.train',
       'tick_gap_ms.dispatch', 'tick_gap_ms.read', 'tick_gap_ms.rows',
       'tick_gap_ms.narrate', 'admit_ms', 'scope_share.kv_read',
       'scope_share.select', 'scope_share.loss', 'scope_share.optimizer',
       'flash_fwd_roofline.train', 'flash_bwd_roofline.train']


def spec(metric: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{metric}.json').read_text())


def serving() -> dict:
    """A 10 s window, two ticks. Programs run over 1-4 and 6-9 s, so the
    chip waits over 0-1, 4-6 and 9-10 s. Tick one is 0.5-5 s, tick two
    5-9.5 s: 0-0.5 and 9.5-10 s lie outside any tick."""
    trace = trace_reduce.Trace(
        ops={0: [('fusion.1 bf16[8]', 1.0, 2.0), ('fusion.2 bf16[8]', 3.0, 4.0),
                 ('fusion.3 bf16[8]', 6.0, 9.0)]},
        modules={0: [('jit_step_fn(1)', 1.0, 4.0), ('jit_step_fn(1)', 6.0, 9.0)]},
        host=[('chipbench.window', 0.0, 10.0)])
    spans = [(TICK, 0.5, 5.0, {'step': 1, 'clock': 100.5}),
             (DISPATCH, 0.5, 1.5, {}),          # 0.5 s of it before the program
             (READ, 1.5, 4.25, {}),             # 0.25 s after it
             (ROWS, 4.25, 4.5, {}),             # all idle
             (NARRATE, 4.5, 5.0, {}),           # all idle
             (TICK, 5.0, 9.5, {'step': 2, 'clock': 105.0002}),
             (DISPATCH, 5.5, 6.5, {}),          # 0.5 s idle; 5-5.5 is the tick's
             (READ, 6.5, 9.0, {}),              # no gap at all
             (ROWS, 9.0, 9.25, {})]
    return {'trace': trace, 'program_trace': ProgramTrace(spans, [])}


# --------------------------------------------------------------- module_gap

def test_module_gap_counts_only_idle_time_inside_the_span():
    records = serving()
    # idle 0.5-1, 4-5 in tick one and 5-6, 9-9.5 in tick two: 3 of 10 s
    assert module_gap.read(records, spec('host_gap_share.serve')) == (
        pytest.approx(30.0))
    # and it cannot pass the device's idle share (5 of 10 s have no op)
    idle = 100.0 * (1 - trace_reduce.device_summary(records['trace'])['busy_s']
                    / 10.0)
    assert idle == pytest.approx(50.0)


def test_module_gap_prints_the_clock_offset(capsys):
    module_gap.read(serving(), spec('host_gap_share.serve'))
    said = capsys.readouterr().err
    assert 'trace clock - program clock = -100.000' in said
    assert 'over 2 spans, spread 0.200 ms' in said


@pytest.mark.parametrize('metric', NEW)
def test_no_program_span_or_scope_reads_none(metric):
    """The parent of PR 24: a trace with the benchmark's spans alone."""
    records = serving()
    records['program_trace'] = ProgramTrace([], [])
    records.update(spans=[], traced_window=(0.0, 10.0))
    entry = spec(metric)
    reader = {'module_gap': module_gap, 'span_gap': span_gap,
              'tracer_span': tracer_span, 'scope_share': scope_share,
              'flash_split': flash_split}[entry['reader']]
    assert reader.read(records, entry) is None


def test_no_trace_directory_reads_none():
    records = serving()
    del records['program_trace']
    assert program_trace.of(records) is None
    assert module_gap.read(records, spec('host_gap_share.serve')) is None
    records['trace_dir'] = ROOT / 'chipbench' / 'metrics'   # holds no trace
    assert program_trace.of(records) is None


# ----------------------------------------------------------------- span_gap

@pytest.mark.parametrize('metric, per_tick_ms', [
    ('tick_gap_ms.dispatch', 500.0),      # 0.5 + 0.5 s over two ticks
    ('tick_gap_ms.read', 125.0),          # 0.25 s
    ('tick_gap_ms.rows', 250.0),          # 0.25 + 0.25 s
    ('tick_gap_ms.narrate', 250.0)])      # 0.5 s
def test_span_gap_splits_the_gaps_between_the_innermost_spans(metric,
                                                              per_tick_ms):
    assert span_gap.read(serving(), spec(metric)) == pytest.approx(per_tick_ms)


def test_span_gaps_sum_to_no_more_than_the_host_gap():
    records = serving()
    parts = sum(span_gap.read(records, spec(f'tick_gap_ms.{name}'))
                for name in ('dispatch', 'read', 'rows', 'narrate'))
    whole = module_gap.read(records, spec('host_gap_share.serve'))
    # 2.25 s in the four; the tick itself keeps 5-5.5 and 9.25-9.5 s
    assert parts * 2 / 1e3 == pytest.approx(2.25)
    assert parts * 2 / 1e3 <= whole / 100.0 * 10.0


def test_a_span_with_no_gap_reads_zero_not_none():
    records = serving()
    records['program_trace'].spans[:] = [
        span for span in records['program_trace'].spans
        if span[0] != READ or span[1] > 5.0]       # the second read alone
    assert span_gap.read(records, spec('tick_gap_ms.read')) == 0.0


# -------------------------------------------------------------- tracer_span

def test_tracer_span_is_the_median_of_the_named_closed_spans_in_the_window():
    event = lambda name, ts, dur, **args: {
        'name': name, 'ph': 'X', 'ts': ts * 1e6, 'dur': dur * 1e3,
        'args': args}
    records = {'traced_window': (10.0, 20.0), 'spans': [
        event('admit', 11.0, 20.0), event('admit', 12.0, 30.0),
        event('admit', 13.0, 70.0), event('queued', 11.0, 500.0),
        event('admit', 9.0, 1.0),                  # before the window
        event('admit', 19.0, 2.0, open=True)]}     # never closed
    assert tracer_span.read(records, spec('admit_ms')) == pytest.approx(30.0)
    assert tracer_span.read({'spans': records['spans']},
                            spec('admit_ms')) is None


# -------------------------------------------------------------- scope_share

def scoped_records() -> dict:
    records = serving()
    path = 'jit(step_fn)/jit(main)/'
    records['program_trace'] = ProgramTrace([], [
        ('fusion.1 bf16[8]', 1.0, 2.0, path + 'kv_read/gather'),
        ('fusion.2 bf16[8]', 3.0, 4.0, path + 'select/sort'),
        ('fusion.3 bf16[8]', 6.0, 9.0, path + 'cond/branch_1_fun/kv_read/mul')])
    return records


def test_scope_share_is_the_scopes_seconds_over_busy_seconds():
    records = scoped_records()
    assert scope_share.read(records, spec('scope_share.kv_read')) == (
        pytest.approx(80.0))
    assert scope_share.read(records, spec('scope_share.select')) == (
        pytest.approx(20.0))
    assert scope_share.read(records, spec('scope_share.loss')) is None


def test_components_peel_the_transformations_off():
    assert program_trace.components(
        'jit(multi)/while/body/transpose(jvp(loss))/loss_head/mul:') == (
            'multi', 'while', 'body', 'loss', 'loss_head', 'mul')
    backward = spec('scope_share.loss')
    records = scoped_records()
    records['program_trace'].scoped[0] = (
        'fusion.1 bf16[8]', 1.0, 2.0,
        'jit(multi)/transpose(jvp(loss))/loss_head/dot_general')
    assert scope_share.read(records, backward) == pytest.approx(20.0)


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _field(number: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_scope_paths_are_read_from_the_event_metadata_of_the_first_chip():
    """A serialized ``XSpace`` written by hand, as the v5e profiler lays it
    out: the path is the ``tf_op`` stat of an instruction's metadata record,
    a string of its own or a reference to another stat's name."""
    entry = lambda key, value: _field(1, key) + _field(2, value)
    stat_names = {1: 'hlo_category', 2: 'tf_op', 300: 'jit(f)/head/dot:'}
    sort = ('%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop')
    events = {
        11: _field(1, 11) + _field(2, sort) + _field(4, 'fusion.7')
        + _field(5, _field(1, 1) + _field(5, 'loop fusion'))
        + _field(5, _field(1, 2) + _field(5, 'jit(step_fn)/select/sort:')),
        12: _field(2, '%dot.1 = f32[8]{0} dot(%a, %b)')
        + _field(5, _field(1, 2) + _field(7, 300)),
        13: _field(2, '%copy.1 = bf16[8]{0} copy(%p)')
        + _field(5, _field(1, 1) + _field(5, 'copy'))}
    plane = lambda name, lines=b'': (
        _field(1, 7) + _field(2, name) + _field(3, lines)
        + b''.join(_field(4, entry(key, value))
                   for key, value in events.items())
        + b''.join(_field(5, entry(key, _field(1, key) + _field(2, name_)))
                   for key, name_ in stat_names.items()))
    line = _field(2, 'XLA Ops') + _field(4, _field(1, 11) + _field(2, 5))
    space = (_field(1, _field(2, '/host:CPU'))
             + _field(1, plane('/device:TPU:1').replace(b'select', b'other!'))
             + _field(1, plane('/device:TPU:0', line)) + _field(4, 'host'))
    assert program_trace.metadata_scopes(space) == {
        sort: 'jit(step_fn)/select/sort:',
        '%dot.1 = f32[8]{0} dot(%a, %b)': 'jit(f)/head/dot:'}
    assert program_trace.metadata_scopes(_field(1, _field(2, '/host:CPU'))) == {}
    fixed = _varint(3 << 3 | 1) + bytes(8) + _varint(4 << 3 | 5) + bytes(4)
    assert list(program_trace.fields(memoryview(fixed + _field(9, 300)))) == [
        (9, 300)]


def test_the_reductions_arithmetic_takes_events_that_carry_their_path():
    loop = ('while.1 s32[]', 0.0, 10.0, 'jit(multi)/while')
    leaf = ('fusion.1 bf16[8]', 1.0, 2.0, 'jit(multi)/while/body/loss/mul')
    assert trace_reduce.innermost([loop, leaf]) == [leaf]
    assert trace_reduce.matching([loop, leaf], ['^fusion']) == [leaf]
    assert program_trace.scoped_in(ProgramTrace([], [leaf]), 1.5, 9.0) == [
        ('fusion.1 bf16[8]', 1.5, 2.0, 'jit(multi)/while/body/loss/mul')]


# -------------------------------------------------------------- flash_split

def flash_records() -> dict:
    config = json.loads((ROOT / 'chipbench' / 'configs'
                         / 'gpt2-medium.json').read_text())
    mix = json.loads((ROOT / 'chipbench' / 'traffic'
                      / 'pretrain-b8-s1024.json').read_text())
    forward = 'jit(multi)/while/body/jvp(model)/GPT2/h_0/attn/pallas_call'
    backward = ('jit(multi)/while/body/transpose(jvp(model))/GPT2/h_0/attn/'
                'pallas_call')
    kernels = [('attn.1 [tpu_custom_call]', 1.0, 1.002, forward),
               ('attn.2 [tpu_custom_call]', 2.0, 2.004, backward),
               ('attn.3 [tpu_custom_call]', 3.0, 3.003, backward),
               ('fusion.9 bf16[8]', 4.0, 5.0, forward)]
    trace = trace_reduce.Trace(
        ops={0: [event[:3] for event in kernels]}, modules={},
        host=[('chipbench.window', 0.0, 10.0)])
    return {'trace': trace, 'program_trace': ProgramTrace([], kernels),
            'config': config, 'traffic': mix, 'device_kind': 'TPU v5 lite',
            'traced': {'steps': 1}}


def test_forward_and_backward_flash_seconds_sum_to_the_pooled_readers(capsys):
    records = flash_records()
    pooled_spec = spec('flash_roofline.train')
    pooled = flash_roofline.read(records, pooled_spec)
    forward = flash_split.read(records, spec('flash_fwd_roofline.train'))
    backward = flash_split.read(records, spec('flash_bwd_roofline.train'))
    said = capsys.readouterr().err
    assert 'forward 0.0020 + backward 0.0070 = 0.0090 s' in said
    assert 'kernels 0.0090 s' in said                  # the pooled reader's
    # least seconds are shares x seconds: the two sides' add up to the pool's
    least = lambda share, seconds: share / 100.0 * seconds
    assert least(forward, 0.002) + least(backward, 0.007) == pytest.approx(
        least(pooled, 0.009))
    assert min(forward, backward) < pooled < max(forward, backward)
    assert spec('flash_fwd_roofline.train')['args']['kernel_patterns'] == (
        pooled_spec['args']['kernel_patterns'])


def test_a_kernel_that_names_no_scope_cannot_be_put_on_a_side():
    records = flash_records()
    records['program_trace'].scoped[1] = ('attn.2 [tpu_custom_call]', 2.0,
                                          2.004, '')
    assert flash_split.read(records, spec('flash_bwd_roofline.train')) is None


# --------------------------------------------------- the real file, on a CPU

def test_program_trace_reads_the_host_spans_of_a_real_trace(tmp_path):
    """A CPU trace has no device plane: the spans and their stats come
    back in the trace's seconds, the scoped list is empty, and the readers
    that need the device say None."""
    import jax
    import jax.numpy as jnp
    from tpusystem.observe.profile import annotate
    jax.profiler.start_trace(str(tmp_path))
    with annotate('tpusystem.serve.tick', step=7, clock=12.5):
        with annotate('tpusystem.engine.read'):
            jnp.zeros(()).block_until_ready()
    with annotate('chipbench.step'):
        pass
    jax.profiler.stop_trace()
    program = program_trace.of({'trace_dir': tmp_path})
    assert [span[0] for span in program.spans] == [
        'tpusystem.serve.tick', 'tpusystem.engine.read']
    tick, read = program.spans
    assert tick[3] == {'step': 7, 'clock': 12.5}
    assert tick[1] <= read[1] <= read[2] <= tick[2] < tick[1] + 60.0
    assert program.scoped == []
    records = {'trace_dir': tmp_path, 'trace': trace_reduce.read(tmp_path)}
    assert module_gap.read(records, spec('host_gap_share.serve')) is None
    assert scope_share.read(records, spec('scope_share.select')) is None
