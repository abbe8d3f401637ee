"""The readers of the program's compile and set-up spans
(``readers/compile_spans.py``) and of the idle gaps under the admission
path (``readers/span_gap.py`` over ``tick_gap_ms.admit`` / ``.prefill`` /
``.adopt`` / ``.tick``), on synthetic records: no chip, no profiler."""

from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import trace_reduce
from chipbench.readers import compile_spans, module_gap, span_gap
from chipbench.readers.program_trace import ProgramTrace

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
SERVING = ['serve-large-closed32', 'serve-dsv2-closed64',
           'serve-nemotron3-closed96']
COMPILE = ['setup_lower_s', 'setup_backend_s', 'setup_engine_s',
           'compile_cache_misses', 'window_compiles']
GAPS = ['tick_gap_ms.admit', 'tick_gap_ms.prefill', 'tick_gap_ms.adopt',
        'tick_gap_ms.tick']
# the five the benchmark had before, and these four: every innermost span
# of a tick
ALL_GAPS = ['tick_gap_ms.dispatch', 'tick_gap_ms.read', 'tick_gap_ms.rows',
            'tick_gap_ms.narrate', 'tick_gap_ms.seat', *GAPS]


def spec(metric: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{metric}.json').read_text())


def event(name: str, start: float, end: float, **args) -> dict:
    """One exported ``Tracer`` span, as ``Tracer.events`` writes it."""
    cat = 'compile' if name.startswith('compile.') else 'setup'
    return {'name': name, 'cat': cat, 'ph': 'X', 'ts': start * 1e6,
            'dur': (end - start) * 1e6, 'args': args}


def compiled() -> dict:
    """Set-up from 10 s, the window over 100-140 s (traced to 108 s).

    The engine is built over 10-30 s and traces, lowers and loads ``init``
    from the cache inside it (12-16 s). The warm-up then compiles ``outer``
    (40-50 s, a cache miss), ``inner`` and ``multiply`` traced inside its
    trace, and an eager ``convert`` whose backend compile never consulted
    the cache. ``step_fn`` recompiles in the window (120-125 s), with an
    ``add`` traced inside it; ``late`` traces after the window closed."""
    spans = [
        event('setup.engine', 10.0, 30.0),
        event('compile.trace', 12.0, 14.0, fun='init'),
        event('compile.lower', 14.0, 14.5, fun='jit(init)'),
        event('compile.backend', 14.5, 16.0, fun='jit(init)', cached=True),
        event('compile.trace', 40.0, 45.0, fun='outer'),
        event('compile.trace', 41.0, 42.0, fun='inner'),
        event('compile.trace', 41.5, 41.8, fun='multiply'),
        event('compile.lower', 45.0, 46.0, fun='jit(outer)'),
        event('compile.backend', 46.0, 50.0, fun='jit(outer)', cached=False),
        event('compile.backend', 60.0, 61.0, fun='jit(convert)'),
        event('compile.trace', 120.0, 121.0, fun='step_fn'),
        event('compile.trace', 120.2, 120.3, fun='add'),
        event('compile.backend', 121.0, 125.0, fun='jit(step_fn)',
              cached=False),
        event('compile.trace', 150.0, 151.0, fun='late'),
        {**event('queued', 101.0, 102.0), 'cat': 'request'}]
    return {'spans': spans, 'traced_window': (100.0, 108.0),
            'window_s': 40.0}


@pytest.mark.parametrize('metric, expected', [
    # 12-14.5 and 40-46 s: the nested traces are inside 40-45 s
    ('setup_lower_s', 8.5),
    # 14.5-16, 46-50 and 60-61 s; the window's 121-125 s is not set-up
    ('setup_backend_s', 6.5),
    # 20 s less the 12-16 s its compiles took
    ('setup_engine_s', 16.0),
    # outer's miss; init hit, convert never asked, step_fn's is in the window
    ('compile_cache_misses', 1),
    # step_fn, and add with it; late is past the window
    ('window_compiles', 1)])
def test_each_reading_of_the_compile_spans(metric, expected):
    assert compile_spans.read(compiled(), spec(metric)) == (
        pytest.approx(expected))


def test_nested_traces_count_once():
    records = compiled()
    lower = spec('setup_lower_s')
    alone = [span for span in records['spans']
             if span['args'].get('fun') not in ('inner', 'multiply')]
    assert compile_spans.read(records, lower) == compile_spans.read(
        {**records, 'spans': alone}, lower)
    # a trace that only starts inside another is not a compile of its own
    records['spans'].append(event('compile.trace', 120.5, 122.0, fun='x'))
    assert compile_spans.read(records, spec('window_compiles')) == 2


def test_set_up_and_the_window_say_what_compiled(capsys):
    compile_spans.read(compiled(), spec('setup_lower_s'))
    compile_spans.read(compiled(), spec('window_compiles'))
    said = capsys.readouterr().err
    # the outermost spans: outer's trace and lowering, init's
    assert 'setup_lower_s: by function, s: outer 6.00, init 2.50; ' \
        '2 functions' in said
    assert 'window_compiles: step_fn 1.00; 1 functions' in said


def test_set_up_splits_into_no_more_than_it_lasted():
    records = compiled()
    parts = sum(compile_spans.read(records, spec(name)) for name in
                ('setup_lower_s', 'setup_backend_s', 'setup_engine_s'))
    assert parts == pytest.approx(31.0) and parts <= 100.0 - 10.0


def test_a_run_whose_cache_was_never_consulted_counts_no_miss():
    records = compiled()
    for span in records['spans']:
        span['args'].pop('cached', None)
    assert compile_spans.read(records, spec('compile_cache_misses')) is None
    assert compile_spans.read(records, spec('setup_backend_s')) == (
        pytest.approx(6.5))


def test_a_run_with_compiles_and_no_engine_span_reads_no_self_time():
    records = compiled()
    records['spans'] = [span for span in records['spans']
                        if span['name'] != 'setup.engine']
    assert compile_spans.read(records, spec('setup_engine_s')) is None
    assert compile_spans.read(records, spec('setup_lower_s')) == (
        pytest.approx(8.5))


@pytest.mark.parametrize('metric', COMPILE)
def test_the_parent_and_an_untraced_run_read_none(metric):
    """The parent records the scheduler's spans and nothing of set-up or
    compiles; a run with no ``Tracer`` records no span at all."""
    parent = compiled()
    parent['spans'] = [span for span in parent['spans']
                       if span['cat'] == 'request']
    assert compile_spans.read(parent, spec(metric)) is None
    untraced = {**compiled(), 'traced_window': None}
    assert compile_spans.read(untraced, spec(metric)) is None


# ----------------------------------------------------------------- span_gap

TICK, ADMIT = 'tpusystem.serve.tick', 'tpusystem.scheduler.admit'
PREFILL, ADOPT = 'tpusystem.engine.prefill', 'tpusystem.engine.adopt'
SEAT, DISPATCH = 'tpusystem.engine.seat', 'tpusystem.engine.dispatch'
READ, ROWS = 'tpusystem.engine.read', 'tpusystem.engine.rows'
NARRATE = 'tpusystem.service.narrate'


def admitting() -> dict:
    """A 10 s window, programs over 1-4 and 6-9 s, ticks over 0.5-5 and
    5-9.5 s. Tick one admits over 0.5-1.2 s (prefill 0.6-0.9, adopt
    0.9-1.0); tick two over 5.1-5.6 s with its seat at 5.2-5.4 s."""
    trace = trace_reduce.Trace(
        ops={0: [('fusion.1 bf16[8]', 1.0, 4.0),
                 ('fusion.2 bf16[8]', 6.0, 9.0)]},
        modules={0: [('jit_step_fn(1)', 1.0, 4.0),
                     ('jit_step_fn(1)', 6.0, 9.0)]},
        host=[('chipbench.window', 0.0, 10.0)])
    spans = [(TICK, 0.5, 5.0, {'step': 1, 'clock': 100.5}),
             (ADMIT, 0.5, 1.2, {}), (PREFILL, 0.6, 0.9, {}),
             (ADOPT, 0.9, 1.0, {}), (DISPATCH, 1.2, 1.5, {}),
             (READ, 1.5, 4.25, {}), (ROWS, 4.25, 4.5, {}),
             (NARRATE, 4.5, 5.0, {}),
             (TICK, 5.0, 9.5, {'step': 2, 'clock': 105.0}),
             (ADMIT, 5.1, 5.6, {}), (SEAT, 5.2, 5.4, {}),
             (DISPATCH, 5.6, 6.5, {}), (READ, 6.5, 9.0, {}),
             (ROWS, 9.0, 9.25, {})]
    return {'trace': trace, 'program_trace': ProgramTrace(spans, [])}


@pytest.mark.parametrize('metric, per_tick_ms', [
    ('tick_gap_ms.admit', 200.0),        # 0.5-0.6, 5.1-5.2 and 5.4-5.6 s
    ('tick_gap_ms.prefill', 150.0),      # 0.6-0.9 s
    ('tick_gap_ms.adopt', 50.0),         # 0.9-1.0 s
    ('tick_gap_ms.tick', 175.0),         # 5.0-5.1 and 9.25-9.5 s
    ('tick_gap_ms.seat', 100.0)])
def test_the_admission_path_takes_its_own_gaps(metric, per_tick_ms):
    assert span_gap.read(admitting(), spec(metric)) == (
        pytest.approx(per_tick_ms))


def test_the_nine_gaps_account_for_the_host_gap():
    """What ISSUE 36 accepts on the chip: the nine ``tick_gap_ms.*`` times
    the ticks are ``host_gap_share.serve`` times the window."""
    records = admitting()
    parts = sum(span_gap.read(records, spec(name)) for name in ALL_GAPS)
    whole = module_gap.read(records, spec('host_gap_share.serve'))
    assert parts * 2 / 1e3 == pytest.approx(whole / 100.0 * 10.0)
    assert whole == pytest.approx(30.0)


@pytest.mark.parametrize('metric', GAPS)
def test_a_trace_with_no_program_span_reads_no_gap(metric):
    records = admitting()
    records['program_trace'] = ProgramTrace([], [])
    assert span_gap.read(records, spec(metric)) is None


@pytest.mark.parametrize('metric', COMPILE + GAPS)
def test_each_metric_is_appended_for_the_three_serving_cells(metric):
    names = [entry['name'] for entry in BENCH['per_layer']]
    entry = BENCH['per_layer'][names.index(metric)]
    assert names.index(metric) >= len(names) - len(COMPILE + GAPS)
    assert entry['workloads'] == SERVING
    own = spec(metric)
    assert {key: own[key] for key in entry if key != 'workloads'} == {
        key: entry[key] for key in entry if key != 'workloads'}
