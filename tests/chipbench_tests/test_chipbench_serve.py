"""The serving driver end to end at a tiny size, sound and broken, and the
property later PRs depend on: a configuration, a traffic mix, a cell and a
per-layer metric are added by files and entries alone."""

from __future__ import annotations

import json

import pytest

from tests.chipbench_tests import tiny


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp('chipbench-serve'))


@pytest.fixture(scope='module')
def sound(root):
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        return tiny.run_cell(root, 'tiny-serve', seed=2 ** 31 + 21)


def test_sound_run_is_correct_and_reports_the_cells_metrics(sound):
    assert sound['correct'] is True
    assert sound['attempted'] > 4 and sound['failed'] == 0
    assert set(sound['metrics']) == {'serve_tokens_per_s', 'ttft_p50_ms',
                                     'itl_p95_ms', 'setup_s'}
    assert all(metric['value'] > 0 for metric in sound['metrics'].values())
    assert list(sound)[-1] == 'compared'
    gap = sound['compared']['logit_gap_max']
    assert 0 <= gap['value'] <= gap['limit']


def test_a_token_altered_where_it_is_produced_is_not_correct(root,
                                                             monkeypatch):
    from tpusystem.serve.engine import Engine
    tiny.steer(monkeypatch)
    real = Engine.step

    def altered(self):
        report = real(self)
        for row, tokens in report.emitted.items():
            tokens[-1] = (tokens[-1] + 1) % tiny.CONFIG['vocab_size']
            if row in self._rowstate:
                self._rowstate[row].tokens[-1] = tokens[-1]
        report.finished = [(row, reason, kept[:-1] + [
            (kept[-1] + 1) % tiny.CONFIG['vocab_size']])
            for row, reason, kept in report.finished]
        return report
    monkeypatch.setattr(Engine, 'step', altered)
    result = tiny.run_cell(root, 'tiny-serve', seed=2 ** 31 + 21)
    assert result['correct'] is False
    gap = result['compared']['logit_gap_max']
    assert gap['value'] > gap['limit']


def test_a_request_that_never_finishes_is_not_correct(root, monkeypatch):
    from tpusystem.serve.service import InferenceService
    tiny.steer(monkeypatch)
    real = InferenceService.submit

    def swallow(self, request, on_token=None):
        if request.id == 'r9':
            return None              # accepted, never queued, never served
        return real(self, request, on_token)
    monkeypatch.setattr(InferenceService, 'submit', swallow)
    mix = dict(tiny.SERVE, drain_seconds=1)
    (root / 'chipbench' / 'traffic' / 'tiny-stall.json').write_text(
        json.dumps(mix))
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    if not any(w['name'] == 'tiny-stall' for w in bench['workloads']):
        bench['workloads'].append({'name': 'tiny-stall', 'config': 'tiny',
                                   'traffic': 'tiny-stall', 'chips': 1,
                                   'why': 't'})
        for metric in bench['end_to_end']:
            if 'tiny-serve' in metric.get('workloads', []):
                metric['workloads'].append('tiny-stall')
        (root / 'BENCHMARK.json').write_text(json.dumps(bench))
        (root / 'chipbench' / 'limits' / 'tiny-stall.json').write_text(
            (root / 'chipbench' / 'limits' / 'tiny-serve.json').read_text())
    result = tiny.run_cell(root, 'tiny-stall', seed=3)
    assert result['correct'] is False and result['failed'] >= 1


def test_int4_control_reads_wider_than_the_tiny_cells_limit(root):
    from chipbench import families, harness
    cell = harness.load_cell('tiny-serve', root)
    sample = [(list(range(7, 40)), list(range(50, 60)))]
    control, _ = families.of(cell.config).served_gap(
        cell.config, 5, sample,
        control_bits=cell.config['reference']['control']['bits'])
    assert control > cell.limits['logit_gap_max']['limit']


@pytest.fixture(params=['tiny', 'checkout'])
def grown(request, root, tmp_path):
    """A root to add to, and the serving cell that is there: the tiny root,
    and a copy of this checkout with its real ``BENCHMARK.json``."""
    if request.param == 'tiny':
        return root, 'tiny-serve'
    return tiny.checkout(tmp_path / 'checkout'), 'serve-large-closed32'


def test_a_cell_and_a_metric_are_added_by_files_alone(grown, monkeypatch):
    """A second configuration, traffic mix, cell, per-layer metric and its
    reader, written as new files (and new entries of BENCHMARK.json), are
    picked up with no edit to any file that was there."""
    import chipbench.readers
    from chipbench import harness, trace_reduce
    root, serving = grown
    tiny.steer(monkeypatch)
    before = tiny.digests(root)
    bench_before = json.loads((root / 'BENCHMARK.json').read_text())
    old_before = harness.load_cell(serving, root)

    config = dict(tiny.CONFIG, name='tiny-one', n_layer=1)
    (root / 'chipbench' / 'configs' / 'tiny-one.json').write_text(
        json.dumps(config))
    mix = dict(tiny.SERVE, clients=2, rows=2)
    (root / 'chipbench' / 'traffic' / 'tiny-pair.json').write_text(
        json.dumps(mix))
    (root / 'chipbench' / 'limits' / 'one-pair.json').write_text(
        json.dumps(tiny.LIMITS['tiny-serve']))
    readers = root / 'chipbench' / 'readers'
    readers.mkdir(exist_ok=True)
    (readers / 'ticks_seen.py').write_text(
        'def read(records, spec):\n'
        '    ticks = records.get("ticks")\n'
        '    return float(len(ticks)) * spec["args"]["scale"] if ticks '
        'else None\n')
    (root / 'chipbench' / 'metrics' / 'ticks_seen.json').write_text(
        json.dumps({'name': 'ticks_seen', 'layer': 'scheduler',
                    'unit': 'ticks', 'better': 'higher',
                    'source': 'program_counter',
                    'moves': 'serve_tokens_per_s', 'reader': 'ticks_seen',
                    'args': {'scale': 2}}))
    bench = json.loads(json.dumps(bench_before))
    bench['configs'].append({'name': 'tiny-one', 'source': 'test',
                             'file': 'chipbench/configs/tiny-one.json',
                             'reduced': [], 'why': 't'})
    bench['workloads'].append({'name': 'one-pair', 'config': 'tiny-one',
                               'traffic': 'tiny-pair', 'chips': 1,
                               'why': 't'})
    for metric in bench['end_to_end']:
        if serving in metric.get('workloads', []):
            metric['workloads'].append('one-pair')
    bench['per_layer'].append({'name': 'ticks_seen', 'unit': 'ticks',
                               'better': 'higher',
                               'source': 'program_counter',
                               'layer': 'scheduler',
                               'moves': 'serve_tokens_per_s',
                               'workloads': ['one-pair']})
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))

    after = tiny.digests(root)
    assert {path: after[path] for path in before} == before
    # the old entries of BENCHMARK.json stand as they were, up to the new
    # cell's name in the lists of cells that report an end-to-end metric
    for key in ('configs', 'workloads', 'per_layer'):
        assert bench[key][:len(bench_before[key])] == bench_before[key]

    cell = harness.load_cell('one-pair', root)
    assert cell.config['n_layer'] == 1 and cell.traffic['clients'] == 2
    assert [m['name'] for m in cell.per_layer] == ['ticks_seen']
    result = tiny.run_cell(root, 'one-pair', seed=77)
    assert result['correct'] is True and result['attempted'] > 2
    assert 'serve_tokens_per_s' in result['metrics']

    monkeypatch.setattr(chipbench.readers, '__path__',
                        list(chipbench.readers.__path__) + [str(readers)])
    records = {'ticks': [{}, {}, {}], 'trace': trace_reduce.Trace({}, {}, [])}
    assert harness.read_per_layer(cell, records, root) == {
        'ticks_seen': {'value': 6.0, 'unit': 'ticks'}}
    assert harness.read_per_layer(cell, {'ticks': []}, root) == {}
    # and the cell that was there still finds its own metrics and no other
    old = harness.load_cell(serving, root)
    assert 'row_occupancy' in [m['name'] for m in old.per_layer]
    assert [m['name'] for m in old.per_layer] == [
        m['name'] for m in old_before.per_layer]
