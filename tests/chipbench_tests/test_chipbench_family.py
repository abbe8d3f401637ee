"""A configuration of a second family is added by files alone: a family
module whose configuration keys are not GPT-2's, a configuration cut in
depth with a serving lever, a traffic mix, cells, limits and a ``step_mfu``
metric, written as new files into a temporary copy. Nothing that was there
changes; both drivers run the new cells through the real harness; a fault
planted in the new family's module comes out as not correct."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

from tests.chipbench_tests import tiny

ROOT = pathlib.Path(__file__).resolve().parents[2]

# The toy family keeps its sizes under its own keys and hands GPT-2's family
# the arithmetic; its operation count is its own (twice GPT-2's), so that a
# reading of it cannot be mistaken for another family's.
FAMILY = '''
"""A toy family: GPT-2's mathematics under other configuration keys."""
from chipbench.families import gpt2


def _sizes(config):
    return {**config, 'n_layer': config['depth'], 'n_embd': config['width'],
            'n_head': config['heads'], 'n_positions': config['context'],
            'vocab_size': config['vocabulary'],
            'initializer_range': config['init_std']}


def _handed(name):
    def call(config, *args, **kwargs):
        return getattr(gpt2, name)(_sizes(config), *args, **kwargs)
    return call


for _name in ('train_module', 'serve_module', 'vocab_size', 'positions',
              'make', 'from_key', 'reference_training', 'served_gap',
              'matmul_params', 'prefill_ops', 'decode_ops', 'flash_layers',
              'flash_ops_and_bytes', 'decode_chain_ops_and_bytes',
              'kv_bytes_per_position'):
    globals()[_name] = _handed(_name)
norms = gpt2.norms


def train_ops_per_token(config, seq):
    return 2 * gpt2.train_ops_per_token(_sizes(config), seq)
'''
CONFIG = {
    'name': 'toy', 'source': 'test', 'family': 'toyformer',
    'depth': 2, 'width': 32, 'heads': 4, 'context': 64, 'vocabulary': 120,
    'init_std': 0.02, 'parameters': 0,
    'published': {'depth': 6}, 'reduced': ['depth'],
    'as_run': {**tiny.CONFIG['as_run'], 'stream_dtype': 'float32',
               'levers': {'stream_dtype': 'float32'}},
    'precision': 'float32 on the CPU',
    'deployment': 'one chip holds every layer that is kept; no layer is '
                  'shared between chips',
    'reference': tiny.CONFIG['reference'],
}
ENTRY = {'name': 'toy', 'source': 'test', 'reduced': ['depth'],
         'file': 'chipbench/configs/toy.json', 'why': 't'}
METRIC = {'name': 'step_mfu.toy', 'layer': 'training step', 'unit': '%',
          'better': 'higher', 'source': 'program_counter',
          'moves': 'train_tokens_per_s', 'workloads': ['toy-train']}


@pytest.fixture(scope='module')
def added(tmp_path_factory):
    """The tiny root with the toy family's files beside what was there, and
    the digests of everything that was there (the temporary root's files
    and the repository's ``chipbench/``) taken before and after."""
    import chipbench.families
    root = tiny.build(tmp_path_factory.mktemp('chipbench-family'))
    bench_dir = root / 'chipbench'
    before = (tiny.digests(root), tiny.digests(ROOT / 'chipbench'))
    bench_before = json.loads((root / 'BENCHMARK.json').read_text())

    (bench_dir / 'families').mkdir()
    (bench_dir / 'families' / 'toyformer.py').write_text(FAMILY)
    (bench_dir / 'configs' / 'toy.json').write_text(json.dumps(CONFIG))
    (bench_dir / 'traffic' / 'toy-chat.json').write_text(
        json.dumps(dict(tiny.SERVE, clients=2, rows=2)))
    for cell, stands_for in (('toy-train', 'tiny-train'),
                             ('toy-serve', 'tiny-serve')):
        (bench_dir / 'limits' / f'{cell}.json').write_text(
            (bench_dir / 'limits' / f'{stands_for}.json').read_text())
    (bench_dir / 'metrics' / 'step_mfu.toy.json').write_text(json.dumps(
        dict(METRIC, reader='step_mfu', args={'kind': 'train'})))
    bench = json.loads(json.dumps(bench_before))
    bench['configs'].append(ENTRY)
    bench['workloads'] += [
        {'name': 'toy-train', 'config': 'toy', 'traffic': 'tiny-train',
         'chips': 1, 'why': 't'},
        {'name': 'toy-serve', 'config': 'toy', 'traffic': 'toy-chat',
         'chips': 1, 'why': 't'}]
    for metric in bench['end_to_end']:
        for cell, stands_for in (('toy-train', 'tiny-train'),
                                 ('toy-serve', 'tiny-serve')):
            if stands_for in metric.get('workloads', []):
                metric['workloads'].append(cell)
    bench['per_layer'].append(METRIC)
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))

    after = (tiny.digests(root), tiny.digests(ROOT / 'chipbench'))
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        patch.setattr(chipbench.families, '__path__',
                      list(chipbench.families.__path__)
                      + [str(bench_dir / 'families')])
        yield {'root': root, 'before': before, 'after': after,
               'bench_before': bench_before, 'bench': bench}
        sys.modules.pop('chipbench.families.toyformer', None)


def test_nothing_that_was_there_changed(added):
    for before, after in zip(added['before'], added['after']):
        assert {path: after[path] for path in before} == before
    assert set(added['after'][1]) == set(added['before'][1])
    new = set(added['after'][0]) - set(added['before'][0])
    assert new == {'chipbench/families/toyformer.py',
                   'chipbench/configs/toy.json',
                   'chipbench/traffic/toy-chat.json',
                   'chipbench/limits/toy-train.json',
                   'chipbench/limits/toy-serve.json',
                   'chipbench/metrics/step_mfu.toy.json'}
    for key in ('configs', 'workloads', 'per_layer'):
        old = added['bench_before'][key]
        assert added['bench'][key][:len(old)] == old


def test_the_new_cells_find_their_files_and_their_cut_is_stated(added):
    from chipbench import families, harness
    cell = harness.load_cell('toy-serve', added['root'])
    assert cell.config['depth'] == 2 and 'n_layer' not in cell.config
    assert cell.traffic['clients'] == 2
    tiny.check_cuts(cell.config, ENTRY)
    module = families.of(cell.config)
    assert module.__name__ == 'chipbench.families.toyformer'
    assert all(callable(getattr(module, name))
               for name in families.INTERFACE)
    train = harness.load_cell('toy-train', added['root'])
    assert [m['name'] for m in train.per_layer] == ['step_mfu.toy']
    old = harness.load_cell('tiny-train', added['root'])
    assert 'step_mfu.toy' not in [m['name'] for m in old.per_layer]


def test_the_serving_run_is_correct_and_the_lever_reached_the_engine(
        added, capsys):
    result = tiny.run_cell(added['root'], 'toy-serve', seed=2 ** 31 + 41)
    assert result['correct'] is True and result['attempted'] > 2
    assert result['failed'] == 0
    assert "resolved {'stream_dtype': 'float32'" in capsys.readouterr().err


def test_a_lever_the_engine_did_not_get_is_refused(added, monkeypatch):
    """What the configuration states is what has to run: were the lever
    dropped on the way, the engine would resolve the platform's default."""
    from tpusystem.serve import InferenceService
    real = InferenceService.__init__

    def dropped(self, *args, stream_dtype=None, **kwargs):
        real(self, *args, **kwargs)
    monkeypatch.setattr(InferenceService, '__init__', dropped)
    with pytest.raises(SystemExit, match='stream_dtype'):
        tiny.run_cell(added['root'], 'toy-serve', seed=5)


def test_the_training_run_is_correct(added):
    result = tiny.run_cell(added['root'], 'toy-train', seed=2 ** 31 + 42)
    assert result['correct'] is True and result['failed'] == 0
    assert set(result['compared']) == {'loss_gap', 'moment_gap',
                                       'update_gap'}


def test_step_mfu_reads_the_new_familys_count(added):
    from chipbench import families, flops, harness, trace_reduce
    cell = harness.load_cell('toy-train', added['root'])
    mix = cell.traffic
    records = {
        'config': cell.config, 'traffic': mix, 'chips': 1,
        'device_kind': 'TPU v5 lite', 'traced': {'steps': 4},
        'trace': trace_reduce.Trace({}, {}, [('chipbench.window', 1.0, 3.0)])}
    tokens = 4 * mix['batch'] * mix['seq']
    count = families.of(cell.config).train_ops_per_token(cell.config,
                                                         mix['seq'])
    as_gpt2 = flops.train_ops_per_token(
        dict(tiny.CONFIG, n_layer=cell.config['depth']), mix['seq'])
    assert count == 2 * as_gpt2
    assert harness.read_per_layer(cell, records, added['root']) == {
        'step_mfu.toy': {'value': pytest.approx(
            100.0 * tokens * count / (2.0 * 197e12)), 'unit': '%'}}


@pytest.mark.parametrize('cell', ['toy-serve', 'toy-train'])
def test_a_fault_in_the_new_familys_module_is_not_correct(added, monkeypatch,
                                                          cell):
    """The program is handed another seed's weights than the reference
    makes for itself: every number compared is the toy family's to give."""
    from chipbench import families
    module = families.of(CONFIG)
    real = module.make

    def another_seed(config, seed, *, stacked=False):
        return real(config, seed if stacked else seed + 1, stacked=stacked)
    monkeypatch.setattr(module, 'make', another_seed)
    result = tiny.run_cell(added['root'], cell, seed=2 ** 31 + 41)
    assert result['correct'] is False
    assert any(not number['value'] <= number['limit']
               for number in result['compared'].values())
