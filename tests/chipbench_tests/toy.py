"""What the next ``model_config`` PR does, with a toy family: a family module
and its plain reference, a configuration cut in depth with a serving lever,
a traffic mix, limits, a metric file on a reader that is there, and their
entries in ``BENCHMARK.json`` — every one a new file or an appended entry.

``add`` does it in any benchmark root, the tiny one of ``tiny.build`` or a
copy of the checkout (``tiny.checkout``); ``unchanged`` and ``found`` are
what has to hold afterwards, whatever the root. ``chipbench/README.md``'s
"Adding a configuration of a new family" lists these steps from here.
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import sys

import pytest

from tests.chipbench_tests import tiny

# The toy family keeps its sizes under its own keys and hands GPT-2's family
# the arithmetic; its operation count is its own (twice GPT-2's), so that a
# reading of it cannot be mistaken for another family's, and what it serves
# is judged through its own reference file.
FAMILY = '''
"""A toy family: GPT-2's mathematics under other configuration keys."""
import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.families import gpt2
from chipbench.reference import toyformer as reference


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
              'make', 'from_key', 'reference_training',
              'matmul_params', 'prefill_ops', 'decode_ops', 'flash_layers',
              'flash_ops_and_bytes', 'decode_chain_ops_and_bytes',
              'kv_bytes_per_position'):
    globals()[_name] = _handed(_name)
norms = gpt2.norms


def served_gap(config, seed, sample, control_bits=None):
    model = dict(heads=config['heads'],
                 eps=float(config['as_run']['layer_norm_epsilon']))
    params = gpt2.make(_sizes(config), seed, stacked=True)
    lowered = (reference.quantize_matrices(params, control_bits)
               if control_bits else None)
    widest, covered = 0.0, 0
    for prompt, tokens in sample:
        padded, span = check.sequence(prompt, tokens, config['context'])
        gaps = (reference.served_gaps(params, jnp.asarray(padded), **model)
                if lowered is None else reference.control_gaps(
                    params, lowered, jnp.asarray(padded), **model))
        gaps = np.asarray(gaps)[span]
        widest, covered = max(widest, float(gaps.max())), covered + gaps.size
    return widest, covered


def train_ops_per_token(config, seq):
    return 2 * gpt2.train_ops_per_token(_sizes(config), seq)
'''
REFERENCE = '''
"""The toy family's plain reference: GPT-2's, under the toy's name. Like
every file of this directory it imports nothing of ``tpusystem/``."""
from chipbench.reference.gpt2 import (control_gaps, quantize_matrices,
                                      served_gaps)

__all__ = ['control_gaps', 'quantize_matrices', 'served_gaps']
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
# A cell: its traffic and limits (new files), the metrics that are there
# whose ``workloads`` lists it joins, and one metric of its own on a reader
# that is there.
CELLS = {
    'toy-serve': {
        'traffic': 'toy-chat',
        'mix': dict(tiny.SERVE, clients=2, rows=2),
        'limits': tiny.LIMITS['tiny-serve'],
        'joins': ('serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms',
                  'decode_tick_ms', 'step_mfu.serve', 'scope_share.kv_read',
                  'kv_read_roofline'),
        'metric': {'name': 'scope_share.head', 'layer': 'engine',
                   'unit': '%', 'better': 'lower', 'source': 'device_trace',
                   'moves': 'serve_tokens_per_s'},
        'reads': {'reader': 'scope_share', 'args': {'scopes': ['head']}}},
    'toy-train': {
        'traffic': 'toy-pretrain',
        'mix': tiny.TRAIN, 'limits': tiny.LIMITS['tiny-train'],
        'joins': ('train_tokens_per_s',),
        'metric': {'name': 'step_mfu.toy', 'layer': 'training step',
                   'unit': '%', 'better': 'higher',
                   'source': 'program_counter',
                   'moves': 'train_tokens_per_s'},
        'reads': {'reader': 'step_mfu', 'args': {'kind': 'train'}}},
}


def snapshot(root: pathlib.Path) -> dict:
    """What is there before a PR adds to ``root``: the digest of every file,
    ``BENCHMARK.json`` as parsed, and each cell as ``harness.load_cell``
    finds it."""
    from chipbench import harness
    bench = json.loads((root / 'BENCHMARK.json').read_text())
    return {'root': root, 'before': tiny.digests(root), 'bench_before': bench,
            'was': {entry['name']: harness.load_cell(entry['name'], root)
                    for entry in bench['workloads']}}


def written(record: dict, bench: dict, new_files: set, expects: dict) -> dict:
    """Write the PR's ``BENCHMARK.json`` and close the record: the files it
    added, and for each new cell what ``harness.load_cell`` has to find
    (``{cell: {'config', 'traffic', 'limits', 'metrics'}}``)."""
    root = record['root']
    (root / 'BENCHMARK.json').write_text(json.dumps(bench, indent=1))
    return {**record, 'bench': bench, 'after': tiny.digests(root),
            'new_files': new_files, 'expects': expects}


def join(bench: dict, cell: str, metrics) -> None:
    """Append ``cell`` to the ``workloads`` list of each of ``metrics``: the
    one thing that says a cell reports a metric that is there."""
    for metric in bench['end_to_end'] + bench['per_layer']:
        if metric['name'] in metrics:
            metric['workloads'].append(cell)


def add(root: pathlib.Path, cells=tuple(CELLS)) -> dict:
    """Write the toy family and ``cells`` into ``root`` as the next PR
    would, and return the record of it (``snapshot``, ``written``)."""
    bench_dir = root / 'chipbench'
    record = snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    new_files = {'chipbench/families/toyformer.py',
                 'chipbench/reference/toyformer.py',
                 'chipbench/configs/toy.json'}
    expects = {}

    for sub, source in (('families', FAMILY), ('reference', REFERENCE)):
        (bench_dir / sub).mkdir(exist_ok=True)
        (bench_dir / sub / 'toyformer.py').write_text(source)
    (bench_dir / 'configs' / 'toy.json').write_text(json.dumps(CONFIG))
    bench['configs'].append(ENTRY)
    for name in cells:
        cell = CELLS[name]
        files = {f'chipbench/traffic/{cell["traffic"]}.json': cell['mix'],
                 f'chipbench/limits/{name}.json': cell['limits'],
                 f'chipbench/metrics/{cell["metric"]["name"]}.json':
                 {**cell['metric'], **cell['reads']}}
        for path, content in files.items():
            (root / path).write_text(json.dumps(content))
        new_files |= set(files)
        bench['workloads'].append({'name': name, 'config': 'toy',
                                   'traffic': cell['traffic'], 'chips': 1,
                                   'why': 't'})
        join(bench, name, cell['joins'])
        bench['per_layer'].append({**cell['metric'], 'workloads': [name]})
        expects[name] = {
            'config': CONFIG, 'traffic': cell['mix'], 'limits': cell['limits'],
            'metrics': {'setup_s', cell['metric']['name'], *cell['joins']}}
    return written(record, bench, new_files, expects)


def fresh_checkout(dest) -> pathlib.Path:
    """``tiny.checkout`` for a rehearsal. A checkout that has the toy is a
    rehearsal's own copy, whose tests are being run from it: there the
    rehearsal is not made again."""
    if (tiny.ROOT / 'chipbench' / 'families' / 'toyformer.py').exists():
        pytest.skip('this checkout is a rehearsal itself: it has the toy')
    return tiny.checkout(dest)


@contextlib.contextmanager
def imported(root: pathlib.Path):
    """The look for a chip steered (``tiny.steer``) and the root's new
    family and reference found by name, as they are in a checkout that
    holds them."""
    import chipbench.families
    import chipbench.reference
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        for package in (chipbench.families, chipbench.reference):
            here = root / 'chipbench' / package.__name__.rpartition('.')[2]
            patch.setattr(package, '__path__',
                          list(package.__path__) + [str(here)])
        try:
            yield
        finally:
            for package in ('families', 'reference'):
                sys.modules.pop(f'chipbench.{package}.toyformer', None)


def unchanged(added: dict) -> None:
    """Every file that was there has its digest, the new files are the ones
    the record names, and every old entry of ``BENCHMARK.json`` stands as it
    was but for the new cells' names at the end of ``workloads`` lists."""
    before, after = added['before'], added['after']
    assert {path: after[path] for path in before} == before
    assert set(after) - set(before) == added['new_files']
    old, new = added['bench_before'], added['bench']
    assert set(new) == set(old)
    for key in ('command', 'paths', 'run_seconds'):
        assert new[key] == old[key]
    for key in ('configs', 'workloads'):
        assert new[key][:len(old[key])] == old[key]
    for key in ('end_to_end', 'per_layer'):
        assert len(new[key]) >= len(old[key])
        for was, now in zip(old[key], new[key]):
            cells = was.get('workloads', [])
            assert {**now, 'workloads': cells} == {**was, 'workloads': cells}
            assert now.get('workloads', [])[:len(cells)] == cells
            assert set(now.get('workloads', [])[len(cells):]) <= set(
                added['expects'])


def found(added: dict) -> None:
    """``harness.load_cell`` gives each new cell its files, the metrics it
    joined and its own, and each cell that was there exactly what it had."""
    from chipbench import harness
    names = lambda metrics: [metric['name'] for metric in metrics]
    for name, want in added['expects'].items():
        new = harness.load_cell(name, added['root'])
        assert (new.chips, new.config, new.traffic, new.limits) == (
            1, want['config'], want['traffic'], want['limits'])
        assert set(names(new.end_to_end + new.per_layer)) == want['metrics']
    assert added['was']
    for name, was in added['was'].items():
        now = harness.load_cell(name, added['root'])
        assert (now.chips, now.config, now.traffic, now.limits) == (
            was.chips, was.config, was.traffic, was.limits)
        assert names(now.end_to_end) == names(was.end_to_end)
        assert names(now.per_layer) == names(was.per_layer)
