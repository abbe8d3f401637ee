"""A tiny benchmark root for the CPU tests: the real harness, readers and
metric files over a two-layer configuration and traffic a test can hold.

Everything a cell needs is written as NEW files under ``dest`` (a
configuration, two traffic mixes, two cells, their limits); nothing that
ships in ``chipbench/`` is edited, which is also what a later PR may do.
"""

from __future__ import annotations

import hashlib
import json
import pathlib
import re
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]

CONFIG = {
    'name': 'tiny', 'source': 'test', 'family': 'gpt2',
    'n_layer': 2, 'n_embd': 32, 'n_head': 4, 'n_positions': 64,
    'vocab_size': 120, 'initializer_range': 0.02, 'reduced': [],
    'as_run': {
        'vocab_rows': 128, 'layer_norm_epsilon': 1e-06, 'dropout': 0.0,
        'attention': 'xla', 'stream_dtype': 'auto', 'decode_impl': 'flax',
        'criterion': {'name': 'ChunkedNextTokenLoss', 'chunks': 2},
        'optimizer': {'name': 'AdamW', 'lr': 0.0003, 'b1': 0.9, 'b2': 0.999,
                      'eps': 1e-08, 'weight_decay': 0.01, 'grad_clip': 1.0}},
    'reference': {'block_rows': 4, 'sample_requests': 4,
                  'control': {'precision': 'fp8', 'bits': 4}},
}
TRAIN = {'driver': 'train', 'batch': 8, 'seq': 32, 'steps_per_dispatch': 2,
         'epoch_batches': 4, 'bigram_fanout': 4, 'shuffle': True,
         'trace_calls': 1}
SERVE = {'driver': 'serve', 'loop': 'closed', 'clients': 4, 'rows': 4,
         'block_size': 16,
         'prompt': {'median': 20, 'sigma': 0.5, 'low': 6, 'high': 40},
         'max_new': {'median': 6, 'sigma': 0.4, 'low': 3, 'high': 10},
         'pool': 64, 'pairing_seed': 1, 'greedy': True,
         'share_prefix': False, 'warm_prompts': [6, 20, 40],
         'trace_seconds': 1, 'drain_seconds': 30}
# set from this cell's own readings on the CPU (PR 23): sound runs read a
# loss gap up to 1.6e-5, a moment gap up to 0.0035, an update gap up to 0.02;
# half a batch reads 9e-4, 0.46 and 0.11, an unchanged state 1.0 and 1.0.
# The logit gap (PR 26): sound runs read 0 on 27 seeds and windows and
# 5.05e-4 once (a bf16 near-tie in a sample that six busy workers' timing
# drew: the window closes on the clock); an altered token reads 0.01 and
# more, the int4 control 0.0093 on its test's sample
LIMITS = {'tiny-train': {'loss_gap': {'limit': 1e-4},
                         'moment_gap': {'limit': 0.03},
                         'update_gap': {'limit': 0.06}},
          'tiny-serve': {'logit_gap_max': {'limit': 2e-3}}}
CELLS = {'tiny-train': ('tiny-train', 'train-medium-seq1024'),
         'tiny-serve': ('tiny-serve', 'serve-large-closed32')}


def build(dest, source: pathlib.Path = ROOT) -> pathlib.Path:
    """Write the tiny root under ``dest`` and return it.

    It follows ``source``'s ``BENCHMARK.json`` and does not know it: ``CELLS``
    alone says which real cell a tiny cell stands for. A name in a metric's
    ``workloads`` that stands for no tiny cell is left out of the tiny
    root's list, and a metric left with no cell is left out of the tiny root
    with its file, so a cell or a metric a later PR adds changes nothing
    here."""
    dest = pathlib.Path(dest)
    bench_dir = dest / 'chipbench'
    for sub in ('metrics', 'configs', 'traffic', 'limits'):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / 'configs' / 'tiny.json').write_text(json.dumps(CONFIG))
    (bench_dir / 'traffic' / 'tiny-train.json').write_text(json.dumps(TRAIN))
    (bench_dir / 'traffic' / 'tiny-serve.json').write_text(json.dumps(SERVE))
    bench = json.loads((source / 'BENCHMARK.json').read_text())
    stands_for = {real: tiny for tiny, (_, real) in CELLS.items()}
    bench['configs'] = [{'name': 'tiny', 'source': 'test', 'reduced': [],
                         'file': 'chipbench/configs/tiny.json', 'why': 't'}]
    bench['workloads'] = [
        {'name': name, 'config': 'tiny', 'traffic': mix, 'chips': 1,
         'why': 't'} for name, (mix, _) in CELLS.items()]
    for group in ('end_to_end', 'per_layer'):
        for metric in bench[group]:
            if 'workloads' in metric:
                metric['workloads'] = [stands_for[name]
                                       for name in metric['workloads']
                                       if name in stands_for]
        bench[group] = [metric for metric in bench[group]
                        if metric.get('workloads', True)]
    for metric in bench['per_layer']:
        shutil.copy(source / 'chipbench' / 'metrics' / f'{metric["name"]}.json',
                    bench_dir / 'metrics')
    (dest / 'BENCHMARK.json').write_text(json.dumps(bench))
    for name in CELLS:
        (bench_dir / 'limits' / f'{name}.json').write_text(
            json.dumps(LIMITS[name]))
    return dest


def checkout(dest) -> pathlib.Path:
    """A copy of the checkout's benchmark under ``dest``: ``BENCHMARK.json``
    and the directories it names under ``paths``, copied, so that a test may
    add to them; the program the benchmark measures is linked, not copied.
    ``python -m pytest`` run from there imports the copy's ``chipbench`` and
    ``tests.chipbench_tests``."""
    dest = pathlib.Path(dest)
    bench = json.loads((ROOT / 'BENCHMARK.json').read_text())
    for path in ['BENCHMARK.json', 'pyproject.toml', 'tests/__init__.py',
                 'tests/conftest.py'] + bench['paths']:
        (dest / path).parent.mkdir(parents=True, exist_ok=True)
        if (ROOT / path).is_dir():
            shutil.copytree(ROOT / path, dest / path,
                            ignore=shutil.ignore_patterns('__pycache__'))
        else:
            shutil.copy(ROOT / path, dest / path)
    for program in ('tpusystem', 'examples'):
        (dest / program).symlink_to(ROOT / program)
    return dest


def digests(root: pathlib.Path) -> dict:
    """``{relative path: sha256}`` of every file under ``root`` but
    ``BENCHMARK.json`` (which gains entries) and compiled bytecode."""
    return {str(path.relative_to(root)): hashlib.sha256(
        path.read_bytes()).hexdigest()
        for path in sorted(root.rglob('*')) if path.is_file()
        and path.name != 'BENCHMARK.json' and '__pycache__' not in path.parts}


def check_cuts(config: dict, entry: dict) -> None:
    """A configuration's ``reduced`` is its entry's; each key it lists is a
    key of the file with the published value beside it, and the file says
    over how many chips a layer is shared (model-configs guide, section 4)."""
    assert config['reduced'] == entry['reduced']
    for key in config['reduced']:
        assert key in config, f'reduced names {key!r}, the file has no such key'
        assert key in config['published'], f'no published value for {key!r}'
        assert config['published'][key] != config[key], key
    if config['reduced']:
        assert re.search(r'\bchips?\b', config['deployment'])


def steer(monkeypatch) -> None:
    """Stand in for the chip: the look for a TPU says yes and the compile
    cache is left alone (tests never place one). Steered here, in the
    test, and not through an option of the command."""
    from chipbench import harness
    monkeypatch.setattr(harness, 'require_chips', lambda chips: {
        'platform': 'cpu', 'kind': 'cpu', 'count': chips})
    monkeypatch.setattr(harness, 'place_compile_cache', lambda: None)


def run_cell(root, name: str, seed: int, seconds: float = 0.5) -> dict:
    """One untraced run of a tiny cell through ``harness.run``."""
    from chipbench import harness
    line = harness.run(name, seed=seed, seconds=seconds, trace=False,
                       started=time.perf_counter(), root=root)
    return json.loads(line)
