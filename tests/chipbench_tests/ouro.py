"""The ``ouro`` family at test size: its tiny configuration (the shipped file
with every width cut; ``tests/test_ouro.py`` and the benchmark's own tests
share it) and its tiny serving cell, added to a tiny root the way
``toy.add`` adds the toy's: new files and appended entries.
"""

from __future__ import annotations

import json
import pathlib
import shutil

from tests.chipbench_tests import tiny, toy

ROOT = tiny.ROOT
SHIPPED = json.loads((ROOT / 'chipbench' / 'configs'
                      / 'ouro-2.6b.json').read_text())
CELL, REAL_CELL = 'ouro-tiny-serve', 'serve-ouro-closed8'
OWN_METRICS = ('loop_weight_roofline',)


def tiny_config(**changes) -> dict:
    """3 passes of 2 layers (two passes still loop after the first), hidden
    64, 4 heads of 16, SwiGLU 96, vocabulary 256, ``max_seq`` 128; float32
    throughout."""
    config = dict(
        SHIPPED, name='ouro-tiny', source='test', hidden_size=64,
        num_hidden_layers=2, total_ut_steps=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, intermediate_size=96,
        vocab_size=256,
        as_run=dict(SHIPPED['as_run'], max_seq=128, param_dtype='float32',
                    compute_dtype='float32', stream_dtype='float32',
                    kv_cache_dtype='float32',
                    levers={'stream_dtype': 'float32'}),
        reference=dict(SHIPPED['reference'], sample_requests=3,
                       control={'bits': 4}))
    config.update(changes)
    return config


MIX = dict(tiny.SERVE, clients=3, rows=3, warm_prompts=[6, 20, 40])
# float32 on the CPU: sound runs read 0 (the served token is the reference's
# best) or a float32 near-tie; a pass left out reads 0.05 and more
LIMITS = {'logit_gap_max': {'limit': 2e-3}}


def add(root: pathlib.Path) -> dict:
    """Write the tiny configuration and its serving cell into ``root``
    (``tiny.build``'s) and return ``toy``'s record of it."""
    record = toy.snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    real = json.loads((ROOT / 'BENCHMARK.json').read_text())
    files = {'chipbench/configs/ouro-tiny.json': tiny_config(),
             'chipbench/traffic/ouro-tiny-qa.json': MIX,
             f'chipbench/limits/{CELL}.json': LIMITS}
    for path, content in files.items():
        (root / path).write_text(json.dumps(content))
    new_files = set(files)
    bench['configs'].append({
        'name': 'ouro-tiny', 'source': 'test', 'why': 't',
        'file': 'chipbench/configs/ouro-tiny.json', 'reduced': []})
    bench['workloads'].append({'name': CELL, 'config': 'ouro-tiny',
                               'traffic': 'ouro-tiny-qa', 'chips': 1,
                               'why': 't'})
    joined = [metric for metric in real['end_to_end'] + real['per_layer']
              if REAL_CELL in metric.get('workloads', [])
              and metric['name'] not in OWN_METRICS]
    have = {metric['name'] for metric in bench['end_to_end']
            + bench['per_layer']}
    # a metric only cells of other families report is not in the tiny root
    # (tiny.build leaves it out): it comes with its file, as this cell's own
    own = [metric for metric in real['per_layer']
           if metric['name'] in OWN_METRICS
           or (metric in joined and metric['name'] not in have)]
    toy.join(bench, CELL, {metric['name'] for metric in joined} & have)
    for entry in own:
        path = f'chipbench/metrics/{entry["name"]}.json'
        shutil.copy(ROOT / path, root / path)
        new_files.add(path)
        bench['per_layer'].append({**entry, 'workloads': [CELL]})
    return toy.written(record, bench, new_files, {CELL: {
        'config': tiny_config(), 'traffic': MIX, 'limits': LIMITS,
        'metrics': {'setup_s', *(metric['name'] for metric in joined),
                    *OWN_METRICS}}})
