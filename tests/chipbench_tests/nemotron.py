"""The ``nemotron_h`` family at test size: its tiny configuration (the
shipped file with every width cut; ``tests/test_nemotron.py`` and the
benchmark's own tests share it) and its tiny serving cell, added to a tiny
root the way ``toy.add`` adds the toy's: new files and appended entries.
"""

from __future__ import annotations

import json
import pathlib
import shutil

from tests.chipbench_tests import tiny, toy

ROOT = tiny.ROOT
SHIPPED = json.loads((ROOT / 'chipbench' / 'configs'
                      / 'nemotron-3-nano-30b-a3b.json').read_text())
CELL, REAL_CELL = 'nemotron-tiny-serve', 'serve-nemotron3-closed96'
OWN_METRICS = ('scope_share.ssm', 'state_update_roofline', 'scan_roofline')


def tiny_config(**changes) -> dict:
    """5 layers ``ME*ME`` of a published ``ME*MEM``, hidden 64; state-space
    layers of 4 heads of 8 in 2 groups, state 16, chunk 8; 4 query heads on
    2 key/value heads of 16; 8 experts of which 3 a token, experts 2-5 held,
    a shared expert; vocabulary 256; steps drawn over [0.05, 0.5] (at widths
    this small ``x``, ``B`` and ``C`` are a tenth of the real ones', and at
    the published steps the state would not reach the logits); float32
    throughout."""
    config = dict(
        SHIPPED, name='nemotron-tiny', source='test', hidden_size=64,
        hybrid_override_pattern='ME*ME', num_hidden_layers=5,
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_intermediate_size=48,
        moe_shared_expert_intermediate_size=96, n_routed_experts=4,
        num_experts_per_tok=3, vocab_size=256,
        time_step_min=0.05, time_step_max=0.5,
        published={'n_routed_experts': 8, 'num_hidden_layers': 6,
                   'hybrid_override_pattern': 'ME*MEM', 'vocab_size': 1024},
        as_run=dict(SHIPPED['as_run'], max_seq=128, first_expert=2,
                    expert_pad=32,
                    param_dtype='float32', compute_dtype='float32',
                    stream_dtype='float32', kv_cache_dtype='float32',
                    levers={'stream_dtype': 'float32'}),
        reference=dict(SHIPPED['reference'], sample_requests=3,
                       control={'bits': 4}))
    config.update(changes)
    return config


MIX = dict(tiny.SERVE, clients=3, rows=3, warm_prompts=[6, 20, 40])
# float32 on the CPU: sound runs read 0 (the served token is the reference's
# best) or a float32 near-tie; another seed's weights, or a state-space
# layer that ignores its state, read 0.05 and more
LIMITS = {'logit_gap_max': {'limit': 2e-3}}


def add(root: pathlib.Path) -> dict:
    """Write the tiny configuration and its serving cell into ``root``
    (``tiny.build``'s) and return ``toy``'s record of it."""
    record = toy.snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    real = json.loads((ROOT / 'BENCHMARK.json').read_text())
    files = {'chipbench/configs/nemotron-tiny.json': tiny_config(),
             'chipbench/traffic/nemotron-tiny-chat.json': MIX,
             f'chipbench/limits/{CELL}.json': LIMITS}
    for path, content in files.items():
        (root / path).write_text(json.dumps(content))
    new_files = set(files)
    bench['configs'].append({
        'name': 'nemotron-tiny', 'source': 'test', 'why': 't',
        'file': 'chipbench/configs/nemotron-tiny.json',
        'reduced': tiny_config()['reduced']})
    bench['workloads'].append({'name': CELL, 'config': 'nemotron-tiny',
                               'traffic': 'nemotron-tiny-chat', 'chips': 1,
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
