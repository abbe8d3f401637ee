"""The ``deepseek_v2`` family at test size: its tiny configuration (the
shipped file with every width cut; ``tests/test_deepseek.py`` and the
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
                      / 'deepseek-v2.json').read_text())
CELL, REAL_CELL = 'dsv2-tiny-serve', 'serve-dsv2-closed64'
OWN_METRICS = ('latent_read_roofline', 'expert_roofline',
               'scope_share.experts', 'scope_share.router',
               'expert_imbalance')


def tiny_config(**changes) -> dict:
    """3 layers (the first dense), hidden 64, 4 heads, ``kv_lora_rank`` 16,
    rope 8, 16 experts in 4 groups of which 2, 3 a token, 1 shared,
    vocabulary 256; experts 4-11 of 16 are held; float32 throughout."""
    config = dict(
        SHIPPED, name='dsv2-tiny', source='test', hidden_size=64,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=48, n_routed_experts=8,
        n_group=4, topk_group=2, num_experts_per_tok=3, n_shared_experts=1,
        num_hidden_layers=3, vocab_size=256,
        published={'n_routed_experts': 16, 'num_hidden_layers': 6,
                   'vocab_size': 1024},
        rope_scaling=dict(SHIPPED['rope_scaling'], factor=4,
                          original_max_position_embeddings=32),
        as_run=dict(SHIPPED['as_run'], max_seq=128, first_expert=4,
                    param_dtype='float32', compute_dtype='float32',
                    stream_dtype='float32', kv_cache_dtype='float32',
                    levers={'stream_dtype': 'float32'}),
        reference=dict(SHIPPED['reference'], sample_requests=3,
                       control={'bits': 4}))
    config.update(changes)
    return config


MIX = dict(tiny.SERVE, clients=3, rows=3, warm_prompts=[6, 20, 40])
# float32 on the CPU: sound runs read 0 (the served token is the reference's
# best) or a float32 near-tie; another seed's weights read 0.1 and more
LIMITS = {'logit_gap_max': {'limit': 2e-3}}


def add(root: pathlib.Path) -> dict:
    """Write the tiny configuration and its serving cell into ``root``
    (``tiny.build``'s) and return ``toy``'s record of it."""
    record = toy.snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    real = json.loads((ROOT / 'BENCHMARK.json').read_text())
    files = {'chipbench/configs/dsv2-tiny.json': tiny_config(),
             'chipbench/traffic/dsv2-tiny-chat.json': MIX,
             f'chipbench/limits/{CELL}.json': LIMITS}
    for path, content in files.items():
        (root / path).write_text(json.dumps(content))
    new_files = set(files)
    bench['configs'].append({
        'name': 'dsv2-tiny', 'source': 'test', 'why': 't',
        'file': 'chipbench/configs/dsv2-tiny.json',
        'reduced': tiny_config()['reduced']})
    bench['workloads'].append({'name': CELL, 'config': 'dsv2-tiny',
                               'traffic': 'dsv2-tiny-chat', 'chips': 1,
                               'why': 't'})
    joins = {metric['name'] for metric in real['end_to_end']
             + real['per_layer'] if REAL_CELL in metric.get('workloads', [])
             and metric['name'] not in OWN_METRICS}
    toy.join(bench, CELL, joins)
    for name in OWN_METRICS:
        path = f'chipbench/metrics/{name}.json'
        shutil.copy(ROOT / path, root / path)
        new_files.add(path)
        entry = next(m for m in real['per_layer'] if m['name'] == name)
        bench['per_layer'].append({**entry, 'workloads': [CELL]})
    return toy.written(record, bench, new_files, {CELL: {
        'config': tiny_config(), 'traffic': MIX, 'limits': LIMITS,
        'metrics': {'setup_s', *joins, *OWN_METRICS}}})
