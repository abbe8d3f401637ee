"""The ``dots3`` family in the benchmark: its configuration, traffic and cell
as files, its counts against hand-worked numbers, its readers' arithmetic on
synthetic records (no chip, no profiler), and a tiny cell through the real
harness and serving driver, sound and with a fault planted."""

from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil

import pytest

from chipbench import families, harness, trace_reduce
from chipbench.families import dots3 as family
from chipbench.readers import program_trace, sparse_roofline
from tests.chipbench_tests import tiny, toy

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
CONFIG = json.loads((ROOT / 'chipbench' / 'configs'
                     / 'dots3-note-prev.json').read_text())
CELL, TINY_CELL = 'serve-dots3-closed96', 'dots3-tiny-serve'
FULL, SLIDING = 'full_attention', 'sliding_attention'
PUBLISHED_TYPES = [FULL, FULL] + [SLIDING, SLIDING, SLIDING, FULL] * 11
HELD = {'num_hidden_layers': 5, 'layer_types': PUBLISHED_TYPES[:5],
        'n_routed_experts': 32, 'vocab_size': 19008}
OWN_METRICS = ('indexer_roofline', 'sparse_read_roofline',
               'window_read_roofline', 'scope_share.indexer')


def tiny_config(**changes) -> dict:
    """Every width cut (``tpusystem.models.dots3_tiny``'s, under the
    published names): 4 layers (full, full, sliding, sliding; the first
    dense), 16 experts of which 4-11 are held, 3 a token, 1 shared;
    ``index_topk`` 8, window 5, ``max_seq`` 64; float32 throughout."""
    config = dict(
        CONFIG, name='dots3-tiny', source='test', hidden_size=64,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        index_n_heads=4, index_head_dim=16, index_topk=8,
        swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        sliding_window_size=5, intermediate_size=128,
        moe_intermediate_size=48, n_routed_experts=8, num_experts_per_tok=3,
        n_shared_experts=1, num_hidden_layers=4,
        layer_types=[FULL, FULL, SLIDING, SLIDING], vocab_size=256,
        published={'n_routed_experts': 16, 'num_hidden_layers': 46,
                   'layer_types': PUBLISHED_TYPES, 'vocab_size': 1024},
        as_run=dict(CONFIG['as_run'], max_seq=64, first_expert=4,
                    param_dtype='float32', compute_dtype='float32',
                    stream_dtype='float32', kv_cache_dtype='float32',
                    levers={'stream_dtype': 'float32'}),
        reference=dict(CONFIG['reference'], sample_requests=3,
                       control={'bits': 4}))
    config.update(changes)
    return config


# ------------------------------------------------------------- the files

def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    entry = next(c for c in BENCH['configs']
                 if c['name'] == 'dots3-note-prev')
    assert CONFIG['family'] == 'dots3' and families.of(CONFIG) is family
    assert CONFIG['reduced'] == list(HELD) == entry['reduced']
    for key, value in HELD.items():
        assert CONFIG[key] == value
    assert CONFIG['published']['layer_types'] == PUBLISHED_TYPES
    assert (CONFIG['published']['n_routed_experts'],
            CONFIG['published']['vocab_size']) == (256, 152064)
    tiny.check_cuts(CONFIG, entry)
    assert 'eight chips share each layer' in CONFIG['deployment']
    # the published widths of the mechanisms this cell exercises
    assert (CONFIG['hidden_size'], CONFIG['num_attention_heads'],
            CONFIG['kv_lora_rank'], CONFIG['swa_kv_lora_rank'],
            CONFIG['index_topk'], CONFIG['sliding_window_size'],
            CONFIG['num_experts_per_tok']) == (5120, 128, 512, 1024, 2048,
                                               513, 8)
    assert CONFIG['parameters'] == family.parameters(CONFIG) == 4_087_154_176
    as_run = CONFIG['as_run']
    assert {as_run[key] for key in ('param_dtype', 'compute_dtype',
                                    'stream_dtype', 'kv_cache_dtype')} == {
        'bfloat16'}
    assert (as_run['decode_impl'], as_run['max_seq'], as_run['levers']) == (
        'flax', 11264, {'stream_dtype': 'bfloat16'})
    assert CONFIG['reference'] == {'sample_requests': 6,
                                   'control': {'bits': 8}}
    assert set(CONFIG['assumed']) >= {
        'lora_rescale', 'headwise_gate', 'indexer', 'window', 'weights',
        'max_seq', 'window_cache', 'selections'}


def test_the_cell_holds_its_traffic_and_joins_its_metrics():
    entry = next(w for w in BENCH['workloads'] if w['name'] == CELL)
    assert entry == {**entry, 'config': 'dots3-note-prev',
                     'traffic': 'closed96-longctx', 'chips': 1}
    assert len(entry['why']) <= 200
    mix = json.loads((ROOT / 'chipbench' / 'traffic'
                      / 'closed96-longctx.json').read_text())
    assert (mix['driver'], mix['loop'], mix['clients'], mix['rows'],
            mix['block_size']) == ('serve_sparse', 'closed', 96, 96, 16)
    assert mix['prompt'] == {'median': 3072, 'sigma': 0.4, 'low': 2048,
                             'high': 8192}
    assert mix['max_new'] == {'median': 1024, 'sigma': 0.5, 'low': 256,
                              'high': 3072}
    assert (mix['pool'], mix['warm_prompts'], mix['greedy'],
            mix['share_prefix'], mix['drain_seconds'],
            mix['trace_seconds']) == (32, [2048, 4096, 8192], True, False,
                                      120, 8)
    cell = harness.load_cell(CELL)
    reported = {m['name'] for m in cell.end_to_end + cell.per_layer}
    assert {'serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms', 'setup_s',
            'expert_roofline', 'scope_share.kv_read', *OWN_METRICS} <= reported
    assert not reported & {'kv_read_roofline', 'latent_read_roofline',
                           'decode_chain_roofline', 'loop_weight_roofline'}
    names = [m['name'] for m in BENCH['per_layer']]
    assert names[-len(OWN_METRICS):] == list(OWN_METRICS)
    for name in OWN_METRICS:
        metric = next(m for m in BENCH['per_layer'] if m['name'] == name)
        assert metric['workloads'] == [CELL]
        spec = json.loads((ROOT / 'chipbench' / 'metrics'
                           / f'{name}.json').read_text())
        assert spec['moves'] == metric['moves']


# ------------------------------------------------------------- the counts

def test_counts_match_hand_worked_numbers():
    c = CONFIG
    # a full layer: latent 576 values and an indexer key of 128, in bf16
    assert family.kv_bytes_per_position(c) == 2 * (576 + 128) * 2
    assert family.window_bytes_per_row(c) == 3 * 513 * 1088 * 2
    full_attention = (5120 * 1024 + 1024 * 128 * 192 + 5120 * 576
                      + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128
                      + 1024 * 64 * 128 + 5120 * 128 + 5120 * 64)
    assert family.attention_params(c, FULL) == full_attention == 144_048_128
    assert family.attention_params(c, SLIDING) == 90_832_896
    depth = 4096
    latent_full = 2 * 128 * (2 * 512 + 64)
    latent_window = 2 * 64 * (2 * 1024 + 64)
    assert family.decode_ops(c, depth) == pytest.approx(
        2 * family.matmul_params(c) + 2 * (depth * 2 * 64 * 128
                                           + 2048 * latent_full)
        + 3 * 513 * latent_window)
    depths = [100, 3000, 5000]
    ops, moved = family.sparse_ops_and_bytes(c, 'indexer', depths)
    assert (ops, moved) == (2 * 8100 * 2 * 64 * 128, 2 * 8100 * 256)
    ops, moved = family.sparse_ops_and_bytes(c, 'sparse_read', depths)
    assert moved == 2 * (100 + 2048 + 2048) * 1152
    assert ops == 2 * (100 + 2048 + 2048) * 128 * (576 + 512) * 2
    ops, moved = family.sparse_ops_and_bytes(c, 'window_read', depths)
    assert moved == 3 * (100 + 513 + 513) * 2176
    assert ops == 3 * (100 + 513 + 513) * 64 * (1088 + 1024) * 2
    with pytest.raises(ValueError):
        family.sparse_ops_and_bytes(c, 'elsewhere', depths)


def test_the_training_names_and_the_fused_kernels_raise():
    for name in ('train_module', 'reference_training', 'train_ops_per_token',
                 'flash_layers', 'flash_ops_and_bytes',
                 'decode_chain_ops_and_bytes'):
        with pytest.raises(NotImplementedError):
            getattr(family, name)(CONFIG)


# ------------------------------------------------ the readers' arithmetic

HBM, PEAK = 819e9, 197e12                   # peaks.json, TPU v5 lite
STEP = 'jit(step_fn)/Dots3/layer_0/attn/'
SCOPED = [
    ('paged_index_scores.1 [tpu_custom_call]', 1.0, 1.2,
     STEP + 'indexer/pallas_call:'),
    ('fusion.1', 1.2, 1.3, STEP + 'indexer/reduce:'),
    ('paged_latent_attention.2 [tpu_custom_call]', 1.3, 1.6,
     STEP + 'kv_read/sparse_read/pallas_call:'),
    ('fusion.2', 1.6, 1.9,
     'jit(step_fn)/Dots3/layer_2/attn/kv_read/window_read/dot_general:'),
    ('fusion.3', 3.0, 3.5, 'jit(run)/Dots3/layer_0/attn/indexer/dot:')]


def records(scoped=SCOPED, config=CONFIG) -> dict:
    trace = trace_reduce.Trace(
        ops={0: [event[:3] for event in scoped]},
        modules={0: [('jit_step_fn(1)', 1.0, 2.0), ('jit_run(2)', 3.0, 3.6)]},
        host=[('chipbench.window', 0.0, 10.0)])
    return {
        'trace': trace, 'traced_window': (100.0, 110.0), 'config': config,
        'device_kind': 'TPU v5 lite',
        'program_trace': program_trace.ProgramTrace([], list(scoped)),
        # decoded inside the window: depths 3001, 3002 and 91
        'requests': [{'prompt': 3000, 'times': [99.0, 101.0, 102.0, 111.0]},
                     {'prompt': 90, 'times': [95.0, 100.5]}],
        'spans': []}


def spec(name: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{name}.json').read_text())


@pytest.mark.parametrize('part, seconds', [
    ('indexer', 0.3), ('sparse_read', 0.3), ('window_read', 0.3)])
def test_each_part_is_its_counts_over_its_scope_in_the_step(part, seconds,
                                                            capsys):
    """Only the decode step's operations under the part's scope count (the
    prefill program's ``indexer`` does not), and only what the algorithm
    reads: the positions held, the selected, the window's."""
    depths = [3001, 3002, 91]
    ops, moved = family.sparse_ops_and_bytes(CONFIG, part, depths)
    least = max(ops / PEAK, moved / HBM)
    got = sparse_roofline.read(records(), spec(f'{part}_roofline'))
    assert got == pytest.approx(100.0 * least / seconds)
    assert '3 decoded tokens' in capsys.readouterr().err


def test_the_parent_and_other_families_read_none():
    """A program without the scopes (the parent of this change), a run that
    was not traced, and a family with no such counts: nothing to read,
    nothing raised, the metric left out."""
    parent = records(scoped=[('fusion.9', 1.0, 2.0,
                              'jit(step_fn)/Dots3/layer_0/attn/kv_read/x:')])
    assert sparse_roofline.read(parent, spec('indexer_roofline')) is None
    untraced = records()
    untraced['traced_window'] = None
    assert sparse_roofline.read(untraced, spec('indexer_roofline')) is None
    other = records(config=json.loads((ROOT / 'chipbench' / 'configs'
                                       / 'deepseek-v2.json').read_text()))
    assert sparse_roofline.read(other, spec('window_read_roofline')) is None


@pytest.mark.parametrize('traffic', ['closed96-longctx', 'tiny'])
def test_every_seed_gets_the_same_work(traffic):
    """The driver's clients: the pool in rounds of four, one size from each
    quarter by prompt; the fill's requests cut to ``serve.Clients``' own
    shares, fixed by their size and round whatever the seed."""
    from chipbench.drivers import serve, serve_sparse
    mix = (MIX if traffic == 'tiny' else json.loads(
        (ROOT / 'chipbench' / 'traffic' / f'{traffic}.json').read_text()))
    fills = []
    for seed in (7, 2 ** 31 + 5):
        plain = serve.Clients(seed, mix, 100)
        even = serve_sparse.EvenClients(seed, mix, 100)
        assert sorted(even.sizes) == sorted(plain.sizes)
        assert sorted(even.under_way) == pytest.approx(
            sorted(plain.under_way))
        rounds = [even.sizes[k:k + 4] for k in range(0, len(even.sizes), 4)]
        assert all(four == sorted(four) for four in rounds)
        assert plain.sizes != sorted(plain.sizes)
        fills.append(sorted(
            (even.sizes[client % len(even.sizes)], round(share, 9))
            for client, share in enumerate(even.under_way)))
    # a fill of whole cycles of the pool is the same work at every seed
    assert (fills[0] == fills[1]) == (mix['clients'] % mix['pool'] == 0)
    assert serve_sparse.EvenClients(7, mix, 100).sizes \
        != serve_sparse.EvenClients(8, mix, 100).sizes


# ----------------------------------------- the tiny cell through the harness

MIX = dict(tiny.SERVE, driver='serve_sparse', clients=3, rows=3,
           block_size=4,
           prompt={'median': 24, 'sigma': 0.4, 'low': 12, 'high': 40},
           max_new={'median': 10, 'sigma': 0.4, 'low': 6, 'high': 20},
           warm_prompts=[12, 32, 60])
# float32 on the CPU: sound runs read 0 (the served token is the reference's
# best) or a float32 near-tie, and select what the reference selects; a
# window wider than stated reads a gap of 0.05 and more, a selection wider
# than stated a share of flips over 1 (the logits, which go through the
# program's selections, do not see it)
LIMITS = {'logit_gap_max': {'limit': 2e-3},
          'selection_flip_share': {'limit': 0.02}}


def add(root: pathlib.Path) -> dict:
    """The tiny configuration and its serving cell, written into ``root``
    (``tiny.build``'s) as new files and appended entries."""
    record = toy.snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    files = {'chipbench/configs/dots3-tiny.json': tiny_config(),
             'chipbench/traffic/dots3-tiny-long.json': MIX,
             f'chipbench/limits/{TINY_CELL}.json': LIMITS}
    for path, content in files.items():
        (root / path).write_text(json.dumps(content))
    new_files = set(files)
    bench['configs'].append({
        'name': 'dots3-tiny', 'source': 'test', 'why': 't',
        'file': 'chipbench/configs/dots3-tiny.json',
        'reduced': tiny_config()['reduced']})
    bench['workloads'].append({'name': TINY_CELL, 'config': 'dots3-tiny',
                               'traffic': 'dots3-tiny-long', 'chips': 1,
                               'why': 't'})
    have = {m['name'] for m in bench['end_to_end'] + bench['per_layer']}
    joined = {m['name'] for m in BENCH['end_to_end'] + BENCH['per_layer']
              if CELL in m.get('workloads', ())} & have
    toy.join(bench, TINY_CELL, joined)
    for name in OWN_METRICS:
        path = f'chipbench/metrics/{name}.json'
        shutil.copy(ROOT / path, root / path)
        new_files.add(path)
        entry = next(m for m in BENCH['per_layer'] if m['name'] == name)
        bench['per_layer'].append({**entry, 'workloads': [TINY_CELL]})
    return toy.written(record, bench, new_files, {TINY_CELL: {
        'config': tiny_config(), 'traffic': MIX, 'limits': LIMITS,
        'metrics': {'setup_s', *joined, *OWN_METRICS}}})


@pytest.fixture(scope='module')
def added(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp('chipbench-dots3'))
    record = add(root)
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        yield record


def test_nothing_that_was_there_changed_and_the_cell_finds_its_files(added):
    toy.unchanged(added)
    toy.found(added)


def test_the_tiny_serving_run_is_correct_through_the_real_driver(added,
                                                                capsys):
    result = tiny.run_cell(added['root'], TINY_CELL, seed=2 ** 31 + 41)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 2
    assert set(result['compared']) == set(LIMITS)
    assert result['compared']['selection_flip_share']['value'] == 0.0
    said = capsys.readouterr().err
    assert ("resolved {'stream_dtype': 'float32', 'decode_impl': 'flax'}"
            in said)


def test_a_window_wider_than_stated_is_not_correct(added, monkeypatch):
    """The planted fault: the program's sliding layers attend 32 positions
    where the configuration states 5; the reference keeps 5."""
    real = family.serve_module
    monkeypatch.setattr(family, 'serve_module', lambda config: (
        dataclasses.replace(real(config), window=32)))
    result = tiny.run_cell(added['root'], TINY_CELL, seed=2 ** 31 + 42)
    assert result['correct'] is False
    assert not (result['compared']['logit_gap_max']['value']
                <= result['compared']['logit_gap_max']['limit'])


def test_a_selection_wider_than_stated_is_not_correct(added, monkeypatch):
    """The planted fault: the program's full layers attend their 32 best
    positions where the configuration states 8; the reference keeps 8. It
    goes through the program's selections, so the logits agree; the share
    of selected positions where the two selections differ does not."""
    real = family.serve_module
    monkeypatch.setattr(family, 'serve_module', lambda config: (
        dataclasses.replace(real(config), index_topk=32)))
    result = tiny.run_cell(added['root'], TINY_CELL, seed=2 ** 31 + 43)
    assert result['correct'] is False
    compared = result['compared']
    assert compared['logit_gap_max']['value'] <= LIMITS['logit_gap_max'][
        'limit']
    assert compared['selection_flip_share']['value'] > 1.0

