"""What can be checked without running a cell: the files, the arithmetic,
the generators and the trace reduction."""

from __future__ import annotations

import json
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench import flops, harness, trace_reduce, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')


def _config(name: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'configs'
                       / f'{name}.json').read_text())


# ------------------------------------------------------------ BENCHMARK.json

def test_benchmark_has_exactly_the_contract_keys():
    assert set(BENCH) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= BENCH['run_seconds'] <= 51
    assert (ROOT / 'BENCHMARK.json').stat().st_size <= 64 * 1024
    assert any(m['name'] == 'setup_s' for m in BENCH['end_to_end'])


@pytest.mark.parametrize('group', ['configs', 'workloads', 'end_to_end',
                                   'per_layer'])
def test_names_and_units_are_well_formed(group):
    names = [entry['name'] for entry in BENCH[group]]
    assert len(names) == len(set(names))
    for entry in BENCH[group]:
        assert NAME.match(entry['name']), entry['name']
        if 'unit' in entry:
            assert UNIT.match(entry['unit']), entry['unit']
            assert entry['better'] in ('lower', 'higher')
        for key in ('why', 'layer', 'source'):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and '\n' not in entry[key]


def test_bounds_are_inside_the_contract():
    for metric in BENCH['end_to_end']:
        assert 0.01 <= metric['bound'] <= 0.1, metric
        assert metric['source'] in ('host_clock', 'device_trace')


@pytest.mark.parametrize('cell', [w['name'] for w in BENCH['workloads']])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    loaded = harness.load_cell(cell)
    assert loaded.chips in (1, 4)
    assert loaded.traffic['driver'] in ('train', 'serve')
    reported = {metric['name'] for metric in loaded.end_to_end}
    assert 'setup_s' in reported and len(reported) >= 2
    assert loaded.per_layer, 'a cell reports at least one per-layer metric'
    for name in loaded.limits:
        assert loaded.limits[name]['limit'] >= 0


@pytest.mark.parametrize('metric', [m['name'] for m in BENCH['per_layer']])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    entry = next(m for m in BENCH['per_layer'] if m['name'] == metric)
    spec = json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{metric}.json').read_text())
    for key in ('layer', 'unit', 'moves', 'source', 'better'):
        assert spec[key] == entry[key], key
    assert (ROOT / 'chipbench' / 'readers' / f'{spec["reader"]}.py').exists()
    moved = next(m for m in BENCH['end_to_end']
                 if m['name'] == entry['moves'])
    cells = entry.get('workloads') or [w['name'] for w in BENCH['workloads']]
    for cell in cells:
        assert 'workloads' not in moved or cell in moved['workloads'], (
            f'{metric} moves {moved["name"]}, which {cell} does not report')
    if metric.endswith('_roofline') or 'mfu' in metric:
        assert entry['unit'] == '%'


def test_configurations_state_their_source_and_cuts():
    for entry in BENCH['configs']:
        config = _config(entry['name'])
        assert config['source'] == entry['source']
        assert config['reduced'] == entry['reduced'] == []
        assert entry['file'].startswith('chipbench/')


def test_four_chip_cells_stay_within_a_quarter():
    four = sum(w['chips'] == 4 for w in BENCH['workloads'])
    assert four <= max(1, len(BENCH['workloads']) // 4)


# ------------------------------------------------------------------ flops.py

@pytest.mark.parametrize('name, layers, dim, matmul, per_token', [
    # 12*24*1024^2 + 50257*1024 = 301989888 + 51463168
    ('gpt2-medium', 24, 1024, 353453056,
     6 * 353453056 + 3 * 2 * 24 * 1024 * 1024),
    # 12*36*1280^2 + 50257*1280 = 707788800 + 64328960
    ('gpt2-large', 36, 1280, 772117760,
     6 * 772117760 + 3 * 2 * 36 * 1024 * 1280),
])
def test_flops_match_hand_worked_counts(name, layers, dim, matmul, per_token):
    config = _config(name)
    assert (config['n_layer'], config['n_embd']) == (layers, dim)
    assert flops.matmul_params(config) == matmul
    assert flops.train_ops_per_token(config, 1024) == per_token
    # a causal pass over S tokens: 2 ops a parameter and half the square
    assert flops.prefill_ops(config, 512) == 512 * (
        2 * matmul + 2 * layers * 512 * dim)
    assert flops.decode_ops(config, 300) == 2 * matmul + 4 * layers * 300 * dim


def test_parameter_counts_are_the_published_ones():
    for name, published in (('gpt2-medium', 354823168),
                            ('gpt2-large', 774030080)):
        config = _config(name)
        layers, dim = config['n_layer'], config['n_embd']
        every = (12 * layers * dim * dim + 13 * layers * dim + 2 * dim
                 + (config['vocab_size'] + config['n_positions']) * dim)
        assert every == published == config['parameters']


def test_flash_and_decode_chain_shapes():
    config = _config('gpt2-medium')
    ops, moved = flops.flash_ops_and_bytes(config, 8, 1024, backward=False)
    assert ops == 2 * 8 * 1024 * 1024 * 1024 and moved == 4 * 8 * 1024 * 1024 * 2
    back_ops, back_moved = flops.flash_ops_and_bytes(config, 8, 1024, True)
    assert (back_ops, back_moved) == (2 * ops, 2 * moved)
    large = _config('gpt2-large')
    ops, moved = flops.decode_chain_ops_and_bytes(large, 32, 1.0)
    assert ops == 2 * 32 * 707788800
    assert 707788800 < moved < 1.01 * 707788800 + 36 * 32 * 8 * 1280 * 2 + 1


def test_peaks_name_their_source_and_refuse_unknown_kinds():
    peak = flops.peaks('TPU v5 lite')
    assert peak['bf16_flops_per_s'] == 197e12
    assert peak['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError):
        flops.peaks('cpu')
    seconds, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert (seconds, bound) == (1.0, 'compute')
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, 'memory')


# ---------------------------------------------------------------- traffic.py

def test_training_rows_repeat_for_a_seed_and_differ_across_seeds():
    make = lambda seed: traffic.bigram_tokens(seed, samples=16, seq=64,
                                              vocab=50257)
    first, again, other = make(2 ** 31 + 7), make(2 ** 31 + 7), make(8)
    assert first.dtype == np.int32 and first.shape == (16, 64)
    assert (first == again).all() and (first != other).any()
    assert first.max() < 50257 and first.min() >= 0
    assert len({row.tobytes() for row in first}) == 16   # rows all differ


def test_request_sizes_are_one_pool_in_each_seeds_order():
    mix = json.loads((ROOT / 'chipbench' / 'traffic'
                      / 'closed32-chat.json').read_text())
    first, again = traffic.request_sizes(3, mix), traffic.request_sizes(3, mix)
    other = traffic.request_sizes(4, mix)
    assert first == again and first != other
    assert sorted(first) == sorted(other)       # same sizes, another order
    prompts = [p for p, _ in first]
    assert min(prompts) >= 48 and max(prompts) <= 768
    assert abs(float(np.median(prompts)) - 192) <= 2
    assert all(p + n <= 1024 and 16 <= n <= 256 for p, n in first)
    assert traffic.request_prompt(3, 5, 40, 50257) == \
        traffic.request_prompt(3, 5, 40, 50257)
    assert traffic.request_prompt(3, 5, 40, 50257) != \
        traffic.request_prompt(3, 6, 40, 50257)


# ----------------------------------------------------------- trace_reduce.py

def _synthetic() -> trace_reduce.Trace:
    ops = {0: trace_reduce.innermost([
        ('while.1 s32[]', 0.9, 7.6),            # a loop around everything
        ('fusion.1 bf16[8,64]', 1.0, 2.0), ('attn.7 [tpu_custom_call]', 2.0, 2.5),
        ('fusion.2 bf16[8,64]', 4.0, 5.0), ('attn.9 [tpu_custom_call]', 7.0, 7.5),
        ('before', 0.0, 0.5)])}
    modules = {0: [('jit_multi(1)', 1.0, 2.5), ('jit_multi(1)', 4.0, 5.0)]}
    host = [('chipbench.window', 1.0, 8.0), ('chipbench.step', 2.4, 4.2),
            ('chipbench.submit', 5.0, 6.0), ('chipbench.generate', 5.2, 5.4)]
    return trace_reduce.Trace(ops, modules, host)


def test_busy_union_and_idle_share():
    trace = _synthetic()
    assert trace_reduce.window_of(trace) == (1.0, 8.0)
    inside = trace_reduce.clip(trace.ops[0], 1.0, 8.0)
    assert trace_reduce.busy_seconds(inside) == pytest.approx(3.0)
    summary = trace_reduce.device_summary(trace)
    assert summary['busy_s'] == pytest.approx(3.0)
    assert summary['window_s'] == pytest.approx(7.0)
    assert trace_reduce.idle_gaps(trace.ops[0], 1.0, 8.0) == [
        (2.5, 4.0), (5.0, 7.0), (7.5, 8.0)]


def test_kernel_sum_and_top_ops():
    trace = _synthetic()
    assert 'while.1 s32[]' not in [name for name, _, _ in trace.ops[0]]
    pattern = json.loads((ROOT / 'chipbench' / 'metrics' /
                          'flash_roofline.train.json').read_text())['args']
    assert trace_reduce.kernel_seconds(
        trace.ops[0], pattern['kernel_patterns']) == pytest.approx(1.0)
    assert trace_reduce.kernel_seconds(trace.ops[0], ['nothing']) == 0
    assert trace_reduce.top_ops(trace.ops[0], 1) == [
        ['fusion bf16[8,64]', 2.0]]


def test_instruction_text_becomes_a_short_name():
    text = ('%attn.649 = (bf16[1,128,1024,64]{3,2,1,0:T(8,128)(2,1)}, '
            'bf16[128,1024,64]{2,1,0}) custom-call(bf16[128] %b), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert trace_reduce.short_name(text) == 'attn.649 [tpu_custom_call]'
    assert trace_reduce.short_name(
        '%fusion.5302 = bf16[50304,1024]{1,0:T(8,128)} fusion(bf16[8] %x)'
    ) == 'fusion.5302 bf16[50304,1024]'
    assert trace_reduce.short_name('jit_multi(123)') == 'jit_multi(123)'


def test_gap_attribution_prefers_the_innermost_span():
    trace = _synthetic()
    gaps = trace_reduce.idle_gaps(trace.ops[0], 1.0, 8.0)
    named = dict(trace_reduce.attribute_gaps(gaps, trace.host))
    assert named['chipbench.step'] == pytest.approx(1.5)
    assert named['chipbench.generate'] == pytest.approx(0.2)
    assert named['chipbench.submit'] == pytest.approx(0.8)
    assert named['unattributed'] == pytest.approx(1.5)
    assert 'chipbench.window' not in named


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.device_summary(trace_reduce.Trace({}, {}, []))


# --------------------------------------------------------------- the command

def test_the_command_refuses_to_run_without_a_tpu():
    done = subprocess.run(
        [sys.executable, 'chipbench/run.py', '--workload',
         BENCH['workloads'][0]['name'], '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=ROOT, capture_output=True, text=True,
        env={'JAX_PLATFORMS': 'cpu', 'PATH': '/usr/bin:/bin'}, timeout=120)
    assert done.returncode != 0
    assert done.stdout.strip() == ''
    assert 'TPU' in done.stderr
