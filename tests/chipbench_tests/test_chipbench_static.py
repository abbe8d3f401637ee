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

from chipbench import families, flops, harness, trace_reduce, traffic
from tests.chipbench_tests import tiny

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
    # the seam: a driver and a family are modules found by name
    assert (ROOT / 'chipbench' / 'drivers'
            / f'{loaded.traffic["driver"]}.py').exists()
    assert (ROOT / 'chipbench' / 'families'
            / f'{loaded.config["family"]}.py').exists()
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
    # which cells report it is BENCHMARK.json's to say, and nothing else's
    assert 'workloads' not in spec
    assert (ROOT / 'chipbench' / 'readers' / f'{spec["reader"]}.py').exists()
    moved = next(m for m in BENCH['end_to_end']
                 if m['name'] == entry['moves'])
    cells = entry.get('workloads') or [w['name'] for w in BENCH['workloads']]
    for cell in cells:
        assert 'workloads' not in moved or cell in moved['workloads'], (
            f'{metric} moves {moved["name"]}, which {cell} does not report')
    if metric.endswith('_roofline') or 'mfu' in metric:
        assert entry['unit'] == '%'


@pytest.mark.parametrize('name', [c['name'] for c in BENCH['configs']])
def test_configurations_state_their_source_and_cuts(name):
    entry = next(c for c in BENCH['configs'] if c['name'] == name)
    config = json.loads((ROOT / entry['file']).read_text())
    assert config['source'] == entry['source']
    assert entry['file'].startswith('chipbench/')
    tiny.check_cuts(config, entry)


def test_a_cut_without_its_published_value_or_deployment_is_refused():
    config = {'depth': 4, 'reduced': ['depth'], 'published': {'depth': 32},
              'deployment': 'eight chips share each layer; this is one'}
    tiny.check_cuts(config, {'reduced': ['depth']})
    for broken in ({**config, 'published': {}},
                   {**config, 'deployment': 'whole'},
                   {**config, 'reduced': ['width']},
                   {**config, 'published': {'depth': 4}}):
        with pytest.raises((AssertionError, KeyError)):
            tiny.check_cuts(broken, {'reduced': broken['reduced']})
    with pytest.raises(AssertionError):
        tiny.check_cuts(config, {'reduced': []})


@pytest.mark.parametrize('family', sorted(
    path.stem for path in (ROOT / 'chipbench' / 'families').glob('*.py')
    if path.stem != '__init__'))
def test_a_family_gives_every_name_the_readme_fixes(family):
    module = families.of({'family': family})     # refuses a missing name
    readme = (ROOT / 'chipbench' / 'README.md').read_text()
    for name in families.INTERFACE:
        assert callable(getattr(module, name)), name
        assert f'`{name}(' in readme, f'the README does not fix {name}'


def test_a_family_that_lacks_a_name_is_refused_when_it_is_found(monkeypatch):
    from chipbench.families import gpt2
    monkeypatch.delattr(gpt2, 'flash_layers')
    with pytest.raises(AttributeError, match='flash_layers'):
        families.of({'family': 'gpt2'})


def test_nothing_outside_the_families_names_a_model():
    """The acceptance grep of ISSUE 26, kept as a test."""
    named = re.compile(r'GPT2|n_layer|n_embd|n_head|n_positions|'
                       r'reference\.gpt2|reference import gpt2')
    bench = ROOT / 'chipbench'
    files = [bench / name for name in ('harness.py', 'run.py', 'check.py',
                                       'control.py', 'traffic.py', 'flops.py',
                                       'weights.py')]
    files += sorted((bench / 'drivers').glob('*.py'))
    files += [path for path in sorted((bench / 'readers').glob('*.py'))
              if path.name != 'program_trace.py']   # its notes quote a trace
    for path in files:
        assert not named.search(path.read_text()), path.name


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


def _attribute_gaps_by_every_span(gaps, host_spans, count=10):
    """``attribute_gaps`` as PR 23 wrote it, every span shown to every gap:
    the reference the quicker one has to equal, digit for digit."""
    totals: dict = {}
    spans = sorted(host_spans, key=lambda span: span[2] - span[1])
    for start, end in gaps:
        free = [(start, end)]
        for name, a, b in spans:
            if name == trace_reduce.WINDOW_SPAN:
                continue
            rest = []
            for lo, hi in free:
                cut_lo, cut_hi = max(lo, a), min(hi, b)
                if cut_hi <= cut_lo:
                    rest.append((lo, hi))
                    continue
                totals[name] = totals.get(name, 0.0) + (cut_hi - cut_lo)
                if lo < cut_lo:
                    rest.append((lo, cut_lo))
                if cut_hi < hi:
                    rest.append((cut_hi, hi))
            free = rest
        left = sum(hi - lo for lo, hi in free)
        if left > 0:
            totals['unattributed'] = totals.get('unattributed', 0.0) + left
    ranked = sorted(totals.items(), key=lambda item: -item[1])
    return [[name, seconds] for name, seconds in ranked[:count]]


@pytest.mark.parametrize('seed', range(6))
def test_gap_attribution_equals_the_loop_over_every_span(seed):
    """Nested, touching, equal-length and far-reaching spans over many
    gaps: the same names, the same seconds to the last bit, the same order."""
    rng = np.random.default_rng(seed)
    edges = np.sort(rng.uniform(0.0, 100.0, size=800)).tolist()
    gaps = list(zip(edges[::2], edges[1::2]))
    spans = [('chipbench.window', 0.0, 100.0), ('long', 10.0, 90.0)]
    for index, start in enumerate(rng.uniform(0.0, 99.0, size=40)):
        length = float(rng.choice([0.25, 0.25, 1.0, 3.0]))
        spans.append((f'span{index % 7}', float(start), float(start) + length))
        if index % 5 == 0:            # a span nested in the one before
            spans.append((f'inner{index % 3}', float(start) + 0.05,
                          float(start) + 0.05 + length / 3))
    spans.append(('touching', gaps[7][1], gaps[8][0]))   # lies in no gap
    order = rng.permutation(len(spans))
    spans = [spans[i] for i in order]
    want = _attribute_gaps_by_every_span(gaps, spans, count=50)
    assert trace_reduce.attribute_gaps(gaps, spans, count=50) == want
    assert 'touching' not in dict(want)
    assert {'long', 'unattributed', 'inner0', 'span3'} <= set(dict(want))
    assert trace_reduce.attribute_gaps(gaps, [], count=5) == \
        _attribute_gaps_by_every_span(gaps, [], count=5)


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
