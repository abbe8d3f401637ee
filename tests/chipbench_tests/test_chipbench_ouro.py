"""The ``ouro`` family in the benchmark: its configuration and cell as files,
its counts against hand-worked numbers, its seeded leaves as the reference
gets them, its reference reading, its reader's arithmetic on synthetic
records (no chip, no profiler), and its tiny cell through the real harness
and serving driver, sound and with a pass left out."""

from __future__ import annotations

import dataclasses
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, flops, harness, trace_reduce, traffic, weights
from chipbench.families import ouro as family
from chipbench.readers import loop_roofline, program_trace
from chipbench.reference import ouro as reference
from tests.chipbench_tests import ouro, tiny, toy
from tpusystem.serve.engine import prefill_bucket

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
CONFIG = ouro.SHIPPED
CELL = 'serve-ouro-closed8'
# the source's config.json, as the model-configs catalog holds it
SOURCE = {
    'head_dim': 128, 'hidden_act': 'silu', 'hidden_size': 2048,
    'intermediate_size': 5632, 'layer_types': ['full_attention'] * 48,
    'max_position_embeddings': 65536, 'max_window_layers': 48,
    'model_type': 'ouro', 'num_attention_heads': 16,
    'num_hidden_layers': 48, 'num_key_value_heads': 16,
    'rms_norm_eps': 1e-06, 'rope_scaling': None, 'rope_theta': 1000000,
    'sliding_window': None, 'tie_word_embeddings': False,
    'total_ut_steps': 4, 'early_exit_threshold': 1,
    'use_sliding_window': False, 'vocab_size': 49152}
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632                 # 51 380 224
HEAD = 49152 * 2048                                       # 100 663 296


# ------------------------------------------------------------- the files

def test_the_configuration_holds_the_published_keys_and_cuts_nothing():
    entry = next(c for c in BENCH['configs'] if c['name'] == 'ouro-2.6b')
    names = [c['name'] for c in BENCH['configs']]
    assert names.index('ouro-2.6b') == 4 and names[:4] == [
        'gpt2-medium', 'gpt2-large', 'deepseek-v2',
        'nemotron-3-nano-30b-a3b'], 'appended, not inserted'
    assert CONFIG['family'] == 'ouro'
    for key, value in SOURCE.items():
        assert CONFIG[key] == value, key
    assert CONFIG['reduced'] == [] == entry['reduced']
    tiny.check_cuts(CONFIG, entry)
    assert 'one chip holds the whole model' in CONFIG['deployment']
    assert set(CONFIG['assumed']) >= {
        'sandwich_norms', 'final_norm_each_pass', 'no_bias', 'max_seq',
        'weights', 'exit_gate'}
    as_run = CONFIG['as_run']
    assert {as_run[key] for key in ('param_dtype', 'compute_dtype',
                                    'stream_dtype', 'kv_cache_dtype')} == {
        'bfloat16'}
    assert (as_run['decode_impl'], as_run['max_seq'], as_run['levers']) == (
        'flax', 512, {'stream_dtype': 'bfloat16', 'bucket_floor': 256})
    assert CONFIG['reference'] == {'sample_requests': 6,
                                   'control': {'bits': 8}}


def test_the_published_count_is_every_leaf_of_the_tree():
    """48 layers of 51 388 416 (the seven matrices and four norms), table
    and head, the final norm, the exit gate's 2048 weights and its bias."""
    leaves = (list(family.top_leaves(CONFIG).values())
              + [leaf for index in range(48)
                 for leaf in family.layer_leaves(CONFIG, index).values()])
    every = sum(int(np.prod(shape)) for _, shape in leaves)
    assert every == 48 * (LAYER + 4 * 2048) + 2 * HEAD + 2048 + 2049
    assert every == CONFIG['parameters'] == 2_667_974_657
    assert every * 2 / 1e9 == pytest.approx(5.336, abs=1e-3)   # bfloat16


def test_the_cell_holds_its_traffic_and_joins_its_metrics():
    cell = harness.load_cell(CELL)
    names = [w['name'] for w in BENCH['workloads']]
    assert names.index(CELL) == 4 and cell.chips == 1
    mix = cell.traffic
    assert (mix['driver'], mix['loop'], mix['clients'], mix['rows'],
            mix['block_size'], mix['pool']) == ('serve', 'closed', 8, 8, 16,
                                                32)
    assert mix['prompt'] == {'median': 128, 'sigma': 0.6, 'low': 32,
                             'high': 256}
    assert mix['max_new'] == {'median': 160, 'sigma': 0.5, 'low': 48,
                              'high': 256}
    assert (mix['greedy'], mix['share_prefix'], mix['trace_seconds'],
            mix['drain_seconds']) == (True, False, 8, 60)
    sizes = traffic.request_sizes(7, mix)
    assert len(sizes) == 32 and sorted(sizes) == sorted(
        traffic.request_sizes(2 ** 31 + 5, mix))
    assert all(32 <= p <= 256 and 48 <= n <= 256
               and p + n <= family.positions(CONFIG) for p, n in sizes)
    # the warm buckets are exactly those the pool's prompts reach: every
    # prompt pads to the configuration's floor of 256 (the pool's median
    # lies on the 128-bucket's edge, 16 of 32 prompts on either side)
    floor = CONFIG['as_run']['levers']['bucket_floor']
    buckets = {prefill_bucket(max(p, floor), 16, 512) for p, _ in sizes}
    assert buckets == set(mix['warm_prompts']) == {256}
    assert sum(p <= 128 for p, _ in sizes) == 16
    # 8 rows of 512 positions: 257 blocks of 16 with the trash block
    assert 8 * 512 // 16 + 1 == 257
    reported = {m['name'] for m in cell.end_to_end + cell.per_layer}
    assert reported == {
        'setup_s', 'serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms',
        'row_occupancy', 'queue_wait_ms', 'admit_ms', 'kv_blocks_live_share',
        'prefill_share', 'decode_tick_ms', 'step_mfu.serve',
        'device_idle_share.serve', 'host_gap_share.serve',
        *(m['name'] for m in BENCH['per_layer']
          if m['name'].startswith('tick_gap_ms.')),
        'scope_share.kv_read', 'scope_share.select', 'setup_lower_s',
        'setup_backend_s', 'setup_engine_s', 'compile_cache_misses',
        'window_compiles', 'kv_read_roofline', *ouro.OWN_METRICS}
    assert len(reported) == 4 + 9 + 9 + 2 + 5 + 1 + 1
    per_layer = [m['name'] for m in BENCH['per_layer']]
    assert per_layer[-1] == 'loop_weight_roofline'
    assert BENCH['per_layer'][-1]['workloads'] == [CELL]
    limit = cell.limits['logit_gap_max']
    assert limit['lower'] < limit['limit'] < limit['upper']


def test_the_family_module_serves_and_refuses_what_it_says():
    assert families.of(CONFIG) is family
    served = family.serve_module(CONFIG)
    assert (served.layers, served.passes, served.dim, served.heads,
            served.kv_heads, served.head_dim, served.ffn_dim,
            served.vocab_size, served.max_seq, served.dtype,
            served.rope_theta, served.eps, served.early_exit_threshold) == (
        48, 4, 2048, 16, 16, 128, 5632, 49152, 512, 'bfloat16', 1e6, 1e-6,
        1.0)
    assert family.vocab_size(CONFIG) == 49152
    assert family.positions(CONFIG) == 512
    for name in ('train_module', 'reference_training', 'train_ops_per_token',
                 'flash_layers', 'flash_ops_and_bytes',
                 'decode_chain_ops_and_bytes'):
        with pytest.raises(NotImplementedError):
            getattr(family, name)(CONFIG)


# ------------------------------------------------------------ the counts

def test_counts_match_hand_worked_numbers():
    assert family.layer_params(CONFIG) == LAYER
    loop = 4 * 48 * LAYER                                 # 9 865 003 008
    assert family.loop_params(CONFIG) == loop == 9_865_003_008
    assert flops.matmul_params(CONFIG) == loop + HEAD
    # each pass attends its own keys: 4 x 48 layers of 16 heads x (128 +
    # 128) at 2 operations
    attended = 4 * 48 * 2 * 16 * 256
    assert flops.prefill_ops(CONFIG, 256) == 256 * (
        2 * (loop + HEAD) + 128 * attended)
    assert flops.decode_ops(CONFIG, 300) == 2 * (loop + HEAD) + 300 * attended
    # a key and a value of 2048 bfloat16 lanes in each of 192 caches
    assert flops.kv_bytes_per_position(CONFIG) == 4 * 48 * 2 * 2048 * 2
    assert flops.kv_bytes_per_position(CONFIG) == 1_572_864
    ops, moved = family.loop_weight_ops_and_bytes(CONFIG, ticks=1, tokens=8)
    assert (ops, moved) == (2.0 * 8 * loop, 2.0 * loop)
    # what a decode tick streams: four passes of the stack and the head
    assert moved + 2 * HEAD == 19_931_332_608
    assert moved / 819e9 * 1e3 == pytest.approx(24.09, abs=0.01)  # ms


# ------------------------------------------------------- the seeded leaves

def test_the_reference_gets_the_programs_leaves_widened():
    config = ouro.tiny_config(
        as_run=dict(ouro.tiny_config()['as_run'], param_dtype='bfloat16'))
    made = weights.flatten(family.make(config, 3))
    assert {leaf.dtype for leaf in made.values()} == {jnp.dtype('bfloat16')}
    leaves_of = family.reference_leaves(config, 3)
    for group in ('top', 0, 1):
        table = (family.top_leaves(config) if group == 'top'
                 else family.layer_leaves(config, group))
        for name, leaf in leaves_of(group).items():
            path = table[name][0] if group == 'top' \
                else f'layer_{group}/{table[name][0]}'
            assert leaf.dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(made[path], np.float32))
    first, second = leaves_of(0), leaves_of(1)
    assert not np.array_equal(first['q'], second['q'])    # a key each
    assert float(jnp.mean(first['input_norm'])) == pytest.approx(1.0,
                                                                 abs=0.02)
    assert float(jnp.std(first['gate'])) == pytest.approx(0.02, rel=0.1)


# -------------------------------------------------- the reference's reading

def test_served_gap_reads_zero_on_the_references_own_tokens_and_sees_a_fault():
    config = ouro.tiny_config()
    model = family.reference_model(config)
    prompt = np.random.default_rng(4).integers(0, 256, 30).tolist()
    ids = list(prompt)
    for _ in range(5):                     # the reference's own greedy tokens
        padded = np.zeros(128, np.int32)
        padded[:len(ids)] = ids
        scores = reference.logits([jnp.asarray(padded)],
                                  family.reference_leaves(config, 9),
                                  model)[0]
        ids.append(int(jnp.argmax(scores[len(ids) - 1])))
    widest, covered = family.served_gap(config, 9, [(prompt, ids[30:])])
    assert (widest, covered) == (0.0, 5)
    altered = ids[30:32] + [(ids[32] + 1) % 256] + ids[33:]
    widest, _ = family.served_gap(config, 9, [(prompt, altered)])
    assert widest > 0.01
    control, _ = family.served_gap(config, 9, [(prompt, ids[30:])],
                                   control_bits=2)
    assert control > 0.0                   # 2-bit matrices put another first


def test_the_control_narrows_the_matrices_and_nothing_else():
    wide = family.reference_leaves(ouro.tiny_config(), 2)(0)
    low = reference.narrow(wide, 4)
    for name, leaf in wide.items():
        same = bool(jnp.all(low[name] == leaf))
        assert same == (name not in ('q', 'k', 'v', 'o', 'gate', 'up',
                                     'down')), name


# -------------------------------------------------- the reader's arithmetic

HBM, PEAK = 819e9, 197e12                   # peaks.json, TPU v5 lite
STEP = 'jit(step_fn)/Ouro/loop_pass/layer_0/'
SCOPED = [
    # (short name, start, end, scope path) on the trace's clock
    ('fusion.1', 1.0, 1.5, STEP + 'attn/q/dot_general:'),
    ('paged_decode_attention.3 [tpu_custom_call]', 1.5, 1.6,
     STEP + 'attn/pass_0/kv_read/pallas_call:'),
    ('fusion.2', 1.6, 1.9, STEP + 'down/dot_general:'),
    ('fusion.3', 1.9, 2.0, 'jit(step_fn)/Ouro/head/dot_general:'),
    ('fusion.4', 3.0, 3.5, 'jit(run)/Ouro/loop_pass/layer_0/up/dot:'),
    ('fusion.5', 9.8, 10.6, STEP + 'gate/dot_general:')]   # cut at 10 s


def records(scoped=SCOPED) -> dict:
    """A 10 s traced window: trace clock 0-10, host clock 100-110; three
    decode ticks begin and end inside it, one is cut at its end."""
    trace = trace_reduce.Trace(
        ops={0: [event[:3] for event in scoped]},
        modules={0: [('jit_step_fn(1)', 1.0, 2.0), ('jit_run(2)', 3.0, 3.6),
                     ('jit_step_fn(1)', 4.0, 4.5),
                     ('jit_step_fn(1)', 6.0, 6.5),
                     ('jit_step_fn(1)', 9.8, 10.6)]},
        host=[('chipbench.window', 0.0, 10.0)])
    return {
        'trace': trace, 'traced_window': (100.0, 110.0), 'config': CONFIG,
        'device_kind': 'TPU v5 lite',
        'program_trace': program_trace.ProgramTrace([], list(scoped)),
        # decoded tokens inside the window: 102, 103, 100.5
        'requests': [{'prompt': 300, 'times': [101.0, 102.0, 103.0, 111.0]},
                     {'prompt': 90, 'times': [90.0, 95.0, 100.5]},
                     {'prompt': 77, 'times': [108.0]}],
        'spans': []}


def spec() -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / 'loop_weight_roofline.json').read_text())


def test_loop_weight_roofline_counts_the_passes_over_the_steps(capsys):
    """The time is the decode step's whole executions, the kernel, the head
    and the compiler's unscoped copies in them included: the three ticks
    whose middle lies in the window (1.0, 0.5, 0.5 s), not the one cut at
    its end, nor the prefill program."""
    ticks, tokens = 3, 3
    spent = 1.0 + 0.5 + 0.5
    by_bytes = ticks * 2 * 4 * 48 * LAYER / HBM
    assert by_bytes > 2 * tokens * 4 * 48 * LAYER / PEAK
    got = loop_roofline.read(records(), spec())
    assert got == pytest.approx(100.0 * by_bytes / spent)
    said = capsys.readouterr().err
    assert 'bound by memory' in said and '3 ticks, 3 tokens decoded' in said


def test_copies_without_a_scope_count_with_the_step():
    """The compiler streams the matrices through copies with no scope path:
    they lie inside the step's executions and so in its time, and adding
    them to the trace moves nothing."""
    copies = SCOPED + [('copy-done.7', 4.0, 4.4, ''),
                       ('slice-done.2', 6.1, 6.3, '')]
    assert loop_roofline.read(records(copies), spec()) == pytest.approx(
        loop_roofline.read(records(), spec()))


def test_a_program_without_the_scope_reads_none():
    """The parent of the change, and a run that was not traced: nothing to
    read, nothing raised, the metric left out."""
    parent = records(scoped=[('fusion.9', 1.0, 2.0,
                              'jit(step_fn)/GPT2/h_1/attn/dot:')])
    assert loop_roofline.read(parent, spec()) is None
    untraced = records()
    untraced['traced_window'] = None
    assert loop_roofline.read(untraced, spec()) is None
    no_trace = records()
    no_trace['program_trace'] = None
    assert loop_roofline.read(no_trace, spec()) is None
    idle = records()
    idle['requests'] = []
    assert loop_roofline.read(idle, spec()) is None


# ----------------------------------------- the tiny cell through the harness

@pytest.fixture(scope='module')
def added(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp('chipbench-ouro'))
    record = ouro.add(root)
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        yield record


def test_nothing_that_was_there_changed_and_the_cell_finds_its_files(added):
    toy.unchanged(added)
    toy.found(added)
    cell = harness.load_cell(ouro.CELL, added['root'])
    tiny.check_cuts(cell.config, {'reduced': cell.config['reduced']})


def test_the_tiny_serving_run_is_correct_through_the_real_driver(added,
                                                                capsys):
    result = tiny.run_cell(added['root'], ouro.CELL, seed=2 ** 31 + 77)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 2
    assert set(result['metrics']) == {'serve_tokens_per_s', 'ttft_p50_ms',
                                      'itl_p95_ms', 'setup_s'}
    said = capsys.readouterr().err
    assert ("resolved {'stream_dtype': 'float32', 'decode_impl': 'flax'}"
            in said)


def test_a_pass_left_out_is_not_correct(added, monkeypatch):
    """The planted fault: the program runs one pass fewer than the
    configuration states; the reference runs them all."""
    real = family.serve_module
    monkeypatch.setattr(family, 'serve_module', lambda config: (
        dataclasses.replace(real(config),
                            passes=config['total_ut_steps'] - 1)))
    result = tiny.run_cell(added['root'], ouro.CELL, seed=2 ** 31 + 78)
    assert result['correct'] is False
    assert not (result['compared']['logit_gap_max']['value']
                <= result['compared']['logit_gap_max']['limit'])
