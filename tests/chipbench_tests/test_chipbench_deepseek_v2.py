"""The ``deepseek_v2`` family in the benchmark: its configuration and cell as
files, its counts against hand-worked numbers, its seeded leaves pinned bit
for bit, its reference reading, its readers' arithmetic on synthetic records
(no chip, no profiler), and its tiny cell through the real harness and
serving driver, sound and with a fault planted."""

from __future__ import annotations

import hashlib
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, flops, harness, trace_reduce, traffic, weights
from chipbench.families import deepseek_v2 as family
from chipbench.readers import (expert_imbalance, expert_roofline,
                               latent_read_roofline, program_trace,
                               scope_kernel_share)
from chipbench.reference import deepseek_v2 as reference
from tests.chipbench_tests import dsv2, tiny, toy

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
CONFIG = dsv2.SHIPPED
CELL = 'serve-dsv2-closed64'
# the source's config.json, as the model-configs catalog holds it
SOURCE = {
    'attention_bias': False, 'first_k_dense_replace': 1, 'hidden_act': 'silu',
    'hidden_size': 5120, 'intermediate_size': 12288, 'kv_lora_rank': 512,
    'max_position_embeddings': 163840, 'model_type': 'deepseek_v2',
    'moe_intermediate_size': 1536, 'moe_layer_freq': 1, 'n_group': 8,
    'n_routed_experts': 160, 'n_shared_experts': 2, 'norm_topk_prob': False,
    'num_attention_heads': 128, 'num_experts_per_tok': 6,
    'num_hidden_layers': 60, 'num_key_value_heads': 128, 'q_lora_rank': 1536,
    'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64, 'rms_norm_eps': 1e-06,
    'rope_scaling': {'beta_fast': 32, 'beta_slow': 1, 'factor': 40,
                     'mscale': 0.707, 'mscale_all_dim': 0.707,
                     'original_max_position_embeddings': 4096,
                     'type': 'yarn'},
    'rope_theta': 10000, 'routed_scaling_factor': 16,
    'scoring_func': 'softmax', 'seq_aux': True, 'tie_word_embeddings': False,
    'topk_group': 3, 'topk_method': 'group_limited_greedy', 'v_head_dim': 128,
    'vocab_size': 102400}
HELD = {'num_hidden_layers': 5, 'n_routed_experts': 40, 'vocab_size': 25600}
MLA = (5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256
       + 128 * 128 * 5120)                                   # 149 225 472
EXPERT = 3 * 5120 * 1536                                     # 23 592 960


# ------------------------------------------------------------- the files

def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    entry = next(c for c in BENCH['configs'] if c['name'] == 'deepseek-v2')
    assert entry == BENCH['configs'][-1], 'appended, not inserted'
    assert CONFIG['family'] == 'deepseek_v2'
    assert CONFIG['reduced'] == list(HELD) == entry['reduced']
    for key, value in SOURCE.items():
        if key in HELD:
            assert CONFIG[key] == HELD[key]
            assert CONFIG['published'][key] == value
        else:
            assert CONFIG[key] == value, key
    tiny.check_cuts(CONFIG, entry)
    assert 'four chips share each layer' in CONFIG['deployment']
    assert not set(CONFIG['reduced']) & {      # never a width
        'hidden_size', 'intermediate_size', 'moe_intermediate_size',
        'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim', 'qk_rope_head_dim',
        'v_head_dim', 'num_attention_heads', 'num_experts_per_tok'}
    as_run = CONFIG['as_run']
    assert {as_run[key] for key in ('param_dtype', 'compute_dtype',
                                    'stream_dtype', 'kv_cache_dtype')} == {
        'bfloat16'}
    assert (as_run['decode_impl'], as_run['max_seq'], as_run['levers']) == (
        'flax', 5120, {'stream_dtype': 'bfloat16'})
    assert CONFIG['reference']['control'] == {'bits': 8}
    assert set(CONFIG['assumed']) >= {'rope_pairing', 'weights', 'max_seq'}
    # the published count, from the published shapes
    layer = MLA + 1536 + 512 + 2 * 5120
    whole = (2 * 102400 * 5120 + 5120 + layer + 3 * 5120 * 12288
             + 59 * (layer + 5120 * 160 + 162 * EXPERT))
    assert CONFIG['parameters'] == whole == 235_741_434_880


def test_the_cell_is_the_issues_traffic_and_joins_its_metrics():
    cell = harness.load_cell(CELL)
    assert BENCH['workloads'][-1]['name'] == CELL and cell.chips == 1
    mix = cell.traffic
    assert (mix['driver'], mix['loop'], mix['clients'], mix['rows'],
            mix['block_size'], mix['pool']) == ('serve', 'closed', 64, 64,
                                                16, 64)
    assert mix['prompt'] == {'median': 1024, 'sigma': 0.6, 'low': 256,
                             'high': 4096}
    assert mix['max_new'] == {'median': 384, 'sigma': 0.5, 'low': 64,
                              'high': 1024}
    assert (mix['greedy'], mix['share_prefix'], mix['trace_seconds'],
            mix['drain_seconds']) == (True, False, 8, 60)
    assert mix['warm_prompts'] == [256, 512, 1024, 2048, 4096]
    sizes = traffic.request_sizes(7, mix)
    assert len(sizes) == 64 and sorted(sizes) == sorted(
        traffic.request_sizes(2 ** 31 + 5, mix))
    assert all(256 <= p <= 4096 and 64 <= n <= 1024
               and p + n <= family.positions(CONFIG) for p, n in sizes)
    # every warm bucket is one the pool's prompts reach, and none is missed
    buckets = {1 << (p - 1).bit_length() for p, _ in sizes}
    assert buckets == set(mix['warm_prompts'])
    reported = {m['name'] for m in cell.end_to_end + cell.per_layer}
    assert reported == {
        'setup_s', 'serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms',
        'row_occupancy', 'queue_wait_ms', 'admit_ms', 'kv_blocks_live_share',
        'prefill_share', 'decode_tick_ms', 'step_mfu.serve',
        'device_idle_share.serve', 'host_gap_share.serve',
        'tick_gap_ms.dispatch', 'tick_gap_ms.read', 'tick_gap_ms.rows',
        'tick_gap_ms.narrate', 'tick_gap_ms.seat', 'scope_share.kv_read',
        'scope_share.select', *dsv2.OWN_METRICS}
    for entry in BENCH['per_layer'][-len(dsv2.OWN_METRICS):]:
        assert entry['workloads'] == [CELL]
        assert entry['name'] in dsv2.OWN_METRICS
    assert 0 <= cell.limits['logit_gap_max']['limit']


def test_the_family_module_serves_and_refuses_what_it_says():
    module = families.of(CONFIG)
    assert module is family
    served = family.serve_module(CONFIG)
    assert (served.layers, served.experts, served.held, served.vocab_size,
            served.max_seq, served.dtype) == (5, 160, (0, 40), 25600, 5120,
                                              'bfloat16')
    assert [served.expert_layer(i) for i in range(5)] == [False] + [True] * 4
    assert family.vocab_size(CONFIG) == 25600
    assert family.positions(CONFIG) == 5120
    for name in ('train_module', 'reference_training', 'train_ops_per_token',
                 'flash_layers', 'flash_ops_and_bytes',
                 'decode_chain_ops_and_bytes'):
        with pytest.raises(NotImplementedError):
            getattr(family, name)(CONFIG)
    # the tree it would hand over: 5.164 B parameters, 10.33 GB in bfloat16
    held = sum(int(np.prod(shape)) for _, shape in
               list(family.top_leaves(CONFIG).values())
               + [leaf for index in range(5)
                  for leaf in family.layer_leaves(CONFIG, index).values()])
    assert held == 5_163_975_680


# ------------------------------------------------------------ the counts

def test_counts_match_hand_worked_numbers():
    assert family._mla_params(CONFIG) == MLA == 149_225_472
    assert family.expert_params(CONFIG) == EXPERT
    dense_layer = MLA + 3 * 5120 * 12288                     # 337 969 152
    expert_layer = MLA + 2 * EXPERT + 5120 * 160 + 1.5 * EXPERT
    matmul = dense_layer + 4 * expert_layer + 25600 * 5120
    assert flops.matmul_params(CONFIG) == matmul == 1_399_521_280
    # a causal pass: 2 ops a parameter, half the square at 128 x (192 + 128)
    assert flops.prefill_ops(CONFIG, 1024) == 1024 * (
        2 * matmul + 5 * 2 * 512 * 128 * 320)
    # a decoded token attends latent rows: score over 576, mix over 512
    assert flops.decode_ops(CONFIG, 2000) == (
        2 * matmul + 5 * 2 * 2000 * 128 * 1088)
    assert flops.kv_bytes_per_position(CONFIG) == 5 * 576 * 2 == 5760
    ops, moved = family.latent_read_ops_and_bytes(CONFIG, 1000)
    assert (ops, moved) == (1000 * 5 * 278_528.0, 1000 * 5760.0)
    assert ops / moved == pytest.approx(241.8, abs=0.1)      # the chip's ridge
    ops, moved = family.expert_ops_and_bytes(CONFIG, hit=146, seated=384)
    assert (ops, moved) == (384 * 2.0 * EXPERT, 146 * 2.0 * EXPERT)
    assert 2 * EXPERT == 47_185_920                           # 47.2 MB a hit


# ------------------------------------------------------- the seeded leaves

PINNED = json.loads((pathlib.Path(__file__).parent
                     / 'deepseek_v2_weight_digests.json').read_text())


def leaf_digests(tree: dict) -> dict:
    out = {}
    for name, leaf in weights.flatten(tree).items():
        leaf = np.asarray(leaf)
        out[name] = hashlib.sha256(
            str(leaf.dtype).encode() + str(leaf.shape).encode()
            + leaf.tobytes()).hexdigest()[:16]
    return out


@pytest.mark.parametrize('seed', sorted(PINNED))
def test_every_seeded_leaf_is_bit_for_bit_what_pr_32_made(seed):
    config = dsv2.tiny_config()
    made = family.make(config, int(seed))
    assert leaf_digests(made) == PINNED[seed]
    # the reference is handed the same draws under its own names
    leaves_of = family.reference_leaves(config, int(seed))
    flat = weights.flatten(made)
    for group in ('top', 0, 1, 2):
        table = (family.top_leaves(config) if group == 'top'
                 else family.layer_leaves(config, group))
        for name, leaf in leaves_of(group).items():
            path = table[name][0] if group == 'top' \
                else f'layer_{group}/{table[name][0]}'
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat[path]))


def test_a_bfloat16_configuration_draws_bfloat16_and_widens_for_the_reference():
    config = dsv2.tiny_config(as_run=dict(dsv2.tiny_config()['as_run'],
                                          param_dtype='bfloat16'))
    made = weights.flatten(family.make(config, 3))
    assert {leaf.dtype for leaf in made.values()} == {jnp.dtype('bfloat16')}
    wide = family.reference_leaves(config, 3)(1)
    assert wide['gate'].dtype == jnp.float32 and wide['gate'].shape == (
        8, 64, 48)
    np.testing.assert_array_equal(
        np.asarray(wide['router']),
        np.asarray(made['layer_1/moe/router'].astype(jnp.float32)))
    assert float(jnp.mean(wide['attn_norm'])) == pytest.approx(1.0, abs=0.02)
    assert float(jnp.std(wide['gate'])) == pytest.approx(0.02, rel=0.05)


# -------------------------------------------------- the reference's reading

def test_served_gap_reads_zero_on_the_references_own_tokens_and_sees_a_fault():
    config = dsv2.tiny_config()
    model = family.reference_model(config)
    prompt = np.random.default_rng(4).integers(0, 256, 30).tolist()
    ids = list(prompt)
    for _ in range(5):                     # the reference's own greedy tokens
        padded = np.zeros(128, np.int32)
        padded[:len(ids)] = ids
        scores = reference.logits([jnp.asarray(padded)],
                                  family.reference_leaves(config, 9), 3,
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


def test_served_gap_goes_through_the_experts_the_program_recorded(capsys):
    """A configuration whose module was built holds a record among its
    levers; ``served_gap`` takes every sampled request's routing from it
    (one that is missing is an error, not the reference's own), pads it with
    -1, and the reference counts the choices that were not its own."""
    config = dsv2.tiny_config()
    assert family.routed(config) is None
    family.serve_module(config)
    record = family.routed(config)
    assert config['as_run']['levers']['routing_sink'] == record.record
    prompt = np.random.default_rng(5).integers(0, 256, 30).tolist()
    tokens = [7, 8, 9, 10]
    with pytest.raises(KeyError):
        family.served_gap(config, 9, [(prompt, tokens)])
    # the reference's own choices, as a program would have recorded them
    model = family.reference_model(config)
    leaves = family.reference_leaves(config, 9)
    ids = jnp.asarray(prompt + tokens)
    x, own = leaves('top')['embedding'][ids], []
    for index in range(3):
        p = leaves(index)
        if 'router' in p:
            h = reference.rms_norm(
                x + reference.latent_attention(
                    reference.rms_norm(x, p['attn_norm'], 1e-6), p, model,
                    'float32'), p['ffn_norm'], 1e-6)
            chosen, _ = reference.route(h, p['router'], model, 'float32')
            own.append(np.stack([np.flatnonzero(row)
                                 for row in np.asarray(chosen)]))
        x, _ = reference.layer(x, p, model=model)
    routing = np.stack(own, axis=1)[:33].astype(np.uint8)    # [33, 2, 3]
    record.record('r0', prompt, tokens, routing)
    plain, _ = family.served_gap(dsv2.tiny_config(), 9, [(prompt, tokens)])
    given, covered = family.served_gap(config, 9, [(prompt, tokens)])
    assert covered == 4 and given == pytest.approx(plain, abs=1e-5)
    assert 'own at 0 of 66 choices' in capsys.readouterr().err
    # held experts are 4-11: at a served position one held expert goes and
    # another held one comes, and the logits from there on move
    swapped = routing.copy()
    at = next(position for position in range(29, 33)
              if any(4 <= e < 12 for e in routing[position, 0]))
    gone = next(slot for slot, e in enumerate(routing[at, 0]) if 4 <= e < 12)
    swapped[at, 0, gone] = next(e for e in range(4, 12)
                                if e not in routing[at, 0])
    record.record('r0', prompt, tokens, swapped)
    moved, _ = family.served_gap(config, 9, [(prompt, tokens)])
    # the swap itself, and whatever it moved in the layer after
    assert 'own at 0 of' not in capsys.readouterr().err
    assert abs(moved - plain) > 1e-5


# -------------------------------------------------- the readers' arithmetic

HBM, PEAK = 819e9, 197e12                   # peaks.json, TPU v5 lite
STEP = 'jit(step_fn)/DeepSeekV2/layer_1/attn/'
SCOPED = [
    # (short name, start, end, scope path) on the trace's clock
    ('fusion.1', 1.0, 1.4, STEP + 'cond/branch_3_fun/kv_read/gather:'),
    ('fusion.2', 1.4, 1.5, STEP + 'cond/branch_3_fun/kv_read/dot_general:'),
    ('fusion.3', 2.0, 2.1, 'jit(step_fn)/DeepSeekV2/layer_1/moe/experts/'
                           'take:'),
    # the grouped product's Mosaic call: named by the compiler, no scope
    ('ragged-dot-none.2 [tpu_custom_call]', 2.1, 2.3, ''),
    ('fusion.4', 2.3, 2.5, 'jit(step_fn)/DeepSeekV2/layer_1/moe/router/sort:'),
    # the prefill program's, under the same scope names: not the tick's
    ('fusion.5', 3.0, 4.0, 'jit(run)/DeepSeekV2/layer_1/attn/kv_read/dot:'),
    ('fusion.6', 4.0, 5.0, 'jit(run)/DeepSeekV2/layer_1/moe/experts/dot:'),
    ('ragged-dot-none.7 [tpu_custom_call]', 5.0, 6.0, ''),
    ('fusion.7', 9.8, 10.6, STEP + 'kv_read/gather:')]       # cut at 10 s


def mark(at: float, **counts) -> dict:
    return {'name': 'expert_load', 'ph': 'i', 'ts': at * 1e6, 'args': counts}


def records(scoped=SCOPED, spans=None) -> dict:
    """A 10 s traced window: trace clock 0-10, host clock 100-110."""
    trace = trace_reduce.Trace(
        ops={0: [event[:3] for event in scoped]},
        modules={0: [('jit_step_fn(1)', 1.0, 2.6), ('jit_run(2)', 3.0, 6.5),
                     ('jit_step_fn(1)', 9.5, 10.8)]},
        host=[('chipbench.window', 0.0, 10.0)])
    return {
        'trace': trace, 'traced_window': (100.0, 110.0), 'config': CONFIG,
        'device_kind': 'TPU v5 lite',
        'program_trace': program_trace.ProgramTrace([], list(scoped)),
        'requests': [{'prompt': 1000, 'times': [101.0, 102.0, 103.0, 111.0]},
                     {'prompt': 3000, 'times': [90.0, 95.0, 100.5]},
                     {'prompt': 700, 'times': []}],
        'spans': spans if spans is not None else [
            mark(99.0, seated=90, hit=80, largest=9),         # before it
            mark(101.0, seated=96, hit=70, largest=8),
            mark(102.0, seated=104, hit=76, largest=12),
            {'name': 'admit', 'ph': 'X', 'ts': 101e6, 'dur': 5, 'args': {}}]}


def spec(name: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{name}.json').read_text())


def test_latent_read_roofline_counts_held_positions_over_the_ticks_scope(capsys):
    attended = 1001 + 1002 + 3002
    spent = 0.4 + 0.1 + 0.2                 # the last is cut at 10 s
    by_bytes = attended * 5760 / HBM
    by_ops = attended * 5 * 278_528 / PEAK
    assert by_ops > by_bytes                # 242 FLOP/B is just past the ridge
    got = latent_read_roofline.read(records(), spec('latent_read_roofline'))
    assert got == pytest.approx(100.0 * by_ops / spent)
    said = capsys.readouterr().err
    assert 'bound by compute' in said
    assert f'{attended} positions attended' in said


def test_expert_roofline_reads_the_programs_counts_and_the_ticks_scope(capsys):
    hit, seated = 70 + 76, 96 + 104
    by_bytes = hit * 47_185_920 / HBM
    assert by_bytes > seated * 2 * EXPERT / PEAK
    got = expert_roofline.read(records(), spec('expert_roofline'))
    assert got == pytest.approx(100.0 * by_bytes / 0.3)
    assert '2 ticks, 146 experts hit, 200 assignments' in \
        capsys.readouterr().err
    # many assignments on few experts: the products' operations bound it
    busy = records(spans=[mark(101.0, seated=40000, hit=4, largest=10000)])
    got = expert_roofline.read(busy, spec('expert_roofline'))
    assert got == pytest.approx(100.0 * 40000 * 2 * EXPERT / PEAK / 0.3)


def test_expert_imbalance_is_the_largest_over_the_mean_of_the_held():
    got = expert_imbalance.read(records(), spec('expert_imbalance'))
    assert got == pytest.approx((8 * 40 / 96 + 12 * 40 / 104) / 2)


def test_the_experts_share_counts_the_grouped_products_the_scope_misses(capsys):
    """Decode and prefill alike: what runs under ``experts`` and the Mosaic
    calls of the grouped products, which carry no scope path."""
    got = scope_kernel_share.read(records(), spec('scope_share.experts'))
    under, kernels = 0.1 + 1.0, 0.2 + 1.0
    busy = 0.4 + 0.1 + 0.1 + 0.2 + 0.2 + 1.0 + 1.0 + 1.0 + 0.2
    assert got == pytest.approx(100.0 * (under + kernels) / busy)
    said = capsys.readouterr().err
    assert '1.100 s under' in said and '1.200 s in kernels' in said


@pytest.mark.parametrize('reader, metric', [
    (latent_read_roofline, 'latent_read_roofline'),
    (expert_roofline, 'expert_roofline'),
    (expert_imbalance, 'expert_imbalance'),
    (scope_kernel_share, 'scope_share.experts')])
def test_a_program_without_the_scope_or_the_counter_reads_none(reader, metric):
    """The parent of PR 32, and a run that was not traced: nothing to read,
    nothing raised, the metric left out."""
    parent = records(scoped=[('fusion.9', 1.0, 2.0,
                              'jit(step_fn)/GPT2/h_1/attn/dot:')], spans=[])
    assert reader.read(parent, spec(metric)) is None
    untraced = records()
    untraced['traced_window'] = None
    assert reader.read(untraced, spec(metric)) is None
    no_trace = records(spans=[])
    no_trace['program_trace'] = None
    assert reader.read(no_trace, spec(metric)) is None


# ----------------------------------------- the tiny cell through the harness

@pytest.fixture(scope='module')
def added(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp('chipbench-dsv2'))
    record = dsv2.add(root)
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        yield record


def test_nothing_that_was_there_changed_and_the_cell_finds_its_files(added):
    toy.unchanged(added)
    toy.found(added)
    cell = harness.load_cell(dsv2.CELL, added['root'])
    tiny.check_cuts(cell.config, {'reduced': cell.config['reduced']})


def test_the_tiny_serving_run_is_correct_through_the_real_driver(added, capsys):
    result = tiny.run_cell(added['root'], dsv2.CELL, seed=2 ** 31 + 77)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 2
    assert set(result['metrics']) == {'serve_tokens_per_s', 'ttft_p50_ms',
                                      'itl_p95_ms', 'setup_s'}
    said = capsys.readouterr().err
    assert "resolved {'stream_dtype': 'float32', 'decode_impl': 'flax'}" in said
    assert "the program's experts were not the reference's own at 0 of" in said


def test_a_fault_in_the_weights_is_not_correct(added, monkeypatch):
    real = family.make
    monkeypatch.setattr(family, 'make',
                        lambda config, seed: real(config, seed + 1))
    result = tiny.run_cell(added['root'], dsv2.CELL, seed=2 ** 31 + 78)
    assert result['correct'] is False
    assert not (result['compared']['logit_gap_max']['value']
                <= result['compared']['logit_gap_max']['limit'])
