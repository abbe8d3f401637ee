"""The ``nemotron_h`` family in the benchmark: its configuration and cell as
files, its counts against hand-worked numbers, its seeded leaves pinned bit
for bit, its reference reading, its reader's arithmetic on synthetic records
(no chip, no profiler), and its tiny cell through the real harness and
serving driver, sound and with a fault planted in the scan."""

from __future__ import annotations

import hashlib
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, flops, harness, trace_reduce, traffic, weights
from chipbench.families import nemotron_h as family
from chipbench.readers import program_trace, scope_share, ssm_roofline
from chipbench.reference import nemotron_h as reference
from tests.chipbench_tests import nemotron, tiny, toy

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / 'BENCHMARK.json').read_text())
CONFIG = nemotron.SHIPPED
CELL = 'serve-nemotron3-closed96'
PATTERN = 'MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME'
# the source's config.json, as the model-configs catalog holds it
SOURCE = {
    'attention_bias': False, 'chunk_size': 128, 'conv_kernel': 4, 'expand': 2,
    'head_dim': 128, 'hidden_size': 2688, 'hybrid_override_pattern': PATTERN,
    'intermediate_size': 1856, 'layer_norm_epsilon': 1e-05,
    'mamba_head_dim': 64, 'mamba_hidden_act': 'silu', 'mamba_num_heads': 64,
    'mamba_proj_bias': False, 'max_position_embeddings': 262144,
    'mlp_bias': False, 'mlp_hidden_act': 'relu2', 'model_type': 'nemotron_h',
    'moe_intermediate_size': 1856,
    'moe_shared_expert_intermediate_size': 3712, 'n_group': 1, 'n_groups': 8,
    'n_routed_experts': 128, 'n_shared_experts': 1, 'norm_eps': 1e-05,
    'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_experts_per_tok': 6, 'num_hidden_layers': 52,
    'num_key_value_heads': 2, 'num_logits_to_keep': 1,
    'partial_rotary_factor': 1, 'rescale_prenorm_residual': True,
    'residual_in_fp32': False, 'rope_theta': 10000,
    'routed_scaling_factor': 2.5, 'sliding_window': None,
    'ssm_state_size': 128, 'tie_word_embeddings': False,
    'time_step_floor': 0.0001, 'time_step_max': 0.1, 'time_step_min': 0.001,
    'topk_group': 1, 'use_bias': False, 'use_conv_bias': True,
    'use_mamba_kernels': True, 'vocab_size': 131072}
HELD = {'num_hidden_layers': 26, 'hybrid_override_pattern': PATTERN[:26],
        'n_routed_experts': 32, 'vocab_size': 32768}
SSM = 2688 * 10304 + 4096 * 2688                           # 38 707 200
ATTENTION = 2688 * 2 * (4096 + 256)                        # 23 396 352
EXPERT = 2 * 2688 * 1856                                   # 9 977 856
STATE = 64 * 64 * 128                                      # 524 288 values


# ------------------------------------------------------------- the files

def test_the_configuration_holds_the_published_keys_and_states_its_cut():
    entry = next(c for c in BENCH['configs']
                 if c['name'] == 'nemotron-3-nano-30b-a3b')
    names = [c['name'] for c in BENCH['configs']]
    assert names.index(entry['name']) == 3 and names[:3] == [
        'gpt2-medium', 'gpt2-large', 'deepseek-v2'], 'appended, not inserted'
    assert CONFIG['family'] == 'nemotron_h'
    assert CONFIG['reduced'] == list(HELD) == entry['reduced']
    for key, value in SOURCE.items():
        if key in HELD:
            assert CONFIG[key] == HELD[key]
            assert CONFIG['published'][key] == value
        else:
            assert CONFIG[key] == value, key
    tiny.check_cuts(CONFIG, entry)
    assert 'four chips share each layer' in CONFIG['deployment']
    assert 'two pipeline stages of 26 layers' in CONFIG['deployment']
    cut = CONFIG['hybrid_override_pattern']
    assert (cut.count('M'), cut.count('E'), cut.count('*')) == (12, 11, 3)
    assert not set(CONFIG['reduced']) & {      # never a width
        'hidden_size', 'moe_intermediate_size', 'head_dim', 'mamba_head_dim',
        'moe_shared_expert_intermediate_size', 'mamba_num_heads',
        'ssm_state_size', 'n_groups', 'num_attention_heads',
        'num_key_value_heads', 'num_experts_per_tok', 'conv_kernel'}
    as_run = CONFIG['as_run']
    assert {as_run[key] for key in ('param_dtype', 'compute_dtype',
                                    'stream_dtype', 'kv_cache_dtype')} == {
        'bfloat16'}
    assert (as_run['decode_impl'], as_run['max_seq'], as_run['levers'],
            as_run['state_dtype']) == ('flax', 1536,
                                       {'stream_dtype': 'bfloat16'}, 'float32')
    assert CONFIG['reference'] == {'sample_requests': 6,
                                   'control': {'bits': 8}}
    assert set(CONFIG['assumed']) >= {
        'attention_position', 'state_dtype', 'weights', 'max_seq',
        'first_expert', 'given_routing', 'mlp_hidden_act', 'expert_padding'}
    # the published count, from the published shapes (norms, the
    # convolution, the router's correction and the three vectors a head)
    mamba = SSM + 6144 * 4 + 6144 + 3 * 64 + 4096 + 2688
    experts = (2688 * 128 + 128 + 128 * EXPERT + 2 * 2688 * 3712 + 2688)
    whole = (23 * mamba + 6 * (ATTENTION + 2688) + 23 * experts
             + 2 * 131072 * 2688 + 2688)
    assert CONFIG['parameters'] == whole == 31_577_940_288
    held = (12 * mamba + 3 * (ATTENTION + 2688)
            + 11 * (experts - 96 * EXPERT) + 2 * 32768 * 2688 + 2688)
    assert CONFIG['held_parameters'] == held == 4_446_833_152
    assert f'({held})' in CONFIG['deployment']


def test_the_cell_is_the_issues_traffic_and_joins_its_metrics():
    cell = harness.load_cell(CELL)
    assert [w['name'] for w in BENCH['workloads']].index(CELL) == 3
    assert cell.chips == 1
    mix = cell.traffic
    assert (mix['driver'], mix['loop'], mix['clients'], mix['rows'],
            mix['block_size'], mix['pool']) == ('serve', 'closed', 96, 96,
                                                16, 96)
    assert mix['prompt'] == {'median': 320, 'sigma': 0.7, 'low': 32,
                             'high': 1024}
    assert mix['max_new'] == {'median': 192, 'sigma': 0.6, 'low': 32,
                              'high': 512}
    assert (mix['greedy'], mix['share_prefix'], mix['trace_seconds'],
            mix['drain_seconds']) == (True, False, 8, 60)
    sizes = traffic.request_sizes(7, mix)
    assert len(sizes) == 96 and sorted(sizes) == sorted(
        traffic.request_sizes(2 ** 31 + 5, mix))
    assert all(32 <= p <= 1024 and 32 <= n <= 512
               and p + n <= family.positions(CONFIG) for p, n in sizes)
    # every warm bucket is one the pool's prompts reach, and none is missed
    # (the shortest prompt of the pool is 53 tokens: no 32-bucket)
    buckets = {1 << (p - 1).bit_length() for p, _ in sizes}
    assert buckets == set(mix['warm_prompts']) == {64, 128, 256, 512, 1024}
    reported = {m['name'] for m in cell.end_to_end + cell.per_layer}
    assert reported == {
        'setup_s', 'serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms',
        'row_occupancy', 'queue_wait_ms', 'admit_ms', 'kv_blocks_live_share',
        'prefill_share', 'decode_tick_ms', 'step_mfu.serve',
        'device_idle_share.serve', 'host_gap_share.serve',
        'tick_gap_ms.dispatch', 'tick_gap_ms.read', 'tick_gap_ms.rows',
        'tick_gap_ms.narrate', 'tick_gap_ms.seat', 'scope_share.kv_read',
        'scope_share.select', 'scope_share.experts', 'scope_share.router',
        'expert_roofline', 'expert_imbalance', *nemotron.OWN_METRICS}
    # its own metrics follow everything PR 33 left, and list it first (a
    # later cell that joins them is appended behind it)
    names = [m['name'] for m in BENCH['per_layer']]
    assert names.index('expert_imbalance') + 1 == names.index(
        nemotron.OWN_METRICS[0])
    for name in nemotron.OWN_METRICS:
        assert BENCH['per_layer'][names.index(name)]['workloads'][0] == CELL
    limit = cell.limits['logit_gap_max']
    assert limit['lower'] < limit['limit'] < limit['upper']


def test_the_family_module_serves_and_refuses_what_it_says():
    module = families.of(CONFIG)
    assert module is family
    served = family.serve_module(CONFIG)
    assert (served.layers, served.pattern, served.experts, served.held,
            served.vocab_size, served.max_seq, served.dtype) == (
        26, PATTERN[:26], 128, (0, 32), 32768, 1536, 'bfloat16')
    assert (served.dim, served.ssm_heads, served.ssm_head_dim,
            served.ssm_groups, served.ssm_state, served.expert_width,
            served.shared_width, served.kv_heads) == (2688, 64, 64, 8, 128,
                                                     1856, 3712, 2)
    assert family.vocab_size(CONFIG) == 32768
    assert family.positions(CONFIG) == 1536
    for name in ('train_module', 'reference_training', 'train_ops_per_token',
                 'flash_layers', 'flash_ops_and_bytes',
                 'decode_chain_ops_and_bytes'):
        with pytest.raises(NotImplementedError):
            getattr(family, name)(CONFIG)
    # the tree it would hand over: the held count + what the routed
    # matrices are padded by (11 layers x 32 experts x 2 matrices)
    held = sum(int(np.prod(shape)) for _, shape, _ in
               list(family.top_leaves(CONFIG).values())
               + [leaf for index in range(26)
                  for leaf in family.layer_leaves(CONFIG, index).values()])
    assert held == CONFIG['held_parameters'] + 11 * 32 * 2 * (
        2816 * 2048 - 2688 * 1856)


# ------------------------------------------------------------ the counts

def test_counts_match_hand_worked_numbers():
    assert family.ssm_params(CONFIG) == SSM == 38_707_200
    assert family.attention_params(CONFIG) == ATTENTION
    assert family.expert_params(CONFIG) == EXPERT
    expert_layer = 2688 * 128 + 2 * 2688 * 3712 + 1.5 * EXPERT
    matmul = 12 * SSM + 3 * ATTENTION + 11 * expert_layer + 32768 * 2688
    assert flops.matmul_params(CONFIG) == matmul == 1_010_688_000
    # a causal pass: 2 ops a parameter, 4·H·P·N a state-space layer, half
    # the square at 32 heads x (128 + 128) in each of 3 attention layers
    assert flops.prefill_ops(CONFIG, 1024) == 1024 * (
        2 * matmul + 12 * 4 * STATE + 512 * 3 * 2 * 32 * 256)
    assert flops.decode_ops(CONFIG, 700) == (
        2 * matmul + 12 * 4 * STATE + 700 * 3 * 2 * 32 * 256)
    # keys and values of the attention layers alone: 3 x 2 x 256 x 2 B
    assert flops.kv_bytes_per_position(CONFIG) == 3072
    assert family.state_bytes(CONFIG) == 4 * STATE + 3 * 6144 * 2 == 2_134_016
    ops, moved = family.state_update_ops_and_bytes(CONFIG, 1000)
    assert (ops, moved) == (1000 * 12 * 4.0 * STATE,
                            1000 * 12 * 2.0 * 2_134_016)
    assert ops / moved < 1                   # far under the chip's ridge
    ops, moved = family.scan_ops_and_bytes(CONFIG, 1000)
    assert (ops, moved) == (1000 * 12 * 4.0 * STATE,
                            1000 * 12 * (6144 * 2 + 64 * 4 + 4096 * 4.0))
    ops, moved = family.expert_ops_and_bytes(CONFIG, hit=340, seated=6000)
    assert (ops, moved) == (6000 * 2.0 * EXPERT, 340 * 2.0 * EXPERT)
    assert 2 * EXPERT == 19_955_712                           # 20.0 MB a hit


# ------------------------------------------------------- the seeded leaves

PINNED = json.loads((pathlib.Path(__file__).parent
                     / 'nemotron_h_weight_digests.json').read_text())


def leaf_digests(tree: dict) -> dict:
    out = {}
    for name, leaf in weights.flatten(tree).items():
        leaf = np.asarray(leaf)
        out[name] = hashlib.sha256(
            str(leaf.dtype).encode() + str(leaf.shape).encode()
            + leaf.tobytes()).hexdigest()[:16]
    return out


@pytest.mark.parametrize('seed', sorted(PINNED))
def test_every_seeded_leaf_is_bit_for_bit_what_pr_34_made(seed):
    config = nemotron.tiny_config()
    made = family.make(config, int(seed))
    assert leaf_digests(made) == PINNED[seed]
    # the reference is handed the same draws under its own names (an
    # expert's matrices without what they are padded by)
    leaves_of = family.reference_leaves(config, int(seed))
    flat = weights.flatten(made)
    for group in ('top', 0, 1, 2, 3, 4):
        table = (family.top_leaves(config) if group == 'top'
                 else family.layer_leaves(config, group))
        for name, leaf in leaves_of(group).items():
            path = table[name][0] if group == 'top' \
                else f'layer_{group}/{table[name][0]}'
            corner = tuple(slice(0, size) for size in leaf.shape)
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat[path])[corner])


def test_the_state_space_leaves_are_drawn_where_the_file_says():
    config = dict(nemotron.tiny_config(), time_step_min=0.001,
                  time_step_max=0.1, mamba_num_heads=64)
    leaves = family.reference_leaves(config, 3)(0)
    steps = np.log1p(np.exp(np.asarray(leaves['dt_bias'])))
    assert 0.001 <= steps.min() and steps.max() <= 0.1
    decay = -np.exp(np.asarray(leaves['A_log']))
    assert -16 <= decay.min() and decay.max() <= -1
    assert np.abs(np.asarray(leaves['conv_weight'])).max() <= 0.5
    assert float(jnp.std(leaves['conv_weight'])) == pytest.approx(
        0.5 / 3 ** 0.5, rel=0.1)
    assert float(jnp.mean(leaves['norm_scale'])) == pytest.approx(1.0, abs=0.02)
    assert float(jnp.std(leaves['in_proj'])) == pytest.approx(0.02, rel=0.05)
    router = family.reference_leaves(config, 3)(1)
    assert float(jnp.std(router['correction'])) > 0.005     # not zeros
    assert router['up'].shape == (4, 64, 48)               # of [4, 64, 64]
    bf16 = dict(config, as_run=dict(config['as_run'], param_dtype='bfloat16'))
    made = weights.flatten(family.make(bf16, 3))
    assert {leaf.dtype for leaf in made.values()} == {jnp.dtype('bfloat16')}
    assert made['layer_1/mixer/up'].shape == (4, 64, 64)
    assert made['layer_1/mixer/down'].shape == (4, 64, 64)


# -------------------------------------------------- the reference's reading

def test_served_gap_reads_zero_on_the_references_own_tokens_and_sees_a_fault():
    config = nemotron.tiny_config()
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
    leaves = family.reference_leaves(nemotron.tiny_config(), 2)
    for group in (0, 1, 2):
        wide = leaves(group)
        low = reference.narrow(wide, 4)
        for name, leaf in wide.items():
            same = bool(jnp.all(low[name] == leaf))
            assert same == (name not in reference.MATRICES), name


# -------------------------------------------------- the readers' arithmetic

HBM, PEAK = 819e9, 197e12                   # peaks.json, TPU v5 lite
STEP = 'jit(step_fn)/NemotronH/layer_0/mixer/'
RUN = 'jit(run)/NemotronH/layer_0/mixer/'
SCOPED = [
    # (short name, start, end, scope path) on the trace's clock
    ('fusion.1', 1.0, 1.4, STEP + 'ssm_update/reduce_sum:'),
    ('fusion.2', 1.4, 1.5, STEP + 'ssm_proj/dot_general:'),
    ('fusion.3', 1.5, 1.6, STEP + 'ssm_conv/add:'),
    ('fusion.4', 2.0, 2.3, 'jit(step_fn)/NemotronH/layer_1/mixer/experts/'
                           'take:'),
    # the prefill programs', under the same scope names
    ('fusion.5', 3.0, 3.5, RUN + 'ssm_scan/dot_general:'),
    ('fusion.6', 3.5, 4.0, RUN + 'ssm_scan/while/body/mul:'),
    ('fusion.7', 4.0, 4.2, RUN + 'ssm_proj/dot_general:'),
    ('fusion.8', 9.8, 10.6, STEP + 'ssm_update/reduce_sum:')]  # cut at 10 s


def records(scoped=SCOPED) -> dict:
    """A 10 s traced window: trace clock 0-10, host clock 100-110."""
    trace = trace_reduce.Trace(
        ops={0: [event[:3] for event in scoped]},
        modules={0: [('jit_step_fn(1)', 1.0, 2.6), ('jit_run(2)', 3.0, 4.5),
                     ('jit_step_fn(1)', 9.5, 10.8)]},
        host=[('chipbench.window', 0.0, 10.0)])
    return {
        'trace': trace, 'traced_window': (100.0, 110.0), 'config': CONFIG,
        'device_kind': 'TPU v5 lite',
        'program_trace': program_trace.ProgramTrace([], list(scoped)),
        # first tokens (prefills) at 101, 90 and 108; decoded tokens after
        'requests': [{'prompt': 300, 'times': [101.0, 102.0, 103.0, 111.0]},
                     {'prompt': 900, 'times': [90.0, 95.0, 100.5]},
                     {'prompt': 77, 'times': [108.0]},
                     {'prompt': 700, 'times': []}],
        'spans': []}


def spec(name: str) -> dict:
    return json.loads((ROOT / 'chipbench' / 'metrics'
                       / f'{name}.json').read_text())


def test_state_update_roofline_counts_decoded_tokens_over_the_ticks_scope(
        capsys):
    decoded = 2 + 1                          # 102, 103 and 100.5
    spent = 0.4 + 0.2                        # the last is cut at 10 s
    by_bytes = decoded * 12 * 2 * 2_134_016 / HBM
    assert by_bytes > decoded * 12 * 4 * STATE / PEAK
    got = ssm_roofline.read(records(), spec('state_update_roofline'))
    assert got == pytest.approx(100.0 * by_bytes / spent)
    said = capsys.readouterr().err
    assert 'bound by memory' in said and '3 tokens decoded' in said


def test_scan_roofline_counts_true_prompt_lengths_over_the_prefills_scope(
        capsys):
    prefilled = 300 + 77                     # first tokens at 101 and 108
    by_bytes = prefilled * 12 * (6144 * 2 + 256 + 4096 * 4) / HBM
    got = ssm_roofline.read(records(), spec('scan_roofline'))
    assert got == pytest.approx(100.0 * by_bytes / 1.0)
    assert '377 tokens prefilled' in capsys.readouterr().err


def test_the_state_space_share_is_its_four_scopes_in_every_program():
    got = scope_share.read(records(), spec('scope_share.ssm'))
    under = 0.4 + 0.1 + 0.1 + 0.5 + 0.5 + 0.2 + 0.2
    assert got == pytest.approx(100.0 * under / (under + 0.3))


@pytest.mark.parametrize('metric', ['state_update_roofline', 'scan_roofline'])
def test_a_program_without_the_scope_reads_none(metric):
    """The parent of PR 34, and a run that was not traced: nothing to read,
    nothing raised, the metric left out."""
    parent = records(scoped=[('fusion.9', 1.0, 2.0,
                              'jit(step_fn)/GPT2/h_1/attn/dot:')])
    assert ssm_roofline.read(parent, spec(metric)) is None
    untraced = records()
    untraced['traced_window'] = None
    assert ssm_roofline.read(untraced, spec(metric)) is None
    no_trace = records()
    no_trace['program_trace'] = None
    assert ssm_roofline.read(no_trace, spec(metric)) is None
    idle = records()
    idle['requests'] = []
    assert ssm_roofline.read(idle, spec(metric)) is None


# ----------------------------------------- the tiny cell through the harness

@pytest.fixture(scope='module')
def added(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp('chipbench-nemotron'))
    record = nemotron.add(root)
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        yield record


def test_nothing_that_was_there_changed_and_the_cell_finds_its_files(added):
    toy.unchanged(added)
    toy.found(added)
    cell = harness.load_cell(nemotron.CELL, added['root'])
    tiny.check_cuts(cell.config, {'reduced': cell.config['reduced']})


def test_the_tiny_serving_run_is_correct_through_the_real_driver(added, capsys):
    result = tiny.run_cell(added['root'], nemotron.CELL, seed=2 ** 31 + 77)
    assert result['correct'] is True and result['failed'] == 0
    assert result['attempted'] > 2
    assert set(result['metrics']) == {'serve_tokens_per_s', 'ttft_p50_ms',
                                      'itl_p95_ms', 'setup_s'}
    said = capsys.readouterr().err
    assert "resolved {'stream_dtype': 'float32', 'decode_impl': 'flax'}" in said
    assert "the program's experts were not the reference's own at 0 of" in said


def test_a_fault_in_the_scan_is_not_correct(added, monkeypatch):
    """The planted fault: the prefill's scan runs on ``A`` with its sign
    lost, so the state it hands the decode steps grew where it should have
    decayed. The served tokens are then not the reference's."""
    from tpusystem.ops import ssm
    real = ssm.ssm_scan
    monkeypatch.setattr(
        ssm, 'ssm_scan', lambda x, dt, A, B, C, **kw: real(x, dt, -A, B, C,
                                                           **kw))
    result = tiny.run_cell(added['root'], nemotron.CELL, seed=2 ** 31 + 78)
    assert result['correct'] is False
    assert not (result['compared']['logit_gap_max']['value']
                <= result['compared']['logit_gap_max']['limit'])
