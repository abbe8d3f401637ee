"""The DeepSeek-V2 family at a tiny size on the CPU: the model against the
benchmark's plain reference (``chipbench/reference/deepseek_v2.py``, which
imports nothing of ``tpusystem/``), the two attention paths against each
other, prefill then decode through the latent paged pool against the full
forward pass, the share of an expert-parallel deployment, group-limited
routing against a brute-force enumeration, and the serving engine's
admission, eviction and prefix sharing over a pool whose row is one latent.

Tolerances (float32 throughout, so nothing here is rounding of a narrow
type): logits against the reference ``2e-5`` — the two sum the same products
in another order (grouped products over sorted rows against a masked loop,
absorbed against expanded attention), a few float32 ulps of logits of
magnitude 1; cached against uncached ``1e-5`` for the same reason. Tokens
are compared exactly where the arithmetic is window-invariant (a row's
masked positions contribute exact zeros).
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import deepseek_v2 as family
from chipbench.reference import deepseek_v2 as reference
from tests.chipbench_tests.dsv2 import SHIPPED, tiny_config
from tpusystem.models.deepseek import (yarn_correction_range,
                                       yarn_frequencies, yarn_softmax_scale)
from tpusystem.observe.trace import Tracer
from tpusystem.ops.attention import (expanded_latent_attention,
                                     latent_attention)
from tpusystem.ops.moe import GatedExperts, group_limited_top_k, seat_held
from tpusystem.serve import Engine, InferenceService, Request, Scheduler
from tpusystem.serve.engine import engine_unsupported_reason
from tpusystem.train.decode_fused import fused_paged_reason
from tpusystem.train.generate import _decoder, _stream_params, generate

@pytest.fixture(scope='module')
def served():
    config = tiny_config()
    return config, family.serve_module(config), family.make(config, 11)


def tokens_of(seed: int, *shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, shape).astype(np.int32)


# --------------------------------------------------- the published constants

def test_yarn_constants_are_the_published_ones():
    rope = SHIPPED['rope_scaling']
    assert yarn_correction_range(64, 10000.0, 4096, 32, 1) == (10, 23)
    assert yarn_softmax_scale(192, 40.0, 0.707) == pytest.approx(0.11472,
                                                                 abs=5e-6)
    model = family.reference_model(SHIPPED)
    assert reference.yarn_range(model) == (10, 23)
    assert reference.softmax_scale(model) == pytest.approx(0.11472, abs=5e-6)
    ours = np.asarray(yarn_frequencies(
        64, 10000.0, 40.0, rope['original_max_position_embeddings'],
        rope['beta_fast'], rope['beta_slow']))
    np.testing.assert_allclose(ours, np.asarray(reference.yarn_inv_freq(model)),
                               rtol=1e-6)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # untouched below the ramp, divided by the factor above it
    np.testing.assert_allclose(ours[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(ours[23:], base[23:] / 40, rtol=1e-6)
    assert np.all(np.diff(ours) < 0)


# ------------------------------------------------- model against reference

def test_logits_match_the_plain_reference(served):
    config, module, params = served
    tokens = tokens_of(0, 2, 128)
    ours = module.apply({'params': params}, jnp.asarray(tokens))
    want = reference.logits(
        [jnp.asarray(row) for row in tokens],
        family.reference_leaves(config, 11), config['num_hidden_layers'],
        family.reference_model(config))
    for row in range(2):
        np.testing.assert_allclose(np.asarray(ours[row]),
                                   np.asarray(want[row]), atol=2e-5)
    assert float(jnp.max(jnp.abs(want[0]))) > 0.1


def test_absorbed_attention_equals_expanded():
    """Decode reads the latent rows themselves (``kv_b`` folded into the
    query and the output); prefill expands keys and values per head. One
    mathematics: a cache seeded by the first 9 positions, then 7 more
    through the absorbed path, against the expanded pass over all 16."""
    from flax import linen as nn
    heads, nope, rope, rank, v_dim, seq = 4, 16, 8, 16, 16, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q_content = jax.random.normal(keys[0], (2, seq, heads, nope))
    q_rope = jax.random.normal(keys[1], (2, seq, heads, rope))
    latent = jax.random.normal(keys[2], (2, seq, rank + rope))
    w_key = jax.random.normal(keys[3], (rank, heads, nope)) / 4
    w_value = jax.random.normal(keys[4], (rank, heads, v_dim)) / 4

    class Mixer(nn.Module):
        @nn.compact
        def __call__(self, q_c, q_r, rows):
            return latent_attention(self, q_c, q_r, rows, w_key, w_value,
                                    scale=0.2, max_seq=32, per_row=True)

    want = expanded_latent_attention(
        jnp.concatenate([q_content, q_rope], axis=-1), latent[..., rank:],
        latent, w_key, w_value, 0.2)
    mixer = Mixer()
    first, state = mixer.apply({}, q_content[:, :9], q_rope[:, :9],
                               latent[:, :9], mutable=['cache'])
    rest, state = mixer.apply(state, q_content[:, 9:], q_rope[:, 9:],
                              latent[:, 9:], mutable=['cache'])
    assert state['cache']['key'].shape == (2, 32, 128)   # on whole lanes
    assert 'value' not in state['cache']
    np.testing.assert_allclose(np.asarray(first), np.asarray(want[:, :9]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(rest), np.asarray(want[:, 9:]),
                               atol=1e-5)


def test_long_prefill_goes_through_in_query_blocks(monkeypatch):
    """Past the score budget the expanded path attends block by block over
    the causal part alone; the result is the one-piece result."""
    from tpusystem.ops import attention
    heads, nope, rope, rank, seq = 2, 8, 4, 8, 512
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    query = jax.random.normal(keys[0], (1, seq, heads, nope + rope))
    latent = jax.random.normal(keys[1], (1, seq, rank + rope))
    w_key = jax.random.normal(keys[2], (rank, heads, nope))
    w_value = jax.random.normal(keys[3], (rank, heads, 8))
    whole = expanded_latent_attention(query, latent[..., rank:], latent,
                                      w_key, w_value, 0.3)
    monkeypatch.setattr(attention, '_EXPANDED_SCORE_BYTES',
                        heads * 128 * seq * 4)
    blocks = expanded_latent_attention(query, latent[..., rank:], latent,
                                       w_key, w_value, 0.3)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=1e-5)


# ------------------------------------------------------- the expert layer

def brute_force_choice(scores, k, groups, keep_groups):
    """Every way to keep ``keep_groups`` groups, scored by the groups'
    largest members; then the ``k`` largest of what the best way leaves."""
    experts = scores.shape[0]
    per = experts // groups
    best = max(itertools.combinations(range(groups), keep_groups),
               key=lambda kept: (sum(scores[g * per:(g + 1) * per].max()
                                     for g in kept),
                                 [-g for g in kept]))
    allowed = [e for e in range(experts) if e // per in best]
    return sorted(sorted(allowed, key=lambda e: (-scores[e], e))[:k])


def test_group_limited_routing_matches_a_brute_force_enumeration():
    scores = jax.nn.softmax(
        3 * jax.random.normal(jax.random.PRNGKey(7), (200, 16)), axis=-1)
    ids, weights = group_limited_top_k(scores, k=3, groups=4, keep_groups=2)
    scores = np.asarray(scores)
    for token in range(200):
        want = brute_force_choice(scores[token], 3, 4, 2)
        assert sorted(np.asarray(ids[token]).tolist()) == want
        np.testing.assert_array_equal(
            np.sort(np.asarray(weights[token])),
            np.sort(scores[token][want]))
    # never more than two groups, and not renormalised
    assert all(len({e // 4 for e in row}) <= 2 for row in np.asarray(ids))
    assert float(jnp.max(jnp.sum(weights, axis=-1))) < 1.0
    # the reference's own routing says the same
    model = family.reference_model(tiny_config(), held=None)
    hidden = jax.random.normal(jax.random.PRNGKey(8), (50, 64))
    router = jax.random.normal(jax.random.PRNGKey(9), (64, 16))
    chosen, ref_scores = reference.route(hidden, router, model, 'float32')
    ids, _ = group_limited_top_k(ref_scores, 3, 4, 2)
    for token in range(50):
        assert sorted(np.flatnonzero(np.asarray(chosen[token])).tolist()) \
            == sorted(np.asarray(ids[token]).tolist())


def expert_layer_params(key, held):
    layer = GatedExperts(experts=16, k=3, width=48, groups=4, keep_groups=2,
                         scale=16.0, shared_width=48, held=held,
                         dtype=jnp.float32)
    return layer, layer.init(key, jnp.zeros((1, 4, 64)))['params']


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide: each of four chips holds
    four of the sixteen experts, routes over all sixteen and sums its own;
    the partial sums, with the shared expert (which every chip computes
    alike) counted once, are the uncut reference's layer."""
    whole, params = expert_layer_params(jax.random.PRNGKey(13), None)
    hidden = jax.random.normal(jax.random.PRNGKey(14), (3, 20, 64))
    shared_names = ('shared_gate', 'shared_up', 'shared_down')
    leaves = {name: params[name] for name in ('router', 'gate', 'up', 'down')}
    leaves.update({name: params[name]['kernel'] for name in shared_names})
    model = family.reference_model(tiny_config(), held=None)
    want, _ = reference.expert_layer(hidden.reshape(-1, 64), leaves, model,
                                     'float32')
    shared = reference.gated_mlp(
        hidden.reshape(-1, 64),
        *(leaves[name] for name in shared_names), 'float32')
    total, seated = jnp.zeros_like(want), 0
    for start in (0, 4, 8, 12):
        share, _ = expert_layer_params(jax.random.PRNGKey(0), (start, 4))
        mine = dict(params, **{name: params[name][start:start + 4]
                               for name in ('gate', 'up', 'down')})
        part, counted = share.apply({'params': mine}, hidden,
                                    mutable=['expert_load'])
        total = total + part.reshape(-1, 64) - shared
        seated += int(counted['expert_load']['seated'])
        # the reference, given the same share, gives the same partial sum
        held = dict(leaves, **{name: leaves[name][start:start + 4]
                               for name in ('gate', 'up', 'down')})
        ref_part, _ = reference.expert_layer(
            hidden.reshape(-1, 64), held,
            family.reference_model(tiny_config(), held=(start, 4)),
            'float32')
        np.testing.assert_allclose(np.asarray(part.reshape(-1, 64)),
                                   np.asarray(ref_part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5)
    assert seated == 3 * 20 * 3          # every assignment seated somewhere
    uncut = whole.apply({'params': params}, hidden)
    np.testing.assert_allclose(np.asarray(uncut.reshape(-1, 64)),
                               np.asarray(want), atol=5e-5)


def test_no_token_is_dropped_and_rows_do_not_see_each_other_when_skewed():
    """A router that sends every token to the same experts: all 3 x tokens
    assignments are seated (one expert takes a third of them), and a row's
    output is what it is alone (to float32 round-off: the CPU's matrix
    products sum in an order that follows the batch)."""
    layer, params = expert_layer_params(jax.random.PRNGKey(17), (0, 8))
    skew = jnp.zeros((64, 16)).at[:, 1].set(1.0).at[:, 2].set(0.9) \
        .at[:, 5].set(0.8)
    params = dict(params, router=skew)
    hidden = jnp.abs(jax.random.normal(jax.random.PRNGKey(18), (6, 9, 64)))
    together, counted = layer.apply({'params': params}, hidden,
                                    mutable=['expert_load'])
    assert set(counted['expert_load']) == set(GatedExperts.LOAD)
    assert {name: int(count) for name, count
            in counted['expert_load'].items()} == {
                'seated': 3 * 54, 'hit': 3, 'largest': 54}
    for row in range(6):
        alone = layer.apply({'params': params}, hidden[row:row + 1])
        np.testing.assert_allclose(np.asarray(alone[0]),
                                   np.asarray(together[row]), atol=3e-6)
    # the same layer holding experts 8-15 seats none of them
    elsewhere, params_e = expert_layer_params(jax.random.PRNGKey(17), (8, 8))
    _, counted = elsewhere.apply({'params': dict(params_e, router=skew)},
                                 hidden, mutable=['expert_load'])
    assert {int(count) for count in counted['expert_load'].values()} == {0}


def test_seating_sorts_by_expert_and_bounds_nothing():
    ids = jnp.asarray([[5, 0, 9], [4, 5, 15], [7, 6, 5]])
    order, held, sizes = seat_held(ids, start=4, count=4)
    assert np.asarray(sizes).tolist() == [1, 3, 1, 1]      # experts 4..7
    flat = np.asarray(ids).reshape(-1)[np.asarray(order)]
    assert flat[:6].tolist() == [4, 5, 5, 5, 6, 7]
    assert np.asarray(held).tolist() == [True] * 6 + [False] * 3
    with pytest.raises(ValueError, match='outside'):
        GatedExperts(experts=16, k=3, width=8, held=(12, 8)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 16)))


# --------------------------------------------- decode through the latent pool

def test_prefill_then_decode_matches_the_full_forward_pass(served):
    """Contiguous latent cache (``generate``): every decoded token is the
    argmax of the uncached pass over what came before, and the cached
    logits equal the uncached ones."""
    _, module, params = served
    prompt = tokens_of(1, 2, 21)
    out = np.asarray(generate(module, params, jnp.asarray(prompt), steps=12))
    full = module.apply({'params': params}, jnp.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, 20:-1], axis=-1)), out[:, 21:])
    decoder = _decoder(module, per_row=True)
    _, state = decoder.apply({'params': params}, jnp.asarray(out[:, :21]),
                             mutable=['cache'])
    stepped, _ = decoder.apply({'params': params, 'cache': state['cache']},
                               jnp.asarray(out[:, 21:25]), mutable=['cache'])
    np.testing.assert_allclose(np.asarray(stepped),
                               np.asarray(full[:, 21:25]), atol=1e-5)


def drain(engine) -> dict:
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    return tokens


def standalone(module, params, prompt, steps):
    out = generate(module, params, jnp.asarray([prompt]), steps=steps)
    return np.asarray(out)[0, len(prompt):].tolist()


def test_the_service_serves_it_through_one_step_over_a_latent_pool(served):
    config, module, params = served
    assert engine_unsupported_reason(module) is None
    tracer = Tracer('serve')
    service = InferenceService(module, params, rows=4, block_size=16,
                               tracer=tracer)
    engine = service.engine
    assert engine.decode_impl == 'flax'
    reason = fused_paged_reason(engine._decoder)
    assert 'latent' in reason and 'DeepSeekV2' in reason
    # one latent row of 16 + 8 values a position, stored on whole lanes
    for layer in range(config['num_hidden_layers']):
        cache = engine._cache[f'layer_{layer}']['attn']
        assert set(cache) == {'key', 'table', 'index'}
        assert cache['key'].shape == (engine.pool.blocks * 16, 128)
    prompts = [tokens_of(20 + n, n).tolist() for n in (5, 17, 33, 40, 9, 21)]
    for index, prompt in enumerate(prompts):
        service.service.handle('submit', Request(f'r{index}', prompt, 8))
    service.run_until_idle()
    for index, prompt in enumerate(prompts):
        assert service.scheduler.results[f'r{index}'].tokens == standalone(
            module, params, prompt, 8), f'r{index} diverged'
    assert engine.trace_count == 1
    traced = tracer.compiled('trace')
    assert (traced['seat'], traced['clear']) == (1, 1)
    assert engine.pool.live_blocks == 0
    engine.pool.audit()
    # the counters came with the tokens: two expert layers, 3 a token
    load = engine.expert_load
    assert load['ticks'] > 0 and load['seated'] <= load['ticks'] * 4 * 3 * 2
    assert 0 < load['hit'] <= load['ticks'] * 8 * 2
    assert load['hit'] <= load['seated'] and load['largest'] <= load['seated']
    assert set(engine.last_expert_load) == {'seated', 'hit', 'largest'}


def test_a_module_with_no_expert_layer_counts_nothing():
    from tpusystem.models import gpt2_tiny
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))['params']
    engine = Engine(module, params, rows=2, block_size=8)
    engine.admit([1, 2, 3], max_new=3)
    drain(engine)
    assert engine.last_expert_load is None
    assert engine.expert_load == {'ticks': 0}


def test_a_routing_sink_gets_the_experts_of_every_position(served):
    """Prompt positions from the prefill program, each decoded token's
    input from the tick's read: what a full forward pass of the module
    over the same tokens chooses, so a caller can replay the experts."""
    _, module, params = served
    got = {}
    engine = Engine(module, params, rows=2, block_size=16,
                    routing_sink=lambda *record: got.update(
                        {record[0]: record[1:]}))
    prompts = {f'r{n}': tokens_of(40 + n, n).tolist() for n in (7, 19, 33)}
    for tag, prompt in prompts.items():
        while not engine.free_rows:
            engine.step()
        engine.admit(prompt, max_new=6, tag=tag)
    drain(engine)
    assert set(got) == set(prompts)
    assert engine.trace_count == 1 and engine.expert_load['ticks'] > 0
    for tag, (prompt, tokens, routing) in got.items():
        assert prompt.tolist() == prompts[tag] and len(tokens) == 6
        # every position that went through the 2 expert layers, 3 a token
        assert routing.shape == (len(prompt) + 5, 2, 3)
        assert routing.dtype == np.uint8
        sequence = jnp.asarray([list(prompt) + tokens[:-1]])
        _, sown = module.apply({'params': params}, sequence,
                               mutable=['routing'])
        for layer in (1, 2):
            np.testing.assert_array_equal(
                np.sort(routing[:, layer - 1], axis=-1),
                np.sort(np.asarray(
                    sown['routing'][f'layer_{layer}']['moe']['chosen']),
                    axis=-1))


def test_a_routing_sink_is_refused_where_it_could_not_cover_a_request(served):
    _, module, params = served
    sink = lambda *record: None
    with pytest.raises(ValueError, match='routing_sink'):
        Engine(module, params, rows=2, block_size=16, share_prefix=True,
               routing_sink=sink)
    from tpusystem.models import gpt2_tiny
    dense = gpt2_tiny(dtype='float32')
    with pytest.raises(ValueError, match='routing_sink'):
        Engine(dense, dense.init(jax.random.PRNGKey(0), jnp.zeros(
            (1, 8), jnp.int32))['params'], rows=2, block_size=8,
            routing_sink=sink)
    engine = Engine(module, params, rows=2, block_size=16, routing_sink=sink)
    with pytest.raises(ValueError, match='another engine'):
        engine.admit_prefilled([1, 2, 3], 4, 5, {})
    assert engine.free_rows == 2


def test_eviction_mid_decode_frees_latent_blocks_and_spares_neighbours(served):
    _, module, params = served
    engine = Engine(module, params, rows=3, block_size=8)
    prompts = [tokens_of(40 + n, n).tolist() for n in (11, 19, 6)]
    rows = [engine.admit(prompt, max_new=9).row for prompt in prompts]
    engine.step()
    engine.step()
    held = engine.pool.live_blocks
    engine.evict(rows[1])
    assert engine.pool.live_blocks < held
    late = engine.admit(prompts[1], max_new=9)      # the freed row, reused
    tokens = drain(engine)
    for row, prompt in ((rows[0], prompts[0]), (rows[2], prompts[2]),
                        (late.row, prompts[1])):
        assert tokens[row] == standalone(module, params, prompt, 9)
    assert engine.trace_count == 1 and engine.pool.live_blocks == 0
    engine.pool.audit()


def test_saturation_queues_on_the_latent_pool(served):
    _, module, params = served
    engine = Engine(module, params, rows=2, block_size=8, blocks=9)
    scheduler = Scheduler(engine)
    prompts = [tokens_of(50 + n, n).tolist() for n in (30, 28, 12)]
    for index, prompt in enumerate(prompts):
        scheduler.submit(Request(f'r{index}', prompt, max_new=6))
    results = scheduler.run()           # 5 + 5 blocks do not fit 8: queued
    for index, prompt in enumerate(prompts):
        assert results[f'r{index}'].tokens == standalone(module, params,
                                                         prompt, 6)
    engine.pool.audit()


def test_prefix_sharing_over_latent_rows_is_token_exact(served):
    """Shared blocks hold latent rows; the resume prefill seeds a
    contiguous latent strip from the pool and applies the suffix down the
    absorbed path."""
    _, module, params = served
    engine = Engine(module, params, rows=4, block_size=4, blocks=80,
                    share_prefix=True)
    scheduler = Scheduler(engine)
    head = tokens_of(60, 21).tolist()
    prompts = [head + tokens_of(61 + k, k).tolist() for k in (3, 4, 5, 2)]
    for index, prompt in enumerate(prompts):
        scheduler.submit(Request(f'r{index}', prompt, max_new=5))
    results = scheduler.run()
    for index, prompt in enumerate(prompts):
        assert results[f'r{index}'].tokens == standalone(
            module, params, prompt, 5), f'r{index} diverged'
    assert engine.sharing['prefix_hits'] == 3
    assert engine.sharing['resumed_prefills'] == 3
    assert engine.trace_count == 1
    assert engine.prefix_cached_len(head + [9]) == 20
    engine.pool.audit()


def test_a_handed_off_prefill_is_one_latent_strip_a_layer(served):
    config, module, params = served
    engine = Engine(module, params, rows=2, block_size=8)
    prompt = tokens_of(70, 13).tolist()
    first, strips = engine.export_prefill(prompt)
    assert len(strips) == config['num_hidden_layers']
    assert all(strip.shape == (1, 128, 128) for strip in strips.values())
    other = Engine(module, params, rows=2, block_size=8)
    admission = other.admit_prefilled(prompt, 6, first, strips)
    assert drain(other)[admission.row] == standalone(module, params,
                                                     prompt, 6)


# --------------------------------------------------- streaming a handed tree

def test_a_tree_already_in_the_streamed_type_is_streamed_as_it_is(served):
    _, module, params = served
    narrow = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16), params)
    decoder = _decoder(module)
    streamed = _stream_params(decoder, narrow, 'bfloat16')
    assert all(a is b for a, b in zip(jax.tree.leaves(streamed),
                                      jax.tree.leaves(narrow)))
    # a float32 tree is still cast, its router left alone
    cast = _stream_params(decoder, params, 'bfloat16')
    assert cast['layer_1']['moe']['router'].dtype == jnp.float32
    assert cast['layer_1']['moe']['gate'].dtype == jnp.bfloat16
    assert cast['layer_0']['attn']['kv_b'].dtype == jnp.bfloat16
    assert cast['embedding'].dtype == jnp.float32


def test_a_bfloat16_tree_is_served_with_float32_router_scores(served):
    """The cell's types at the tiny size: parameters, products and pool in
    bfloat16, the router's scores in float32 from the bfloat16 matrix."""
    config = tiny_config(as_run=dict(
        tiny_config()['as_run'], param_dtype='bfloat16',
        compute_dtype='bfloat16', stream_dtype='bfloat16',
        kv_cache_dtype='bfloat16'))
    module, params = family.serve_module(config), family.make(config, 5)
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {
        jnp.dtype('bfloat16')}
    engine = Engine(module, params, rows=2, block_size=16,
                    stream_dtype='bfloat16',    # the sink, as the driver does
                    routing_sink=config['as_run']['levers']['routing_sink'])
    assert all(a is b for a, b in zip(jax.tree.leaves(engine._params),
                                      jax.tree.leaves(params)))
    assert engine._cache['layer_0']['attn']['key'].dtype == jnp.bfloat16
    prompt = tokens_of(80, 24).tolist()
    row = engine.admit(prompt, max_new=6).row
    tokens = drain(engine)[row]
    sample = [(prompt, tokens)]
    widest, covered = family.served_gap(config, 5, sample)
    assert covered == 6 and widest < 0.05      # bfloat16's own, not a fault's
