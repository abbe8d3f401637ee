"""The Nemotron-H family at a tiny size on the CPU: the model against the
benchmark's plain reference (``chipbench/reference/nemotron_h.py``, which
imports nothing of ``tpusystem/`` and runs the state-space layers as the
recurrence, token by token), the chunked scan against that recurrence, a
padded prefill then decode through the engine's cache against the full
forward pass, the share of an expert-parallel deployment, the sigmoid router
against its definition, and the serving engine over two kinds of cache: what
it seats, reseats, hands off, counts and refuses.

Tolerances (float32 throughout, so nothing here is rounding of a narrow
type): logits against the reference ``5e-5`` — the two sum the same products
in another order (the chunked scan's decay-masked products against the
recurrence, grouped products over sorted rows against a masked loop), a few
float32 ulps of logits of magnitude 1 through five layers; the scan against
the recurrence ``2e-5`` on values of magnitude 10; cached against uncached
``2e-5`` for the same reason. Tokens are compared exactly where the
arithmetic is window-invariant.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import nemotron_h as family
from chipbench.reference import nemotron_h as reference
from tests.chipbench_tests.nemotron import SHIPPED, tiny_config
from tpusystem.models.nemotron_h import (PUBLISHED_PATTERN, NemotronH,
                                         nemotron_tiny)
from tpusystem.observe.trace import Tracer
from tpusystem.ops.moe import GatedExperts, corrected_top_k
from tpusystem.ops.ssm import Mamba2, ssm_scan, ssm_update
from tpusystem.parallel import MeshSpec
from tpusystem.serve import Engine, InferenceService, Request
from tpusystem.serve.engine import (_build_prefill, engine_unsupported_reason,
                                    is_recurrent, recurrent_reason)
from tpusystem.train.cursors import (gather_rows, holds_row_state,
                                     is_row_state, rewind)
from tpusystem.train.decode_fused import fused_paged_reason
from tpusystem.train.generate import _decoder, generate, speculative_generate


@pytest.fixture(scope='module')
def served():
    config = tiny_config()
    return config, family.serve_module(config), family.make(config, 11)


def tokens_of(seed: int, *shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, shape).astype(np.int32)


def reference_logits(config, seed, ids):
    return reference.logits([jnp.asarray(ids)],
                            family.reference_leaves(config, seed),
                            family.reference_model(config))[0]


# ------------------------------------------------- the published defaults

def test_the_defaults_are_the_published_widths():
    module = NemotronH()
    assert module.pattern == PUBLISHED_PATTERN == \
        SHIPPED['published']['hybrid_override_pattern']
    assert (module.layers, module.pattern.count('M'), module.pattern.count('E'),
            module.pattern.count('*')) == (52, 23, 23, 6)
    for field, key in (('dim', 'hidden_size'), ('ssm_heads', 'mamba_num_heads'),
                       ('ssm_head_dim', 'mamba_head_dim'),
                       ('ssm_groups', 'n_groups'),
                       ('ssm_state', 'ssm_state_size'),
                       ('conv_kernel', 'conv_kernel'), ('chunk', 'chunk_size'),
                       ('heads', 'num_attention_heads'),
                       ('kv_heads', 'num_key_value_heads'),
                       ('head_dim', 'head_dim'),
                       ('expert_width', 'moe_intermediate_size'),
                       ('shared_width', 'moe_shared_expert_intermediate_size'),
                       ('experts_per_token', 'num_experts_per_tok'),
                       ('routed_scale', 'routed_scaling_factor'),
                       ('eps', 'layer_norm_epsilon')):
        assert getattr(module, field) == SHIPPED[key], field
    assert module.experts == SHIPPED['published']['n_routed_experts']
    assert module.vocab_size == SHIPPED['published']['vocab_size']
    with pytest.raises(ValueError, match="'M' .Mamba-2."):
        nemotron_tiny(pattern='MX').init(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 4), jnp.int32))


# ------------------------------------------------- model against reference

def test_logits_match_the_plain_reference(served):
    """The program's chunked scan (chunk 8) against the reference's
    recurrence, through all three kinds of layer."""
    config, module, params = served
    tokens = tokens_of(0, 2, 128)
    ours = module.apply({'params': params}, jnp.asarray(tokens))
    for row in range(2):
        want = reference_logits(config, 11, tokens[row])
        np.testing.assert_allclose(np.asarray(ours[row]), np.asarray(want),
                                   atol=5e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.1


def test_the_state_reaches_the_logits(served):
    """The seeded state-space leaves are drawn so that the recurrence
    matters: with the state's term taken out of ``y`` (``C`` zeroed: the
    skip ``D x`` alone is left) the logits move by far more than any
    tolerance here."""
    config, module, params = served
    tokens = jnp.asarray(tokens_of(0, 1, 64))
    ours = module.apply({'params': params}, tokens)
    inner, _ = family._sizes(config)
    cut = dict(params)
    for layer in ('layer_0', 'layer_3'):
        mixer = dict(cut[layer]['mixer'])
        groups = config['n_groups'] * config['ssm_state_size']
        first = inner + inner + groups            # z, x and B come before C
        mixer['in_proj'] = mixer['in_proj'].at[:, first:first + groups].set(0)
        mixer['conv_bias'] = mixer['conv_bias'].at[inner + groups:].set(0)
        cut[layer] = dict(cut[layer], mixer=mixer)
    without = module.apply({'params': cut}, tokens)
    assert float(jnp.max(jnp.abs(ours - without))) > 0.01


# ------------------------------------------- the scan against the recurrence

def recurrence(x, dt, A, B, C, initial):
    def step(state, at):
        y, state = ssm_update(state, at[0], at[1], A, at[2], at[3])
        return state, y
    last, ys = jax.lax.scan(step, initial, tuple(
        jnp.moveaxis(each, 1, 0) for each in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1), last


@pytest.mark.parametrize('length', [1, 5, 15, 16, 17, 32, 37, 64])
def test_the_chunked_scan_is_the_recurrence(length):
    """Lengths below, at and across the edges of a 16-position chunk, from
    a state that is not zero; 4 heads in 2 groups."""
    keys = jax.random.split(jax.random.PRNGKey(length), 6)
    batch, heads, width, groups, size = 2, 4, 8, 2, 16
    x = jax.random.normal(keys[0], (batch, length, heads, width))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, length, heads)) - 2)
    A = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    B = jax.random.normal(keys[3], (batch, length, groups, size))
    C = jax.random.normal(keys[4], (batch, length, groups, size))
    initial = jax.random.normal(keys[5], (batch, heads, width, size))
    want_y, want_state = recurrence(x, dt, A, B, C, initial)
    y, state = ssm_scan(x, dt, A, B, C, chunk=16, initial=initial)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=2e-5)
    # positions whose step is 0 leave the state where it stood
    cut = max(length - 3, 0)
    masked = dt.at[:, cut:].set(0.0)
    _, stopped = ssm_scan(x, masked, A, B, C, chunk=16, initial=initial)
    _, until = recurrence(x[:, :cut], dt[:, :cut], A, B[:, :cut], C[:, :cut],
                          initial)
    np.testing.assert_allclose(np.asarray(stopped), np.asarray(until),
                               atol=2e-5)


def test_the_layer_leaves_state_and_tail_at_each_rows_own_length():
    """``length`` per row: the state and the convolution's last inputs are
    those of the unpadded prefix, whatever lies behind it."""
    layer = Mamba2(heads=4, head_dim=8, groups=2, state=16, chunk=8,
                   dtype=jnp.float32, decode=True)
    hidden = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    params = layer.init(jax.random.PRNGKey(2), hidden[:, :1])['params']
    lengths = jnp.asarray([13, 2])
    _, padded = layer.apply({'params': params}, hidden, lengths,
                            mutable=['cache'])
    for row, length in enumerate((13, 2)):
        _, exact = layer.apply({'params': params},
                               hidden[row:row + 1, :length], mutable=['cache'])
        for leaf in ('state', 'conv'):
            np.testing.assert_allclose(
                np.asarray(padded['cache'][leaf][row]),
                np.asarray(exact['cache'][leaf][0]), atol=1e-5)
    assert padded['cache']['conv'].shape == (2, 3, 32 + 2 * 2 * 16)
    assert padded['cache']['state'].dtype == jnp.float32


# --------------------------------------------- padded prefill, then decode

@pytest.mark.parametrize('length, bucket', [(21, 32), (37, 64), (9, 16)])
def test_a_padded_prefill_then_decode_is_the_references_full_pass(
        served, length, bucket):
    """The engine's own prefill program over a prompt right-padded to its
    bucket (``length`` handed in as the engine hands it), then decode steps
    through the cache it left: logits against the reference's one full pass
    over prompt + decoded tokens. **The padding test**: the same with
    ``length`` withheld fails, by far more than the tolerance."""
    config, module, params = served
    ids = tokens_of(length, length + 6)
    want = np.asarray(reference_logits(
        config, 11, np.pad(ids, (0, 128 - ids.size))))[:ids.size]
    decoder = _decoder(module, per_row=True)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :length] = ids[:length]

    def through_the_cache(told):
        logits, state = decoder.apply(
            {'params': params}, jnp.asarray(padded),
            **({'length': jnp.int32(length)} if told else {}),
            mutable=['cache'])
        cache = rewind(state['cache'], length, state_stands=True)
        seen = [np.asarray(logits[0, :length])]
        for token in ids[length:]:
            logits, state = decoder.apply(
                {'params': params, 'cache': cache},
                jnp.asarray([[token]], jnp.int32), mutable=['cache'])
            cache = state['cache']
            seen.append(np.asarray(logits[0]))
        return np.concatenate(seen)

    np.testing.assert_allclose(through_the_cache(True), want, atol=5e-5)
    ignored = through_the_cache(False)
    np.testing.assert_allclose(ignored[:length], want[:length], atol=5e-5)
    assert np.max(np.abs(ignored[length:] - want[length:])) > 0.01


def test_the_engines_prefill_program_hands_the_module_its_length(served):
    _, module, params = served
    run = _build_prefill(_decoder(module), 32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = tokens_of(3, 21)
    greedy = (jnp.uint32(0), jnp.int32(0), jnp.float32(0.0), jnp.int32(0),
              jnp.float32(1.0), jnp.ones(256, bool))
    _, cache = run(params, jnp.asarray(padded), 21, *greedy)
    _, exact = _decoder(module).apply({'params': params},
                                      jnp.asarray(padded[:, :21]),
                                      mutable=['cache'])
    for layer in ('layer_0', 'layer_3'):
        for leaf in ('state', 'conv'):
            np.testing.assert_allclose(
                np.asarray(cache[layer]['mixer'][leaf]),
                np.asarray(exact['cache'][layer]['mixer'][leaf]), atol=1e-5)


def test_prefill_then_decode_matches_the_full_forward_pass(served):
    """Contiguous cache (``generate``, the static loop the engine tests call
    the reference): every decoded token is the argmax of the uncached pass
    over what came before, through the same cache leaves."""
    _, module, params = served
    prompt = tokens_of(1, 2, 21)
    out = np.asarray(generate(module, params, jnp.asarray(prompt), steps=12))
    full = module.apply({'params': params}, jnp.asarray(out))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(full[:, 20:-1], axis=-1)), out[:, 21:])
    decoder = _decoder(module, per_row=True)
    _, state = decoder.apply({'params': params}, jnp.asarray(out[:, :21]),
                             mutable=['cache'])
    # a window of several tokens over a state that exists: the scan from it
    stepped, _ = decoder.apply({'params': params, 'cache': state['cache']},
                               jnp.asarray(out[:, 21:25]), mutable=['cache'])
    np.testing.assert_allclose(np.asarray(stepped),
                               np.asarray(full[:, 21:25]), atol=2e-5)


# ------------------------------------------------------- the expert layer

def expert_layer_params(key, held, pad_to=0):
    layer = GatedExperts(experts=16, k=3, width=48, scale=2.5,
                         shared_width=96, held=held, dtype=jnp.float32,
                         scoring='sigmoid', form='relu2',
                         pad_to=pad_to)
    params = layer.init(key, jnp.zeros((1, 4, 64)))['params']
    return layer, dict(params, correction=0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (16,)))


def reference_leaves(params):
    leaves = {name: params[name]
              for name in ('router', 'correction', 'up', 'down')}
    leaves.update({name: params[name]['kernel']
                   for name in ('shared_up', 'shared_down')})
    return leaves


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide: each of four chips holds
    four of the sixteen experts, routes over all sixteen and sums its own;
    the partial sums, with the shared expert (which every chip computes
    alike) counted once, are the uncut reference's layer."""
    whole, params = expert_layer_params(jax.random.PRNGKey(13), None)
    assert set(params) == {'router', 'correction', 'up', 'down', 'shared_up',
                           'shared_down'}                 # no gate anywhere
    hidden = jax.random.normal(jax.random.PRNGKey(14), (3, 20, 64))
    flat, leaves = hidden.reshape(-1, 64), reference_leaves(params)
    model = family.reference_model(tiny_config(), held=None)
    model = model.__class__(**{**model.__dict__, 'n_routed': 16})
    want, _ = reference.expert_layer(flat, leaves, model, 'float32')
    shared = reference.relu2_mlp(flat, leaves['shared_up'],
                                 leaves['shared_down'], 'float32')
    total, seated = jnp.zeros_like(want), 0
    for start in (0, 4, 8, 12):
        share, _ = expert_layer_params(jax.random.PRNGKey(0), (start, 4))
        mine = dict(params, **{name: params[name][start:start + 4]
                               for name in ('up', 'down')})
        part, counted = share.apply({'params': mine}, hidden,
                                    mutable=['expert_load'])
        total = total + part.reshape(-1, 64) - shared
        seated += int(counted['expert_load']['seated'])
        held = dict(leaves, **{name: leaves[name][start:start + 4]
                               for name in ('up', 'down')})
        ref_part, _ = reference.expert_layer(
            flat, held, model.__class__(**{**model.__dict__,
                                           'held': (start, 4)}), 'float32')
        np.testing.assert_allclose(np.asarray(part.reshape(-1, 64)),
                                   np.asarray(ref_part), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               atol=5e-5)
    assert seated == 3 * 20 * 3          # every assignment seated somewhere
    uncut = whole.apply({'params': params}, hidden)
    np.testing.assert_allclose(np.asarray(uncut.reshape(-1, 64)),
                               np.asarray(want), atol=5e-5)


def test_the_sigmoid_router_chooses_by_corrected_score_and_weighs_by_score():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5), (50, 16)))
    correction = jnp.zeros(16).at[3].set(5.0).at[7].set(-5.0)
    ids, weights = corrected_top_k(scores, correction, 4)
    ids, weights, plain = np.asarray(ids), np.asarray(weights), np.asarray(scores)
    for token in range(50):
        want = np.argsort(-(plain[token] + np.asarray(correction)),
                          kind='stable')[:4]
        assert ids[token].tolist() == want.tolist()
        assert 3 in ids[token] and 7 not in ids[token]
        # the weights know nothing of the correction: the chosen experts'
        # own scores over their sum
        chosen = plain[token][ids[token]]
        np.testing.assert_allclose(weights[token], chosen / chosen.sum(),
                                   rtol=1e-6)
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)
    # without a correction the choice is the plain top-k
    ids, _ = corrected_top_k(scores, jnp.zeros(16), 4)
    assert np.asarray(ids).tolist() == np.argsort(
        -plain, axis=-1, kind='stable')[:, :4].tolist()


def test_the_softmax_form_is_untouched_and_unknown_forms_are_refused():
    layer = GatedExperts(experts=16, k=3, width=48, groups=4, keep_groups=2,
                         shared_width=48, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 4, 64)))['params']
    assert set(params) == {'router', 'gate', 'up', 'down', 'shared_gate',
                           'shared_up', 'shared_down'}
    for wrong in (dict(scoring='tanh'), dict(form='geglu')):
        with pytest.raises(ValueError, match='scoring=|form='):
            GatedExperts(experts=4, k=1, width=8, **wrong).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 2, 8)))


def test_padded_expert_matrices_are_the_same_layer():
    """``pad_to`` stores the routed matrices with both dimensions rounded
    up; the padding, whatever it holds, is read by nothing."""
    plain, params = expert_layer_params(jax.random.PRNGKey(3), (4, 8))
    padded, wide = expert_layer_params(jax.random.PRNGKey(3), (4, 8), 40)
    assert (params['up'].shape, params['down'].shape) == ((8, 64, 48),
                                                          (8, 48, 64))
    assert (wide['up'].shape, wide['down'].shape) == ((8, 80, 80),
                                                      (8, 80, 80))
    wide = dict(params,
                up=wide['up'].at[:, :64, :48].set(params['up']),
                down=wide['down'].at[:, :48, :64].set(params['down']))
    hidden = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 64))
    np.testing.assert_allclose(
        np.asarray(padded.apply({'params': wide}, hidden)),
        np.asarray(plain.apply({'params': params}, hidden)), atol=1e-6)


# ----------------------------------------------------------- the engine

def standalone(module, params, prompt, steps):
    out = generate(module, params, jnp.asarray([prompt]), steps=steps)
    return np.asarray(out)[0, len(prompt):].tolist()


def drain(engine) -> dict:
    tokens = {}
    while engine.active_rows:
        for row, _reason, out in engine.step().finished:
            tokens[row] = out
    return tokens


def test_the_service_serves_it_over_two_kinds_of_cache(served):
    """Prompts whose lengths are not their buckets', through
    ``InferenceService`` on the engine's normal path: token-exact against
    the static loop, one decode trace, one trace of each membership
    program, and tokens the plain reference puts first."""
    config, module, params = served
    assert engine_unsupported_reason(module) is None
    assert is_recurrent(module)
    tracer = Tracer('serve')
    levers = dict(config['as_run']['levers'])
    service = InferenceService(module, params, rows=3, block_size=16,
                               tracer=tracer, **levers)
    engine = service.engine
    assert engine.decode_impl == 'flax'
    assert 'NemotronH' in fused_paged_reason(engine._decoder)
    # state-space layers: a per-row state and tail, no blocks, no table;
    # attention layers: the paged pool, as every flax-step family's
    for layer, kind in enumerate(config['hybrid_override_pattern']):
        cache = engine._cache.get(f'layer_{layer}', {}).get('mixer', {})
        if kind == 'M':
            assert set(cache) == {'state', 'conv', 'index'}
            assert cache['state'].shape == (3, 4, 8, 16)
            assert cache['state'].dtype == jnp.float32
            assert cache['conv'].shape == (3, 3, 32 + 2 * 2 * 16)
        elif kind == '*':
            assert set(cache) == {'key', 'value', 'table', 'index'}
            assert cache['key'].shape == (engine.pool.blocks * 16, 2 * 16)
        else:
            assert not cache
    assert holds_row_state(engine._cache)
    kv = 2 * engine.pool.blocks * 16 * 32 * 4
    state = 2 * 3 * (4 * 8 * 16 + 3 * 96) * 4
    assert engine.cache_bytes == {'kv': kv, 'state': state}
    marks = [event for event in tracer.events()
             if event['name'] == 'cache_bytes']
    assert len(marks) == 1 and {name: marks[0]['args'][name] for name
                                in ('kv', 'state')} == engine.cache_bytes
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
    load = engine.expert_load
    assert load['ticks'] > 0 and 0 < load['hit'] <= load['ticks'] * 4 * 2
    sample = [(prompt, service.scheduler.results[f'r{index}'].tokens)
              for index, prompt in enumerate(prompts)]
    widest, covered = family.served_gap(config, 11, sample)
    assert covered == 6 * 8 and widest <= 1e-4


def test_a_row_seated_after_a_retired_one_reads_as_on_a_fresh_engine(served):
    """A retired row's state is never cleared: it idles, parked, through
    the ticks of its neighbours (its update stays in its own row), and the
    next admission overwrites the row whole. What that row then serves is
    what a fresh engine serves, and its neighbours never noticed."""
    _, module, params = served
    first, second, neighbour = (tokens_of(seed, size).tolist() for seed, size
                                in ((40, 30), (41, 11), (42, 19)))
    tracer = Tracer('rows').watch_compiles()
    engine = Engine(module, params, rows=2, block_size=16)
    gone = engine.admit(first, 4, tag='first')
    stays = engine.admit(neighbour, 30, tag='neighbour')
    retired = {}
    while gone.row not in retired:
        retired.update({row: out for row, _reason, out
                        in engine.step().finished})
    parked = np.asarray(engine._cache['layer_0']['mixer']['state'][gone.row])
    for _ in range(5):                       # the parked row keeps ticking
        engine.step()
    assert np.abs(parked).max() > 0
    again = engine.admit(second, 9, tag='second')
    assert again.row == gone.row
    retired.update(drain(engine))
    assert retired[again.row] == standalone(module, params, second, 9)
    assert retired[stays.row] == standalone(module, params, neighbour, 30)
    fresh = Engine(module, params, rows=2, block_size=16)
    fresh.admit(second, 9)
    assert list(drain(fresh).values()) == [retired[again.row]]
    traced = tracer.compiled('trace')     # the fresh engine's, and this one's
    assert (traced['seat'], traced['clear']) == (2, 2)


@pytest.mark.parametrize('read, widths, why', [
    ('kernel', dict(kv_heads=2, head_dim=64), None),       # rows of 128 lanes
    ('gather', dict(kv_heads=2, head_dim=16), 'cannot tile')],  # of 32
    ids=['rows of 128 lanes', 'rows of 32'])
def test_the_engine_reads_the_pool_through_the_kernel_where_it_tiles(
        read, widths, why, monkeypatch):
    """On the TPU one decoded token a row goes through the paged-attention
    kernel where its plan tiles the stored row; steered here (the look for
    a TPU says yes, the kernel runs interpreted), it is called once an
    attention layer in the one decode trace, a plan that refuses leaves the
    gather, the engine's ``paged_read`` says which, and either way the
    tokens are ``generate``'s."""
    from tpusystem.ops import attention
    from tpusystem.ops.pallas import paged_attention as kernel_module
    module = nemotron_tiny(pattern='M*E*', max_seq=64, **widths)
    params = module.init(jax.random.PRNGKey(3),
                         jnp.zeros((1, 4), jnp.int32))['params']
    prompts = [tokens_of(60 + n, n).tolist() for n in (21, 6)]
    want = [standalone(module, params, prompt, 5) for prompt in prompts]
    assert Engine(module, params, rows=2, block_size=8).paged_read == {
        'read': 'gather', 'reason': 'not on a TPU'}
    calls = []
    real = kernel_module.paged_decode_attention
    monkeypatch.setattr(kernel_module, 'paged_decode_attention',
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setattr(attention, 'on_tpu', lambda: True)
    tracer = Tracer('serve')
    service = InferenceService(module, params, rows=2, block_size=8,
                               decode_impl='flax', tracer=tracer)
    engine = service.engine
    assert engine.paged_read['read'] == read
    assert (why or '') in (engine.paged_read['reason'] or '')
    marks = [event['args'] for event in tracer.events()
             if event['name'] == 'paged_read']
    assert len(marks) == 1 and {name: marks[0][name] for name
                                in ('read', 'reason')} == engine.paged_read
    rows = [engine.admit(prompt, max_new=5).row for prompt in prompts]
    tokens = drain(engine)
    monkeypatch.setattr(attention, 'on_tpu', lambda: False)
    assert engine.trace_count == 1
    assert len(calls) == (2 if read == 'kernel' else 0)   # once a `*` layer
    assert [tokens[row] for row in rows] == want


def tiny_draft():
    draft = nemotron_tiny(pattern='M*', held=None)
    return draft, draft.init(jax.random.PRNGKey(1),
                             jnp.zeros((1, 4), jnp.int32))['params']


@pytest.mark.parametrize('asked, named', [
    (dict(share_prefix=True), 'share_prefix=True'),
    (dict(draft=True), 'a draft module'),
    (dict(mesh=2), 'a mesh')])
def test_the_engine_refuses_by_name_what_a_state_cannot_do(served, asked,
                                                           named):
    _, module, params = served
    levers = dict(asked)
    if levers.pop('draft', False):
        levers['draft_module'], levers['draft_params'] = tiny_draft()
    if 'mesh' in levers:
        levers['mesh'] = MeshSpec(model=2).build(jax.devices()[:2])
    with pytest.raises(ValueError, match='recurrent state') as refused:
        Engine(module, params, rows=2, block_size=16, **levers)
    assert named in str(refused.value) and 'NemotronH' in str(refused.value)


def test_the_reasons_are_the_states_alone():
    """A module with no state-space layer is asked nothing new; a draft
    that has one is refused under a target that has none."""
    plain = nemotron_tiny(pattern='*E*')
    assert not is_recurrent(plain)
    assert recurrent_reason(plain, share_prefix=True, sharded=True) is None
    draft, _ = tiny_draft()
    assert 'a draft module' in recurrent_reason(plain, draft_module=draft)
    assert recurrent_reason(nemotron_tiny()) is None


def test_cursors_gather_a_state_by_row_and_refuse_to_rewind_it(served):
    _, module, params = served
    decoder = _decoder(module, per_row=True)
    _, state = decoder.apply({'params': params},
                             jnp.asarray(tokens_of(5, 3, 12)),
                             mutable=['cache'])
    cache = state['cache']
    picked = gather_rows(cache, jnp.asarray([2, 2, 0]))
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if is_row_state(path):
            got = picked
            for key in path:
                got = got[key.key]
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(leaf[2]))
            np.testing.assert_array_equal(np.asarray(got[2]),
                                          np.asarray(leaf[0]))
    with pytest.raises(ValueError, match='cannot be rewound'):
        rewind(cache, jnp.asarray([3, 3, 3]))
    parked = rewind(cache, jnp.asarray([3, 3, 3]), state_stands=True)
    assert int(parked['layer_2']['mixer']['index'][0]) == 3
    draft, draft_params = tiny_draft()
    with pytest.raises(ValueError, match='cannot be rewound'):
        speculative_generate(module, params, jnp.asarray(tokens_of(6, 1, 8)),
                             steps=4, draft_module=draft,
                             draft_params=draft_params)


def test_a_handed_off_prefill_carries_the_state(served):
    """``export_prefill`` ships the state-space layers' row beside the
    key/value strips; the engine that seats it decodes what the engine that
    prefilled it would have."""
    _, module, params = served
    prompt = tokens_of(9, 27).tolist()
    prefiller = Engine(module, params, rows=1, block_size=16)
    first, strips = prefiller.export_prefill(prompt)
    leaves = {name.rsplit("['", 1)[1][:-2] for name in strips}
    assert leaves == {'state', 'conv', 'key', 'value'}
    assert sum(name.endswith("['state']") for name in strips) == 2
    decoder = Engine(module, params, rows=2, block_size=16)
    seated = decoder.admit_prefilled(prompt, 7, first, strips)
    assert drain(decoder)[seated.row] == standalone(module, params, prompt, 7)
