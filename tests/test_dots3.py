"""The dots3 family at test size (``dots3_tiny``): learned sparse latent
attention beside window latent attention, served through the paged pool and
the per-row window cache, against the benchmark's plain reference at
lengths where both the selection (``index_topk`` 8) and the window (5)
bite; the pieces the serving path is built from (the exact top-k, the two
kernels' walks, the uncached paths at short lengths, the expert share);
and what the engine refuses over a window cache, by name."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import dots3 as family
from chipbench.reference import dots3 as reference
from tpusystem.models import dots3_tiny
from tpusystem.models.dots3 import Dots3
from tpusystem.observe.trace import Tracer
from tpusystem.ops.attention import (banded_latent_attention,
                                     expanded_latent_attention, index_scores,
                                     select_top, selected_latent_attention)
from tpusystem.ops.moe import GatedExperts
from tpusystem.parallel import MeshSpec
from tpusystem.serve import InferenceService, Request
from tpusystem.serve.engine import Engine, window_reason
from tpusystem.train.cursors import gather_rows, rewind
from tpusystem.train.generate import _decoder
from tests.chipbench_tests.test_chipbench_dots3 import (CONFIG as SHIPPED,
                                                        PUBLISHED_TYPES,
                                                        tiny_config)

FULL, SLIDING = 'full_attention', 'sliding_attention'


@pytest.fixture(scope='module')
def tiny():
    config = tiny_config()
    return config, family.serve_module(config), family.make(config, 11)


def served(config, module, params, lengths, *, rows=3, tracer=None):
    """Serve one request a ``(prompt length, max_new)`` pair through
    ``InferenceService``, stating the service for the family's replay as
    the serving driver does; ``[(prompt, tokens)]`` and the engine."""
    config['as_run']['service'] = {'rows': rows, 'block_size': 4}
    service = InferenceService(module, params, rows=rows, block_size=4,
                               tracer=tracer, **config['as_run']['levers'])
    out, prompts = {}, {}
    for index, (length, count) in enumerate(lengths):
        prompt = np.random.default_rng([5, index]).integers(
            0, config['vocab_size'], length).tolist()
        prompts[index] = prompt
        service.service.handle(
            'submit', Request(f'r{index}', prompt, count),
            lambda _, token, index=index: out.setdefault(index, []).append(
                token))
    service.run_until_idle()
    return [(prompts[i], out[i]) for i in sorted(out)], service.engine


# ------------------------------------------------------ served vs reference

@pytest.mark.parametrize('lengths', [
    [(30, 10), (12, 20), (40, 8)],       # selection and window both bite
    [(3, 30), (25, 12)],                 # a row grows past both in decode
], ids=['long-prompts', 'short-prompt'])
def test_served_tokens_are_the_references_best_past_topk_and_window(
        tiny, lengths, capsys):
    """Prefill, adoption into the pool and the window cache, then decode:
    every served token is the float32 reference's own best, and every
    selection the program handed out is the reference's own (the
    reference goes through the program's experts and selections, and
    counts where its own differ)."""
    config, module, params = tiny
    sample, engine = served(config, module, params, lengths)
    assert engine.trace_count == 1 and engine.decode_impl == 'flax'
    assert all(len(prompt) + len(tokens) > 8 for prompt, tokens in sample)
    widest, covered = family.served_gap(config, 11, sample)
    assert covered == sum(len(tokens) for _, tokens in sample)
    assert widest < 2e-3
    reading = family.selection_reading(config)
    assert reading['flips'] == 0, capsys.readouterr().err
    assert reading['unmatched'] == 0
    assert reading['selected'] == family.selected_positions(config, sample)


def test_contiguous_decode_logits_match_the_reference(tiny):
    """The decode clone over its contiguous cache (``generate``'s), token
    by token past the window and the selection, against the reference's
    logits over the whole sequence."""
    config, module, params = tiny
    decoder = _decoder(module)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, 256, 36),
                         jnp.int32)
    prompt = 14
    logits, state = decoder.apply({'params': params}, tokens[None, :prompt],
                                  mutable=['cache'])
    rows = [logits[0]]
    cache = state['cache']
    for position in range(prompt, tokens.shape[0]):
        step, state = decoder.apply(
            {'params': params, 'cache': cache},
            tokens[None, position:position + 1], mutable=['cache'])
        cache = state['cache']
        rows.append(step[0])
    ours = jnp.concatenate(rows)
    theirs = reference.logits([tokens], family.reference_leaves(config, 11),
                              family.reference_model(config))[0]
    np.testing.assert_allclose(ours, theirs, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize('fault', ['window', 'index_topk'])
def test_a_wider_reach_than_stated_is_seen(tiny, fault, monkeypatch):
    """The planted faults: the program's window or selection widened past
    the configuration's; the reference keeps the stated one. A wider
    window moves the logits; a wider selection is what the replay hands
    out, which the reference goes through (the logits do not see it) and
    counts where its own (8 positions) differ: most of what the program
    selected past the eighth place."""
    config, module, params = tiny
    wider = dataclasses.replace(module, **{fault: 32})
    monkeypatch.setattr(family, 'serve_module', lambda config: wider)
    sample, _ = served(config, wider, params, [(30, 10), (40, 8)])
    widest, _ = family.served_gap(config, 11, sample)
    reading = family.selection_reading(config)
    if fault == 'window':
        assert widest > 0.05 and reading['flips'] == 0
    else:
        assert widest < 2e-3 and reading['share'] > 1.0, reading


def test_the_selection_reading_counts_each_position_either_way():
    """Two sequences of 12 and 20 positions past ``index_topk`` 8: the
    record covers 11 and 19, whose selections hold ``min(8, t + 1)`` each,
    in both full layers."""
    config = tiny_config()
    sample = [([1] * 5, [2] * 7), ([3] * 20, [])]
    per_layer = (36 + 3 * 8) + (36 + 11 * 8)
    assert family.selected_positions(config, sample) == 2 * per_layer


# ------------------------------------------------------ the pieces

@pytest.mark.parametrize('asked', [False, True],
                         ids=['window', 'replay'])
def test_the_selections_leave_the_device_only_where_asked(tiny, asked):
    """The same programs either way: without ``routing_selections`` a
    ``routing_sink`` gets the experts alone, a byte each, and every tick
    leaves the selections on the device; with it the selections ride
    behind the experts, which are the same."""
    config, module, params = tiny
    got = {}
    service = InferenceService(
        module, params, rows=2, block_size=4, stream_dtype='float32',
        routing_sink=lambda tag, prompt, tokens, routing: got.update(
            {tag: routing}),
        routing_selections=asked)
    words = []
    dispatch = service.engine._dispatch
    service.engine._dispatch = lambda ahead: _kept(dispatch(ahead), words)
    service.service.handle('submit', Request('a', list(range(1, 21)), 6))
    service.run_until_idle()
    routing = got['a']
    assert routing.shape[0] == 20 + 6 - 1
    if not asked:
        assert routing.dtype == np.uint8 and routing.shape[1:] == (3, 3)
        assert words and all(each is None for each in words)
        return
    experts, selected = family.split_record(config, routing)
    assert experts.shape[1:] == (3, 3) and selected.shape[1:] == (2, 2)
    assert words and all(each is not None for each in words)
    # position t selects min(8, t + 1) of the positions up to it, and more
    # where index scores tie at the eighth place (a ReLU gives many zeros)
    bits = np.unpackbits(selected.view(np.uint8), axis=-1,
                         bitorder='little').sum(axis=-1)
    stated = np.minimum(8, np.arange(1, 26))[:, None]
    assert (bits >= stated).all() and (bits[:8] == stated[:8]).all()


def _kept(tick, words):
    words.append(tick.words)
    return tick


def test_the_selections_are_asked_of_a_sink_over_a_sparse_module(tiny):
    config, module, params = tiny
    with pytest.raises(ValueError, match='routing_selections needs'):
        InferenceService(module, params, rows=2, block_size=4,
                         stream_dtype='float32', routing_selections=True)


def test_select_top_keeps_exactly_the_topk_of_the_valid():
    scores = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 40))
    valid = jnp.broadcast_to(
        jnp.arange(40)[None, None] <= jnp.array([3, 10, 39])[:, None, None],
        scores.shape)
    chosen = select_top(scores, valid, 8)
    masked = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, 8)[0][..., -1:]
    expected = valid & (masked >= kth)
    np.testing.assert_array_equal(chosen, expected)
    np.testing.assert_array_equal(jnp.sum(chosen, -1),
                                  jnp.minimum(jnp.sum(valid, -1), 8))


def _latent_inputs(heads=4, rank=16, rope=8, nope=16, v=16, length=12):
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    query = jax.random.normal(keys[0], (2, length, heads, nope + rope))
    latent = jax.random.normal(keys[1], (2, length, rank + rope))
    w_key = jax.random.normal(keys[2], (rank, heads, nope)) * 0.3
    w_value = jax.random.normal(keys[3], (rank, heads, v)) * 0.3
    return query, latent, w_key, w_value


@pytest.mark.parametrize('kind', ['selected', 'banded'])
def test_under_both_reaches_the_paths_are_plain_causal_attention(kind):
    """At lengths under ``index_topk`` and under the window every query
    attends every earlier position: plain causal latent attention."""
    query, latent, w_key, w_value = _latent_inputs()
    plain = expanded_latent_attention(query, latent[..., 16:], latent, w_key,
                                      w_value, 0.2)
    if kind == 'selected':
        index = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 3, 8))
        out = selected_latent_attention(
            query, latent, w_key, w_value, index, jnp.ones((2, 12, 3)),
            index[:, :, 0], scale=0.2, topk=12)
    else:
        out = banded_latent_attention(query, latent, w_key, w_value,
                                      scale=0.2, window=12)
    np.testing.assert_allclose(out, plain, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize('kind', ['selected', 'banded'])
def test_softmax_rows_cut_into_chunks_attend_as_whole_rows(kind, monkeypatch):
    """The prefill's softmax rows, cut into chunks of keys and joined,
    give what whole rows give, where the selection or the window bites
    and some chunks hold no key a query attends."""
    from tpusystem.ops import attention
    query, latent, w_key, w_value = _latent_inputs(length=40)
    index = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 3, 8))

    def attend():
        if kind == 'selected':
            return selected_latent_attention(
                query, latent, w_key, w_value, index, jnp.ones((2, 40, 3)),
                index[:, :, 0], scale=0.2, topk=6)
        return banded_latent_attention(query, latent, w_key, w_value,
                                       scale=0.2, window=5)

    whole = attend()
    monkeypatch.setattr(attention, '_SOFTMAX_KEYS', 8)
    np.testing.assert_allclose(attend(), whole, atol=1e-5, rtol=1e-5)


def test_the_index_kernel_walk_scores_every_held_position():
    """``paged_index_scores`` (interpreted) over a paged pool against the
    plain score of the rows gathered through the table; past a row's
    cursor it reads ``NEG_INF``."""
    from tpusystem.ops.pallas.index_scores import paged_index_scores
    rows, heads, dim, block, max_blocks = 3, 8, 16, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    pool = jax.random.normal(keys[0], ((1 + rows * max_blocks) * block, dim))
    table = 1 + jax.random.permutation(keys[3], rows * max_blocks).reshape(
        rows, max_blocks)
    cursor = jnp.array([0, 13, 31])
    query = jax.random.normal(keys[1], (rows, heads, dim))
    weights = jax.random.normal(keys[2], (rows, heads))
    scores = paged_index_scores(query, weights, pool, table, cursor,
                                block=block, interpret=True)
    flat = scores.reshape(rows, -1)
    gathered = jnp.take(pool.reshape(-1, block, dim), table,
                        axis=0).reshape(rows, -1, dim)
    plain = index_scores(query[:, None], weights[:, None], gathered)[:, 0]
    held = jnp.arange(max_blocks * block)[None] <= cursor[:, None]
    np.testing.assert_allclose(jnp.where(held, flat, 0),
                               jnp.where(held, plain, 0), atol=1e-4)
    assert bool(jnp.all(held | (flat < -1e29)))


def test_the_latent_kernel_walk_attends_only_the_selection():
    """``paged_latent_attention`` (interpreted) with a selection against
    the plain masked attention over the gathered rows."""
    from tpusystem.ops.pallas.latent_attention import (
        chunk_span, paged_latent_attention)
    rows, heads, rank, rope, block, max_blocks = 3, 4, 16, 8, 4, 8
    width, lanes = rank + rope, 128
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    slots = (1 + rows * max_blocks) * block
    pool = jnp.pad(jax.random.normal(keys[0], (slots, width)),
                   ((0, 0), (0, lanes - width)))
    table = 1 + jnp.arange(rows * max_blocks).reshape(rows, max_blocks)
    cursor = jnp.array([2, 17, 31])
    query = jax.random.normal(keys[1], (rows, heads, width))
    held = jnp.arange(max_blocks * block)[None] <= cursor[:, None]
    chosen = held & jax.random.bernoulli(keys[2], 0.5, held.shape)
    chosen = chosen.at[:, 0].set(True)
    span = chunk_span(block, max_blocks)
    out = paged_latent_attention(
        query, pool, table, cursor, rank=rank, width=width, block=block,
        scale=0.3, interpret=True,
        selected=chosen.astype(jnp.int32).reshape(rows, -1, 1, span))
    gathered = jnp.take(pool.reshape(-1, block, lanes), table,
                        axis=0).reshape(rows, -1, lanes)[..., :width]
    scores = jnp.einsum('rhc,rwc->rhw', query, gathered) * 0.3
    weights = jax.nn.softmax(jnp.where(chosen[:, None], scores, -1e30), -1)
    plain = jnp.einsum('rhw,rwc->rhc', weights, gathered[..., :rank])
    np.testing.assert_allclose(out, plain, atol=1e-4, rtol=1e-4)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a 16-expert layer (the program's
    ``GatedExperts``, each holding 4): their routed parts and the shared
    expert counted once add up to the reference's uncut layer."""
    config = tiny_config()
    layer = family.reference_leaves(config, 2)(1)
    hidden = jax.random.normal(jax.random.PRNGKey(3), (10, 64))
    model = family.reference_model(config, held=None)
    uncut_leaves = dict(layer)
    whole = {name: 0.02 * jax.random.normal(jax.random.PRNGKey(i),
                                            (16,) + leaf.shape[1:])
             for i, (name, leaf) in enumerate(layer.items())
             if name in ('gate', 'up', 'down')}
    uncut_leaves.update(whole)
    uncut, _ = reference.expert_layer(hidden, uncut_leaves, model, 'float32')
    total = 0.0
    for start in range(0, 16, 4):
        share = GatedExperts(experts=16, k=3, width=48, shared_width=48,
                             held=(start, 4), dtype=jnp.float32,
                             scoring='sigmoid')
        params = {'router': layer['router'], 'correction': layer['correction'],
                  **{name: leaf[start:start + 4]
                     for name, leaf in whole.items()},
                  **{f'shared_{part}': {'kernel': layer[f'shared_{part}']}
                     for part in ('gate', 'up', 'down')}}
        total = total + share.apply({'params': params}, hidden)
    shared = reference.gated_mlp(hidden, layer['shared_gate'],
                                 layer['shared_up'], layer['shared_down'],
                                 'float32')
    np.testing.assert_allclose(total - 3 * shared, uncut, atol=2e-5)


# ------------------------------------------------------ the engine

def test_the_engine_records_how_each_kind_reads_and_what_the_cache_holds(
        tiny):
    config, module, params = tiny
    tracer = Tracer('serve')
    _, engine = served(config, module, params, [(9, 4)], rows=2,
                       tracer=tracer)
    assert engine.sparse_attention == {
        'index_topk': 8, 'window': 5, 'full_layers': 2, 'window_layers': 2,
        'index_bytes_per_position': 2 * 16 * 4,
        'window_bytes_per_row': 2 * 16 * 128 * 4,
        'sparse_read': 'gather', 'window_read': 'window cache'}
    blocks = 2 * 64 // 4 + 1
    assert engine.cache_bytes == {
        'kv': 2 * blocks * 4 * 128 * 4, 'state': 0,
        'index': 2 * blocks * 4 * 16 * 4, 'window': 2 * 2 * 16 * 128 * 4}
    marks = {event['name']: event['args'] for event in tracer.events()
             if event.get('ph') == 'i'}
    for name in ('sparse_attention', 'cache_bytes'):
        said = getattr(engine, name)
        assert {key: marks[name][key] for key in said} == said


def test_a_module_without_window_layers_records_no_sparse_attention():
    from tpusystem.models import deepseek_tiny
    module = deepseek_tiny()
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    engine = Engine(module, params['params'], rows=2, block_size=4)
    assert engine.sparse_attention is None
    assert set(engine.cache_bytes) == {'kv', 'state'}


def tiny_draft():
    draft = dots3_tiny(layer_types=(SLIDING,), first_dense=1)
    return draft, draft.init(jax.random.PRNGKey(1),
                             jnp.zeros((1, 4), jnp.int32))['params']


@pytest.mark.parametrize('asked, named', [
    (dict(share_prefix=True), 'share_prefix=True'),
    (dict(draft=True), 'a draft module'),
    (dict(mesh=2), 'a mesh')])
def test_the_engine_refuses_by_name_what_a_window_cache_cannot_do(
        tiny, asked, named):
    config, module, params = tiny
    levers = dict(asked)
    if levers.pop('draft', False):
        levers['draft_module'], levers['draft_params'] = tiny_draft()
    if 'mesh' in levers:
        levers['mesh'] = MeshSpec(model=2).build(jax.devices()[:2])
    with pytest.raises(ValueError, match='window cache') as refused:
        Engine(module, params, rows=2, block_size=4, **levers)
    assert named in str(refused.value) and 'Dots3' in str(refused.value)


def test_a_module_without_a_window_cache_is_asked_nothing_new():
    from tpusystem.models import deepseek_tiny
    plain = deepseek_tiny()
    assert window_reason(plain, share_prefix=True, sharded=True) is None
    draft, _ = tiny_draft()
    assert 'a draft module' in window_reason(plain, draft_module=draft)


def test_cursors_gather_a_window_by_row_and_refuse_to_rewind_it(tiny):
    _, module, _ = tiny
    decoder = dataclasses.replace(_decoder(module, per_row=True))
    cache = jax.eval_shape(
        lambda: decoder.init(jax.random.PRNGKey(0),
                             jnp.zeros((3, 1), jnp.int32)))['cache']
    cache = jax.tree.map(lambda leaf: jnp.arange(leaf.size).reshape(
        leaf.shape).astype(leaf.dtype), cache)
    window = cache['layer_2']['attn']['window']
    gathered = gather_rows(cache, jnp.array([2, 2, 0]))
    np.testing.assert_array_equal(gathered['layer_2']['attn']['window'],
                                  window[jnp.array([2, 2, 0])])
    with pytest.raises(ValueError, match='window cache'):
        rewind(cache, 0)
    assert rewind(cache, 0, state_stands=True)['layer_2']['attn'][
        'window'] is window


def test_the_published_defaults_are_the_configuration():
    """``Dots3()`` at its defaults holds the published widths the
    configuration states (46 layers, 13 full)."""
    model = Dots3()
    assert len(model.layer_types) == 46
    assert sum(kind == FULL for kind in model.layer_types) == 13
    assert list(model.layer_types) == PUBLISHED_TYPES
    assert (model.heads, model.kv_rank, model.window_kv_rank, model.window,
            model.index_topk, model.experts, model.experts_per_token) == (
        SHIPPED['num_attention_heads'], SHIPPED['kv_lora_rank'],
        SHIPPED['swa_kv_lora_rank'], SHIPPED['sliding_window_size'],
        SHIPPED['index_topk'], 256, SHIPPED['num_experts_per_tok'])
