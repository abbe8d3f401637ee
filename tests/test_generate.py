"""KV-cache autoregressive decoding: exact parity with full re-forward.

The decode path (cache variables, cursor-offset positions/rotary, masked
attention over the filled prefix) must produce token-for-token the same
greedy continuation as rerunning the full forward per step — in float32
the two are exactly equal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpusystem.models import gpt2_tiny, llama_tiny
from tpusystem.train import generate


def full_forward_greedy(module, params, prompt, steps):
    sequence = prompt
    for _ in range(steps):
        out = module.apply({'params': params}, sequence)
        logits = out[0] if isinstance(out, tuple) else out   # MoE: (logits, aux)
        next_token = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        sequence = jnp.concatenate([sequence, next_token[:, None]], axis=1)
    return sequence


@pytest.fixture(scope='module')
def prompt():
    return jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 7)), jnp.int32)


@pytest.mark.parametrize('family', [gpt2_tiny, llama_tiny])
@pytest.mark.slow
def test_greedy_decode_matches_full_forward(family, prompt):
    module = family(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    cached = generate(module, params, prompt, steps=5)
    reference = full_forward_greedy(module, params, prompt, 5)
    np.testing.assert_array_equal(np.asarray(cached), np.asarray(reference))


def test_prompt_is_preserved_and_shapes(prompt):
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    out = generate(module, params, prompt, steps=3)
    assert out.shape == (2, 10) and out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out[:, :7]), np.asarray(prompt))


def test_temperature_sampling_stays_in_vocab(prompt):
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    out = generate(module, params, prompt, steps=6, temperature=1.0,
                   rng=jax.random.PRNGKey(7))
    tail = np.asarray(out[:, 7:])
    assert ((tail >= 0) & (tail < module.vocab_size)).all()
    # a different key gives a different draw (overwhelmingly)
    other = generate(module, params, prompt, steps=6, temperature=1.0,
                     rng=jax.random.PRNGKey(8))
    assert not np.array_equal(np.asarray(out), np.asarray(other))


def test_temperature_without_rng_raises(prompt):
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    with pytest.raises(ValueError):
        generate(module, params, prompt, steps=2, temperature=0.5)


def test_capacity_overflow_raises(prompt):
    module = gpt2_tiny(dtype='float32')   # max_seq = 128
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    with pytest.raises(ValueError):
        generate(module, params, prompt, steps=128)


@pytest.mark.slow
def test_moe_model_decodes_matching_full_forward(prompt):
    """MoE decode drops the training-only aux output; in a no-drop config
    (k == experts, capacity covers every token — chosen deliberately) it
    matches the full re-forward exactly. Drop-configs may route
    differently at decode (capacity derives from per-call token counts);
    the model-side comment documents that standard asymmetry."""
    module = gpt2_tiny(dtype='float32', moe_experts=2, moe_every=2)
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    cached = generate(module, params, prompt, steps=4)
    reference = full_forward_greedy(module, params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(cached), np.asarray(reference))


def test_zero_steps_raises(prompt):
    module = gpt2_tiny(dtype='float32')
    with pytest.raises(ValueError):
        generate(module, {}, prompt, steps=0)


def test_repeat_call_reuses_compiled_program(prompt):
    import importlib
    generate_module = importlib.import_module('tpusystem.train.generate')
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    generate(module, params, prompt, steps=2)
    before = generate_module._compiled.cache_info().hits
    generate(module, params, prompt, steps=2)
    assert generate_module._compiled.cache_info().hits == before + 1


def test_decode_clone_strips_training_settings(prompt):
    """flash attention / dropout / fused-loss output must not leak into the
    decode clone — generate works straight off a training-configured module."""
    module = gpt2_tiny(dtype='float32', attention='flash', dropout=0.1,
                       return_features=True)
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    out = generate(module, params, prompt, steps=2)
    assert out.shape == (2, 9)


@pytest.mark.slow
def test_long_prompt_prefill_uses_flash_and_matches_xla(monkeypatch):
    """Prompts >= 512 tokens prefill through the flash kernel (O(seq)
    memory) instead of building the O(seq^2) einsum scores tensor — and
    the prefill logits are unchanged."""
    from tpusystem.ops.pallas import flash as flash_module

    module = gpt2_tiny(dtype='float32', max_seq=1024)
    long_prompt = jnp.asarray(
        np.random.default_rng(4).integers(0, 256, (1, 512)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), long_prompt)['params']

    calls = []
    real_flash = flash_module.flash_attention

    def counting_flash(*args, **kwargs):
        calls.append(args[0].shape)
        return real_flash(*args, **kwargs)

    import tpusystem.ops.pallas.flash
    monkeypatch.setattr(tpusystem.ops.pallas.flash, 'flash_attention',
                        counting_flash)

    import dataclasses
    decoder = dataclasses.replace(module, decode=True)
    logits, _ = decoder.apply({'params': params}, long_prompt,
                              mutable=['cache'])
    assert len(calls) == module.layers, calls      # every layer's prefill
    reference = module.apply({'params': params}, long_prompt)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(reference),
                               atol=2e-4)


@pytest.mark.slow
def test_speculative_decode_equals_greedy_regardless_of_draft():
    """The speculative output must be EXACTLY the target's greedy decode —
    the draft only affects speed. Pinned with a random-weight draft (worst
    case: near-zero acceptance) and with the target itself as draft (best
    case: full acceptance), across speculate widths."""
    from tpusystem.train import generate, speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=128)
    draft = gpt2_tiny(dtype='float32', layers=1, dim=32, heads=2, max_seq=128)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), tokens)['params']
    draft_params = draft.init(jax.random.PRNGKey(9), tokens)['params']

    reference = np.asarray(generate(target, params, tokens, steps=24))
    for speculate in (1, 3, 5):
        out = speculative_generate(
            target, params, tokens, steps=24, draft_module=draft,
            draft_params=draft_params, speculate=speculate)
        np.testing.assert_array_equal(np.asarray(out), reference)

    # perfect draft: the target drafting for itself accepts everything
    out = speculative_generate(
        target, params, tokens, steps=24, draft_module=target,
        draft_params=params, speculate=4)
    np.testing.assert_array_equal(np.asarray(out), reference)


def test_speculative_decode_validates_capacity_and_args():
    from tpusystem.train import speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=32)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), tokens)['params']
    with pytest.raises(ValueError, match='capacity'):
        speculative_generate(target, params, tokens, steps=16,
                             draft_module=target, draft_params=params,
                             speculate=4)
    with pytest.raises(ValueError, match='speculate'):
        speculative_generate(target, params, tokens, steps=4,
                             draft_module=target, draft_params=params,
                             speculate=0)


@pytest.mark.slow
def test_speculative_decode_llama_rotary_positions():
    """Cursor rewind must also restore Llama's rotary positions (read from
    the per-layer cache index)."""
    from tpusystem.train import generate, speculative_generate
    target = llama_tiny(dtype='float32', max_seq=128)
    draft = llama_tiny(dtype='float32', layers=1, ffn_dim=64, max_seq=128)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 256, (2, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(1), tokens)['params']
    draft_params = draft.init(jax.random.PRNGKey(7), tokens)['params']
    reference = np.asarray(generate(target, params, tokens, steps=20))
    out = speculative_generate(target, params, tokens, steps=20,
                               draft_module=draft, draft_params=draft_params,
                               speculate=3)
    np.testing.assert_array_equal(np.asarray(out), reference)


@pytest.mark.slow
def test_speculative_sampling_matches_target_distribution():
    """temperature>0: rejection-sampling acceptance keeps the OUTPUT
    DISTRIBUTION equal to the target's own sampling distribution even with
    a disagreeing random draft (Leviathan et al.) — checked empirically on
    per-position marginals over a small vocab. Seeds pinned: the empirical
    draws are deterministic, so the tolerance cannot flake."""
    from tpusystem.train import generate, speculative_generate
    target = gpt2_tiny(dtype='float32', vocab_size=32, layers=2, dim=32,
                       heads=2, max_seq=64)
    draft = gpt2_tiny(dtype='float32', vocab_size=32, layers=1, dim=16,
                      heads=2, max_seq=64)
    batch, prefix, steps = 4096, 4, 3
    prompt = jnp.tile(jnp.asarray([[3, 1, 4, 1]], jnp.int32), (batch, 1))
    params = target.init(jax.random.PRNGKey(0), prompt[:1])['params']
    draft_params = draft.init(jax.random.PRNGKey(5), prompt[:1])['params']

    reference = np.asarray(generate(
        target, params, prompt, steps=steps, temperature=1.0,
        rng=jax.random.PRNGKey(11)))
    speculative = np.asarray(speculative_generate(
        target, params, prompt, steps=steps, draft_module=draft,
        draft_params=draft_params, speculate=3, temperature=1.0,
        rng=jax.random.PRNGKey(17)))

    for position in range(prefix, prefix + steps):
        ref_hist = np.bincount(reference[:, position], minlength=32) / batch
        spec_hist = np.bincount(speculative[:, position], minlength=32) / batch
        distance = np.abs(ref_hist - spec_hist).sum()
        assert distance < 0.12, (position, distance)
        # the test has teeth: the distribution is genuinely spread out
        assert ref_hist.max() < 0.9


@pytest.mark.slow
@pytest.mark.parametrize('family', ['gpt2', 'llama'])
def test_generate_on_scanned_model_matches_unrolled(family):
    """Decode-mode KV caches ride nn.scan (variable_axes={'cache': 0}):
    generation from a scanned model must equal the unrolled model's
    token-for-token, given transplanted weights."""
    import jax
    from tpusystem.models import gpt2_tiny, llama_tiny
    if family == 'gpt2':
        unrolled = gpt2_tiny(layers=4, dtype='float32')
        scanned = gpt2_tiny(layers=4, scan_layers=True, dtype='float32')
        prefix, stacked_key = 'h_', 'hs'
    else:
        unrolled = llama_tiny(layers=4, dtype='float32')
        scanned = llama_tiny(layers=4, scan_layers=True, dtype='float32')
        prefix, stacked_key = 'layer_', 'blocks'
    prompt = jnp.asarray(
        np.random.default_rng(11).integers(0, 256, (2, 8)), jnp.int32)
    params = unrolled.init(jax.random.PRNGKey(3), prompt)['params']
    per_layer = [params[f'{prefix}{i}'] for i in range(4)]
    stacked = {k: v for k, v in params.items() if not k.startswith(prefix)}
    stacked[stacked_key] = jax.tree.map(
        lambda *leaves: jnp.stack(leaves), *per_layer)
    out_u = generate(unrolled, params, prompt, steps=6)
    out_s = generate(scanned, stacked, prompt, steps=6)
    np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_s))


@pytest.mark.slow
def test_speculative_decode_on_scanned_target():
    """Speculative decoding on a scan_layers target: per-row cache cursors
    live at a leading layer dim (variable_axes={'cache': 0}) and _rewind
    broadcasts the [batch] cursor into that shape — output must still be
    exactly the target's greedy decode."""
    from tpusystem.train import speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=128, layers=4,
                       scan_layers=True)
    draft = gpt2_tiny(dtype='float32', layers=1, dim=32, heads=2,
                      max_seq=128)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 256, (2, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(5), tokens)['params']
    draft_params = draft.init(jax.random.PRNGKey(6), tokens)['params']
    reference = np.asarray(generate(target, params, tokens, steps=16))
    out = speculative_generate(
        target, params, tokens, steps=16, draft_module=draft,
        draft_params=draft_params, speculate=3)
    np.testing.assert_array_equal(np.asarray(out), reference)


def test_stream_dtype_auto_matches_f32_streaming_exactly():
    """For a bf16-compute model, pre-casting f32 matrix masters to bf16
    (stream_dtype='auto') must produce bit-identical generations to
    streaming the f32 masters: the model casts weights to bf16 at every
    use anyway, so only the HBM bytes change (the decode bandwidth
    optimization)."""
    module = gpt2_tiny(dtype='bfloat16')
    prompt = jnp.asarray(
        np.random.default_rng(23).integers(0, 256, (2, 8)), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    auto = generate(module, params, prompt, steps=12)
    f32 = generate(module, params, prompt, steps=12, stream_dtype='float32')
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(f32))


def test_stream_dtype_bfloat16_matches_auto_token_exact(prompt):
    """'bfloat16' on a bf16-compute model is the identical program to
    'auto' (both pre-cast the f32 matrix masters to bf16) — token-exact;
    on an f32-compute model it bf16-rounds the weights but still decodes
    in-vocab tokens."""
    module = gpt2_tiny(dtype='bfloat16')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    auto = generate(module, params, prompt, steps=10)
    forced = generate(module, params, prompt, steps=10,
                      stream_dtype='bfloat16')
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))

    f32_module = gpt2_tiny(dtype='float32')
    f32_params = f32_module.init(jax.random.PRNGKey(0), prompt)['params']
    out = np.asarray(generate(f32_module, f32_params, prompt, steps=6,
                              stream_dtype='bfloat16'))
    assert ((out >= 0) & (out < f32_module.vocab_size)).all()


def test_stream_dtype_unknown_raises_enumerating_the_valid_set(prompt):
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    with pytest.raises(ValueError) as excinfo:
        generate(module, params, prompt, steps=2, stream_dtype='int4')
    for mode in ('auto', 'float32', 'bfloat16', 'int8', 'fp8'):
        assert mode in str(excinfo.value)


def test_quantizer_cache_reuses_compiled_program(prompt):
    """The caster-cache regression pin, quantize flavored: _quantizer must
    be one cached jitted program per mode — an uncached jit would retrace
    the whole-tree quantization every generate() call (the round-5 8x
    decode slowdown)."""
    import importlib
    generate_module = importlib.import_module('tpusystem.train.generate')
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    generate(module, params, prompt, steps=2, stream_dtype='int8')
    before = generate_module._quantizer.cache_info().hits
    generate(module, params, prompt, steps=2, stream_dtype='int8')
    assert generate_module._quantizer.cache_info().hits == before + 1


def test_int8_streaming_bounded_logit_divergence_and_finite_decode(prompt):
    """int8 weight streaming is lossy but bounded: the dequantized tree's
    logits stay within a small absolute band of the master tree's, and
    greedy decode emits finite in-vocab tokens."""
    from tpusystem.ops.precision import dequantize_streamed, quantize_streamed
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    exact = module.apply({'params': params}, prompt)
    quantized = dequantize_streamed(quantize_streamed(params, 'int8'))
    approximate = module.apply({'params': quantized}, prompt)
    divergence = float(jnp.max(jnp.abs(exact - approximate)))
    assert np.isfinite(np.asarray(approximate)).all()
    assert 0.0 < divergence < 0.5, divergence   # lossy, but bounded

    out = np.asarray(generate(module, params, prompt, steps=8,
                              stream_dtype='int8'))
    assert ((out >= 0) & (out < module.vocab_size)).all()


def test_fp8_streaming_bounded_divergence_or_clear_gate(prompt):
    """Where the jaxlib supports float8_e4m3fn the fp8 stream decodes
    finite in-vocab tokens with bounded logit divergence; elsewhere the
    capability probe's reason surfaces in the ValueError."""
    from tpusystem.ops.precision import (dequantize_streamed,
                                         fp8_unsupported_reason,
                                         quantize_streamed)
    module = gpt2_tiny(dtype='float32')
    params = module.init(jax.random.PRNGKey(0), prompt)['params']
    reason = fp8_unsupported_reason()
    if reason is not None:
        with pytest.raises(ValueError, match='fp8'):
            generate(module, params, prompt, steps=2, stream_dtype='fp8')
        return
    exact = module.apply({'params': params}, prompt)
    quantized = dequantize_streamed(quantize_streamed(params, 'fp8'))
    approximate = module.apply({'params': quantized}, prompt)
    assert float(jnp.max(jnp.abs(exact - approximate))) < 0.5
    out = np.asarray(generate(module, params, prompt, steps=6,
                              stream_dtype='fp8'))
    assert ((out >= 0) & (out < module.vocab_size)).all()


@pytest.mark.slow
def test_batched_speculative_matches_batch1_trajectories_row_wise():
    """The batched verify forward amortizes one weight pass across the
    whole batch; per-row acceptance bookkeeping must reproduce each
    row's batch-1 trajectory exactly — a batch of prompts decodes to the
    same tokens as each prompt alone."""
    from tpusystem.train import speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=128)
    draft = gpt2_tiny(dtype='float32', layers=1, dim=32, heads=2,
                      max_seq=128)
    prompts = jnp.asarray(
        np.random.default_rng(31).integers(0, 256, (3, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), prompts)['params']
    draft_params = draft.init(jax.random.PRNGKey(9), prompts)['params']
    batched = np.asarray(speculative_generate(
        target, params, prompts, steps=16, draft_module=draft,
        draft_params=draft_params, speculate=3))
    for row in range(prompts.shape[0]):
        alone = np.asarray(speculative_generate(
            target, params, prompts[row:row + 1], steps=16,
            draft_module=draft, draft_params=draft_params, speculate=3))
        np.testing.assert_array_equal(batched[row:row + 1], alone,
                                      err_msg=f'row {row}')


@pytest.mark.slow
def test_tree_speculative_verify_equals_greedy():
    """Token-tree verify (tree_fanout=F): F draft branches per sequence
    verified as extra batch rows in one target forward — output must
    still be EXACTLY the target's greedy decode, for any fanout and any
    draft quality (including the full-acceptance self-draft)."""
    from tpusystem.train import generate, speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=128)
    draft = gpt2_tiny(dtype='float32', layers=1, dim=32, heads=2,
                      max_seq=128)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), tokens)['params']
    draft_params = draft.init(jax.random.PRNGKey(9), tokens)['params']
    reference = np.asarray(generate(target, params, tokens, steps=20))
    for fanout in (2, 3):
        out = speculative_generate(
            target, params, tokens, steps=20, draft_module=draft,
            draft_params=draft_params, speculate=3, tree_fanout=fanout)
        np.testing.assert_array_equal(np.asarray(out), reference,
                                      err_msg=f'fanout {fanout}')
    out = speculative_generate(
        target, params, tokens, steps=20, draft_module=target,
        draft_params=params, speculate=4, tree_fanout=2)
    np.testing.assert_array_equal(np.asarray(out), reference)


def test_tree_speculative_validates_args():
    from tpusystem.train import speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=64)
    tokens = jnp.zeros((1, 4), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), tokens)['params']
    with pytest.raises(ValueError, match='tree_fanout'):
        speculative_generate(target, params, tokens, steps=4,
                             draft_module=target, draft_params=params,
                             speculate=2, tree_fanout=0)
    with pytest.raises(ValueError, match='greedy'):
        speculative_generate(target, params, tokens, steps=4,
                             draft_module=target, draft_params=params,
                             speculate=2, tree_fanout=2, temperature=1.0,
                             rng=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match='vocab'):
        speculative_generate(target, params, tokens, steps=4,
                             draft_module=target, draft_params=params,
                             speculate=2, tree_fanout=1000)


@pytest.mark.slow
def test_speculative_quantized_streaming_decodes_in_vocab():
    """stream_dtype='int8' applies to BOTH trees of the speculative path
    (the verify forward streams narrow bytes too) — output stays
    finite/in-vocab with per-row bookkeeping intact."""
    from tpusystem.train import speculative_generate
    target = gpt2_tiny(dtype='float32', max_seq=128)
    draft = gpt2_tiny(dtype='float32', layers=1, dim=32, heads=2,
                      max_seq=128)
    tokens = jnp.asarray(
        np.random.default_rng(13).integers(0, 256, (2, 8)), jnp.int32)
    params = target.init(jax.random.PRNGKey(0), tokens)['params']
    draft_params = draft.init(jax.random.PRNGKey(9), tokens)['params']
    out = np.asarray(speculative_generate(
        target, params, tokens, steps=12, draft_module=draft,
        draft_params=draft_params, speculate=3, stream_dtype='int8'))
    assert ((out >= 0) & (out < target.vocab_size)).all()
    np.testing.assert_array_equal(out[:, :8], np.asarray(tokens))


def test_cursor_authority_is_the_shared_module():
    """The speculative path and the serving engine must edit cache
    cursors through ONE implementation (tpusystem.train.cursors) — a
    private copy in either would let the two drift on which leaves count
    as cursors or how scanned stacks broadcast."""
    import importlib

    import tpusystem.serve.engine as serve_engine
    from tpusystem.train import cursors
    generate_module = importlib.import_module('tpusystem.train.generate')
    assert generate_module._rewind is cursors.rewind
    assert generate_module._gather_rows is cursors.gather_rows
    assert serve_engine.rewind is cursors.rewind


def test_cursors_rewind_and_gather_cover_scanned_and_flat_caches():
    """Unit pin of the shared authority: rewind broadcasts a [batch]
    cursor into flat AND layer-stacked cursor leaves (touching nothing
    else); gather_rows copies KV on the batch axis and cursors on the
    last axis; read_cursor returns the per-row cursor either way."""
    import jax.numpy as jnp

    from tpusystem.train import cursors
    flat = {'h_0': {'attn': {'index': jnp.array([3, 5], jnp.int32),
                             'key': jnp.arange(2 * 4 * 1 * 1, dtype=jnp.float32)
                             .reshape(2, 4, 1, 1)}},
            'position': jnp.array([3, 5], jnp.int32)}
    rewound = cursors.rewind(flat, jnp.array([1, 2], jnp.int32))
    np.testing.assert_array_equal(rewound['h_0']['attn']['index'], [1, 2])
    np.testing.assert_array_equal(rewound['position'], [1, 2])
    np.testing.assert_array_equal(rewound['h_0']['attn']['key'],
                                  flat['h_0']['attn']['key'])
    np.testing.assert_array_equal(cursors.read_cursor(flat), [3, 5])

    stacked = {'hs': {'attn': {'index': jnp.tile(
        jnp.array([[3, 5]], jnp.int32), (4, 1))}}}   # [layers, batch]
    rewound = cursors.rewind(stacked, jnp.array([7, 9], jnp.int32))
    assert rewound['hs']['attn']['index'].shape == (4, 2)
    np.testing.assert_array_equal(rewound['hs']['attn']['index'][2], [7, 9])
    np.testing.assert_array_equal(cursors.read_cursor(stacked), [3, 5])

    gathered = cursors.gather_rows(flat, jnp.array([1, 1], jnp.int32))
    np.testing.assert_array_equal(gathered['h_0']['attn']['index'], [5, 5])
    np.testing.assert_array_equal(gathered['h_0']['attn']['key'][0],
                                  flat['h_0']['attn']['key'][1])
    with pytest.raises(ValueError, match='index'):
        cursors.read_cursor({'h_0': {'attn': {'key': jnp.zeros((1,))}}})


@pytest.mark.slow
def test_bucketed_cache_attention_crosses_bucket_boundary():
    """max_seq 512 decode buckets cache reads at [256, 512]; a generation
    crossing the 256-token boundary must stay token-exact with the full
    re-forward reference (the switch picks a wider window mid-scan)."""
    module = gpt2_tiny(dtype='float32', max_seq=512)
    prompt = jnp.asarray(
        np.random.default_rng(29).integers(0, 256, (2, 250)), jnp.int32)
    params = module.init(jax.random.PRNGKey(1), prompt[:, :8])['params']
    decoded = generate(module, params, prompt, steps=20)   # 250 -> 270
    reference = full_forward_greedy(module, params, prompt, 20)
    np.testing.assert_array_equal(np.asarray(decoded), np.asarray(reference))
