"""The Ouro family (``models/ouro.py``): a stack run several times a token,
with a cache for every pass of every layer, served through
``InferenceService`` and held against the benchmark's plain reference
(``chipbench/reference/ouro.py``) at ``ouro_tiny``'s size on the CPU.

Every comparison is in float32 with every matrix product at ``highest``
precision (the module's ``dtype`` is float32 here), so the program and the
reference differ by float32 rounding alone: logits agree to 1e-5 (they are
~0.2 in size), and a served token is the reference's best up to a float32
near-tie (``logit_gap_max`` 2e-3, the tiny benchmark cell's limit). Each
planted fault — a pass left out, passes sharing one cache, a pass not closed
by the final norm — moves the served tokens off the reference's by a gap
over 0.05, twenty-five times that limit and more."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.families import ouro as family
from chipbench.reference import ouro as reference
from tests.chipbench_tests import ouro as tiny_ouro
from tpusystem.models import ouro
from tpusystem.models.ouro import Ouro, ouro_tiny
from tpusystem.observe.trace import Tracer
from tpusystem.serve import InferenceService, Request

CONFIG = tiny_ouro.tiny_config()
SEED = 5
LIMIT = 2e-3          # float32 near-ties only (module docstring)


def module(**overrides) -> Ouro:
    return dataclasses.replace(family.serve_module(CONFIG), **overrides)


def serve(model: Ouro, prompts: dict, max_new: int = 7, tracer=None):
    """Serve every prompt through ``InferenceService`` over the seeded
    weights; returns ``({id: tokens}, the service)``."""
    service = InferenceService(model, family.make(CONFIG, SEED), rows=3,
                               block_size=16, stream_dtype='float32',
                               tracer=tracer)
    for name, prompt in prompts.items():
        service.service.handle('submit', Request(name, prompt, max_new))
    service.run_until_idle()
    return ({name: done.tokens for name, done
             in service.scheduler.results.items()}, service)


PROMPTS = {f'r{index}': np.random.default_rng(10 + index).integers(
    0, 256, 9 + 13 * index).tolist() for index in range(4)}


def gap(served: dict) -> float:
    widest, covered = family.served_gap(
        CONFIG, SEED, [(PROMPTS[name], tokens)
                       for name, tokens in served.items()])
    assert covered == sum(len(tokens) for tokens in served.values())
    return widest


def test_the_tiny_preset_loops_and_the_family_builds_the_same_module():
    model = ouro_tiny()
    assert (model.passes, model.layers, model.heads, model.vocab_size) == (
        3, 2, 4, 256)
    built = family.serve_module(CONFIG)
    assert (built.passes, built.layers, built.dim, built.heads,
            built.kv_heads, built.head_dim, built.ffn_dim, built.max_seq,
            built.dtype) == (3, 2, 64, 4, 4, 16, 96, 128, 'float32')


def test_the_reference_and_the_module_agree_on_the_full_forward():
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, 40))
    model = family.serve_module(CONFIG)
    with jax.default_matmul_precision('highest'):
        got = model.apply({'params': family.make(CONFIG, SEED)},
                          tokens[None])[0]
    want = reference.logits([tokens], family.reference_leaves(CONFIG, SEED),
                            family.reference_model(CONFIG))[0]
    assert float(jnp.std(want)) > 0.05           # the logits carry signal
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_prefill_then_decode_through_the_pool_is_the_references_forward():
    served, service = serve(family.serve_module(CONFIG), PROMPTS)
    assert all(len(tokens) == 7 for tokens in served.values())
    assert gap(served) <= LIMIT
    engine = service.engine
    assert engine.decode_impl == 'flax' and engine.trace_count == 1
    # a cache for every pass of every layer, each with its own table and
    # cursor, all alike
    leaves = {jax.tree_util.keystr(path): leaf for path, leaf
              in jax.tree_util.tree_leaves_with_path(engine._cache)}
    for name in ('key', 'value', 'table', 'index'):
        found = [path for path in leaves if path.endswith(f"['{name}']")]
        assert len(found) == 2 * 3, found
        assert {f"['layer_{layer}']['attn']['pass_{loop}']['{name}']"
                for layer in range(2) for loop in range(3)} == set(found)
    for name in ('table', 'index'):
        same = [np.asarray(leaf) for path, leaf in leaves.items()
                if path.endswith(f"['{name}']")]
        assert all((each == same[0]).all() for each in same)


def test_the_engine_says_how_the_stack_runs_and_the_tracer_marks_it():
    tracer = Tracer('serve')
    _, service = serve(family.serve_module(CONFIG),
                       {'r0': PROMPTS['r0']}, max_new=2, tracer=tracer)
    # 3 passes x 2 layers, a key and a value of 4 x 16 float32 each
    assert service.engine.recurrence == {
        'passes': 3, 'cache_layers': 6,
        'kv_bytes_per_position': 6 * 2 * 64 * 4}
    marked = [event for event in tracer.events()
              if event['name'] == 'recurrence']
    assert len(marked) == 1
    assert {key: marked[0]['args'][key] for key in service.engine.recurrence
            } == {'passes': 3, 'cache_layers': 6,
                  'kv_bytes_per_position': 3072}


def test_a_program_that_runs_one_pass_fewer_is_far_off():
    served, _ = serve(module(passes=2), PROMPTS)
    assert gap(served) > 25 * LIMIT


class SharedCache(ouro.LoopedAttention):
    """Every pass reads and writes pass 0's cache."""

    def __call__(self, hidden, loop):
        return super().__call__(hidden, 0)


class OnceFinalNorm(ouro.RMSNorm):
    """The final norm applied after the last pass only (it is called once a
    pass, ``passes`` times a trace)."""

    calls = [0]

    def __call__(self, hidden):
        if self.name == 'final_norm':
            self.calls[0] += 1
            if self.calls[0] % CONFIG['total_ut_steps']:
                return hidden
        return super().__call__(hidden)


@pytest.mark.parametrize('where, fault', [('LoopedAttention', SharedCache),
                                          ('RMSNorm', OnceFinalNorm)])
def test_a_planted_fault_in_the_loop_is_far_off(monkeypatch, where, fault):
    # the prefill programs are cached by the decode clone, which does not
    # see a class planted in the module: none may outlive the fault
    from tpusystem.serve.engine import _compiled_prefill
    monkeypatch.setattr(ouro, where, fault)
    _compiled_prefill.cache_clear()
    try:
        served, _ = serve(family.serve_module(CONFIG), PROMPTS)
    finally:
        _compiled_prefill.cache_clear()
    assert gap(served) > 25 * LIMIT


def test_a_threshold_below_one_is_refused_by_name():
    with pytest.raises(ValueError, match='early_exit_threshold=0.5') as raised:
        ouro_tiny(early_exit_threshold=0.5)
    assert 'different passes' in str(raised.value)
    assert 'never written' in str(raised.value)
    assert ouro.exit_reason(1.0) is None
    assert ouro_tiny(early_exit_threshold=1.0).early_exit_threshold == 1.0


def test_the_fused_chain_names_why_it_does_not_serve_a_looped_model():
    from tpusystem.train.decode_fused import fused_paged_reason
    from tpusystem.train.generate import _decoder
    reason = fused_paged_reason(_decoder(family.serve_module(CONFIG)))
    assert 'GPT2 family only' in reason and 'Ouro' in reason


def test_the_published_shapes_take_the_paged_read_kernel_on_a_tpu(
        monkeypatch):
    """16 query heads on 16 key/value heads of 128 (2048 lanes a row),
    blocks of 16 bfloat16 positions, 32 blocks a row: the plan tiles."""
    from tpusystem.ops import attention
    monkeypatch.setattr(attention, 'on_tpu', lambda: True)
    assert attention.paged_read(1, 16, 16, 128, 16, 32, jnp.bfloat16) == (
        'kernel', None)
