"""The plain reference against the program's ``GPT2`` at a tiny size, in
float32, on the benchmark's own seeded weights; and the controls' levers."""

from __future__ import annotations

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, weights
from chipbench.families import gpt2 as family
from chipbench.reference import gpt2
from tests.chipbench_tests import tiny

CONFIG = tiny.CONFIG
MODEL = dict(heads=CONFIG['n_head'], eps=CONFIG['as_run']['layer_norm_epsilon'])


def leaf_digests(tree: dict) -> dict:
    """Sixteen hex digits of sha256 over each leaf's type, shape and bytes."""
    out = {}
    for name, leaf in weights.flatten(tree).items():
        leaf = np.asarray(leaf)
        out[name] = hashlib.sha256(
            str(leaf.dtype).encode() + str(leaf.shape).encode()
            + leaf.tobytes()).hexdigest()[:16]
    return out


PINNED = json.loads((pathlib.Path(__file__).parent
                     / 'weight_digests.json').read_text())


@pytest.mark.parametrize('case', sorted(PINNED))
def test_every_seeded_leaf_is_bit_for_bit_what_pr_25_made(case):
    """``weight_digests.json`` was recorded from ``chipbench/weights.py::
    make`` at PR 25's tree, before the leaf tables moved into the family:
    the same configuration and seed give the same bits, for the program's
    tree and the reference's."""
    seed, layout = case.split('/')
    made = family.make(CONFIG, int(seed), stacked=layout == 'stacked')
    assert leaf_digests(made) == PINNED[case]


@pytest.fixture(scope='module')
def tokens():
    return np.random.default_rng(0).integers(
        0, CONFIG['vocab_size'], size=(3, 48)).astype(np.int32)


def test_reference_logits_match_the_programs_gpt2_in_float32(tokens):
    from tpusystem.models import GPT2
    module = GPT2(vocab_size=CONFIG['as_run']['vocab_rows'],
                  layers=CONFIG['n_layer'], dim=CONFIG['n_embd'],
                  heads=CONFIG['n_head'], max_seq=CONFIG['n_positions'],
                  dropout=0.0, dtype='float32')
    with jax.default_matmul_precision('highest'):
        got = module.apply({'params': family.make(CONFIG, 11)}, tokens)
    want = gpt2.logits(family.make(CONFIG, 11, stacked=True),
                       jnp.asarray(tokens), **MODEL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_seeded_weights_repeat_and_both_layouts_hold_the_same_numbers():
    flat = weights.flatten(family.make(CONFIG, 2 ** 31 + 3))
    again = weights.flatten(family.make(CONFIG, 2 ** 31 + 3))
    other = weights.flatten(family.make(CONFIG, 4))
    stacked = weights.flatten(family.make(CONFIG, 2 ** 31 + 3, stacked=True))
    assert all((flat[k] == again[k]).all() for k in flat)
    assert any((flat[k] != other[k]).any() for k in flat)
    np.testing.assert_array_equal(flat['h_1/fc/kernel'],
                                  stacked['h/fc/kernel'][1])
    assert abs(float(flat['ln_f/scale'].mean()) - 1.0) < 0.02
    unrolled = jax.device_get(family.norms(family.make(CONFIG, 5)))
    kept = jax.device_get(family.stacked_norms(
        family.make(CONFIG, 5, stacked=True)))
    assert unrolled.keys() == kept.keys()
    assert 'h_0/attn/qkv/bias.k' in kept        # the fused leaf is three
    for name in kept:
        assert float(unrolled[name]) == pytest.approx(float(kept[name]),
                                                      rel=1e-6)


def test_reference_step_is_optax_adamw_with_a_clipped_gradient(tokens):
    import optax
    stated = CONFIG['as_run']['optimizer']
    batch = jnp.asarray(tokens[:2])
    params = family.make(CONFIG, 3, stacked=True)
    mean_loss = lambda p: (lambda s, n: s / n)(
        *gpt2.loss_sum(p, batch, **MODEL))
    transform = optax.chain(
        optax.clip_by_global_norm(stated['grad_clip']),
        optax.adamw(stated['lr'], b1=stated['b1'], b2=stated['b2'],
                    eps=stated['eps'], weight_decay=stated['weight_decay']))
    state = transform.init(params)
    want = params
    for _ in range(2):
        grads = jax.grad(mean_loss)(want)
        updates, state = transform.update(grads, state, want)
        want = optax.apply_updates(want, updates)
    got = family.make(CONFIG, 3, stacked=True)
    mu = jax.tree.map(jnp.zeros_like, got)
    nu = jax.tree.map(jnp.zeros_like, got)
    count = jnp.zeros((), jnp.int32)
    for _ in range(2):
        got, mu, nu, count, _ = gpt2.train_step(
            got, mu, nu, count, batch, precision='float32', block_rows=1,
            lr=stated['lr'], b1=stated['b1'], b2=stated['b2'],
            adam_eps=stated['eps'], weight_decay=stated['weight_decay'],
            grad_clip=stated['grad_clip'], **MODEL)
    start = weights.flatten(params)
    for name, leaf in weights.flatten(got).items():
        moved = np.asarray(leaf) - np.asarray(start[name])
        wanted = np.asarray(weights.flatten(want)[name]) - np.asarray(
            start[name])
        # Adam normalises each element, so one whose gradient is nought to
        # rounding may step either way: compare the leaf, not the element
        assert np.linalg.norm(moved - wanted) <= 2e-2 * np.linalg.norm(
            wanted), name


@pytest.mark.parametrize('precision, floor', [('bfloat16', 1e-8),
                                              ('fp8', 1e-5)])
def test_lower_precisions_move_the_loss(tokens, precision, floor):
    params = family.make(CONFIG, 7, stacked=True)
    exact, count = gpt2.loss_sum(params, jnp.asarray(tokens), **MODEL)
    lowered, _ = gpt2.loss_sum(params, jnp.asarray(tokens),
                               precision=precision, **MODEL)
    assert abs(float(lowered - exact)) / float(exact) > floor
    assert np.isfinite(float(lowered))


def test_int4_matrices_read_wider_than_int8_and_leave_the_table_alone():
    params = family.make(CONFIG, 9, stacked=True)
    narrow = gpt2.quantize_matrices(params, 4)
    np.testing.assert_array_equal(narrow['wte']['embedding'],
                                  params['wte']['embedding'])
    np.testing.assert_array_equal(narrow['h']['fc']['bias'],
                                  params['h']['fc']['bias'])
    assert len(np.unique(np.asarray(narrow['h']['fc']['kernel'][0, :, 0]))) <= 15
    sample = [(list(range(5, 25)), list(range(30, 42)))]
    gap4, covered = family.served_gap(CONFIG, 9, sample, control_bits=4)
    gap8, _ = family.served_gap(CONFIG, 9, sample, control_bits=8)
    assert covered == 12 and gap4 >= gap8 >= 0.0


def test_served_gap_is_nought_for_the_references_own_greedy_tokens():
    params = family.make(CONFIG, 13, stacked=True)
    prompt = list(range(3, 20))
    ids = list(prompt)
    for _ in range(6):
        scores = gpt2.logits(params, jnp.asarray([ids]), **MODEL)
        ids.append(int(jnp.argmax(scores[0, -1])))
    gap, covered = family.served_gap(CONFIG, 13, [(prompt, ids[len(prompt):])])
    assert covered == 6 and gap == 0.0
    altered = ids[len(prompt):]
    altered[2] = (altered[2] + 1) % CONFIG['vocab_size']
    wrong, _ = family.served_gap(CONFIG, 13, [(prompt, altered)])
    assert wrong > 0.0


def test_gap_arithmetic_measures_against_the_median_leaf():
    reference = {'a': 10.0, 'b': 1.0, 'c': 1e-6}
    program = {'a': 11.0, 'b': 1.0, 'c': 2e-6}
    gaps = check.relative_gaps(program, reference)
    assert gaps == {'a': pytest.approx(0.1), 'b': 0.0,
                    'c': pytest.approx(1e-6)}
    assert check.worst(gaps) == (pytest.approx(0.1), 'a')
    assert check.worst({'a': 0.5, 'b': float('nan')})[1] == 'b'
    sample = check.sample_requests(5, [([1] * n, [2] * 3) for n in
                                       (4, 9, 30, 7, 8)], 3)
    assert len(sample) == 3 and len(sample[0][0]) == 30
    assert sample == check.sample_requests(5, [([1] * n, [2] * 3) for n in
                                               (4, 9, 30, 7, 8)], 3)
