"""The training driver end to end at a tiny size, sound and broken.

The look for a chip is steered from here (``tiny.steer``); the rest of a
run is the real one: the program's own compiler pipeline and ``train``
handler, the reference, the comparison and the result line."""

from __future__ import annotations

import pytest

from chipbench.drivers import train
from tests.chipbench_tests import tiny


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return tiny.build(tmp_path_factory.mktemp('chipbench-train'))


@pytest.fixture(scope='module')
def sound(root):
    with pytest.MonkeyPatch.context() as patch:
        tiny.steer(patch)
        return tiny.run_cell(root, 'tiny-train', seed=2 ** 31 + 11)


def test_sound_run_is_correct_and_reports_the_cells_metrics(sound):
    assert sound['correct'] is True
    assert sound['attempted'] >= 1 and sound['failed'] == 0
    assert set(sound['metrics']) == {'train_tokens_per_s', 'setup_s'}
    assert all(metric['value'] > 0 for metric in sound['metrics'].values())
    assert list(sound)[-1] == 'compared'
    assert set(sound['compared']) == {'loss_gap', 'moment_gap', 'update_gap'}
    for number in sound['compared'].values():
        assert 0 <= number['value'] <= number['limit']


def _unchanged(lm):
    """A step that returns its state unchanged: the losses are the real
    ones, the update is thrown away."""
    import jax
    import jax.numpy as jnp

    def fit_many(self, stack):
        spare = jax.tree.map(jnp.copy, self.state)
        _, losses = self._train_many(spare, stack, stack)
        return losses
    lm.LanguageModel.fit_many = fit_many


def _half_batch(lm):
    """Half of the batch left out, the mean taken over the rest: the
    second half of every batch is overwritten with the first."""
    real = lm.LanguageModel.fit_many

    def fit_many(self, stack):
        half = stack.shape[1] // 2
        return real(self, stack.at[:, half:].set(stack[:, :half]))
    lm.LanguageModel.fit_many = fit_many


@pytest.mark.parametrize('fault', [_unchanged, _half_batch])
def test_a_broken_step_comes_out_as_not_correct(root, monkeypatch, fault):
    tiny.steer(monkeypatch)
    real = train.load_lm

    def broken():
        lm = real()
        fault(lm)
        return lm
    monkeypatch.setattr(train, 'load_lm', broken)
    result = tiny.run_cell(root, 'tiny-train', seed=2 ** 31 + 11)
    assert result['correct'] is False
    assert any(not number['value'] <= number['limit']
               for number in result['compared'].values())


def test_the_fp8_control_fails_the_tiny_cells_limits(root):
    """The control of "How correct is decided": the reference put in the
    program's place, computed in fp8, must not pass."""
    import numpy as np
    from chipbench import check, families, harness, traffic
    cell = harness.load_cell('tiny-train', root)
    family = families.of(cell.config)
    mix = cell.traffic
    rows = traffic.bigram_tokens(5, samples=mix['batch'] * 2, seq=mix['seq'],
                                 vocab=cell.config['vocab_size'])
    fed = list(np.split(rows, 2))
    reference = family.reference_training(cell.config, 5, fed)
    lowered = family.reference_training(
        cell.config, 5, fed,
        precision=cell.config['reference']['control']['precision'])
    numbers, _ = check.compare_training(lowered, reference)
    compared = [(name, numbers[name], cell.limits[name]['limit'])
                for name in cell.limits]
    assert harness.judge(compared, failed=0) is False
    same, _ = check.compare_training(reference, reference)
    assert harness.judge([(name, same[name], cell.limits[name]['limit'])
                          for name in cell.limits], failed=0) is True
