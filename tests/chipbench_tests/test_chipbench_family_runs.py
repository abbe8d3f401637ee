"""The cells of a family added by files alone (``toy.add``, as
``test_chipbench_family.py`` checks it) run through the real harness and
both drivers, and a fault planted in the new family's module comes out as
not correct."""

from __future__ import annotations

import pytest

from tests.chipbench_tests import tiny, toy


@pytest.fixture(scope='module')
def added(tmp_path_factory):
    root = tiny.build(tmp_path_factory.mktemp('chipbench-family-runs'))
    record = toy.add(root)
    with toy.imported(root):
        yield record


def test_the_serving_run_is_correct_and_the_lever_reached_the_engine(
        added, capsys):
    result = tiny.run_cell(added['root'], 'toy-serve', seed=2 ** 31 + 41)
    assert result['correct'] is True and result['attempted'] > 2
    assert result['failed'] == 0
    assert "resolved {'stream_dtype': 'float32'" in capsys.readouterr().err


def test_a_lever_the_engine_did_not_get_is_refused(added, monkeypatch):
    """What the configuration states is what has to run: were the lever
    dropped on the way, the engine would resolve the platform's default."""
    from tpusystem.serve import InferenceService
    real = InferenceService.__init__

    def dropped(self, *args, stream_dtype=None, **kwargs):
        real(self, *args, **kwargs)
    monkeypatch.setattr(InferenceService, '__init__', dropped)
    with pytest.raises(SystemExit, match='stream_dtype'):
        tiny.run_cell(added['root'], 'toy-serve', seed=5)


def test_the_training_run_is_correct(added):
    result = tiny.run_cell(added['root'], 'toy-train', seed=2 ** 31 + 42)
    assert result['correct'] is True and result['failed'] == 0
    assert set(result['compared']) == {'loss_gap', 'moment_gap',
                                       'update_gap'}


@pytest.mark.parametrize('cell', ['toy-serve', 'toy-train'])
def test_a_fault_in_the_new_familys_module_is_not_correct(added, monkeypatch,
                                                          cell):
    """The program is handed another seed's weights than the reference
    makes for itself: every number compared is the toy family's to give."""
    from chipbench import families
    module = families.of(toy.CONFIG)
    real = module.make

    def another_seed(config, seed, *, stacked=False):
        return real(config, seed if stacked else seed + 1, stacked=stacked)
    monkeypatch.setattr(module, 'make', another_seed)
    result = tiny.run_cell(added['root'], cell, seed=2 ** 31 + 41)
    assert result['correct'] is False
    assert any(not number['value'] <= number['limit']
               for number in result['compared'].values())
