"""A configuration of a second family is added by files alone: a family
module whose configuration keys are not GPT-2's, its reference, a
configuration cut in depth with a serving lever, traffic mixes, cells,
limits and a metric each, written as new files and appended entries
(``toy.add``) into the tiny root and into a copy of this checkout. Nothing
that was there changes, and every cell, new and old, finds its files and
its metrics. ``test_chipbench_family_runs.py`` drives the new cells."""

from __future__ import annotations

import pytest

from tests.chipbench_tests import tiny, toy


@pytest.fixture(scope='module', params=['tiny', 'checkout'])
def added(request, tmp_path_factory):
    """The root with the toy family's files beside what was there, and the
    digests of what ships in ``chipbench/`` taken before and after."""
    dest = tmp_path_factory.mktemp(f'chipbench-family-{request.param}')
    root = (tiny.build if request.param == 'tiny'
            else toy.fresh_checkout)(dest)
    shipped = tiny.digests(tiny.ROOT / 'chipbench')
    record = toy.add(root)
    record['shipped'] = (shipped, tiny.digests(tiny.ROOT / 'chipbench'))
    with toy.imported(root):
        yield record


def test_nothing_that_was_there_changed(added):
    toy.unchanged(added)
    before, after = added['shipped']
    assert after == before


def test_the_new_cells_find_their_files_and_their_cut_is_stated(added):
    from chipbench import families, harness
    toy.found(added)
    cell = harness.load_cell('toy-serve', added['root'])
    assert cell.config['depth'] == 2 and 'n_layer' not in cell.config
    assert cell.traffic['clients'] == 2
    tiny.check_cuts(cell.config, toy.ENTRY)
    module = families.of(cell.config)
    assert module.__name__ == 'chipbench.families.toyformer'
    assert module.reference.__name__ == 'chipbench.reference.toyformer'
    assert all(callable(getattr(module, name))
               for name in families.INTERFACE)
    for old in added['was']:
        per_layer = [m['name'] for m in harness.load_cell(
            old, added['root']).per_layer]
        assert 'step_mfu.toy' not in per_layer
        assert 'scope_share.head' not in per_layer


def test_step_mfu_reads_the_new_familys_count(added):
    from chipbench import families, flops, harness, trace_reduce
    cell = harness.load_cell('toy-train', added['root'])
    mix = cell.traffic
    records = {
        'config': cell.config, 'traffic': mix, 'chips': 1,
        'device_kind': 'TPU v5 lite', 'traced': {'steps': 4},
        'trace': trace_reduce.Trace({}, {}, [('chipbench.window', 1.0, 3.0)])}
    tokens = 4 * mix['batch'] * mix['seq']
    count = families.of(cell.config).train_ops_per_token(cell.config,
                                                         mix['seq'])
    as_gpt2 = flops.train_ops_per_token(
        dict(tiny.CONFIG, n_layer=cell.config['depth']), mix['seq'])
    assert count == 2 * as_gpt2
    assert harness.read_per_layer(cell, records, added['root']) == {
        'step_mfu.toy': {'value': pytest.approx(
            100.0 * tokens * count / (2.0 * 197e12)), 'unit': '%'}}
