"""The benchmark a test file of this directory was written against.

``test_chipbench_deepseek_v2.py`` (PR 32) asserts that its configuration,
its cell and its five metrics are the *last* entries of ``BENCHMARK.json``
and that its cell is the only one those metrics list: true of the benchmark
as PR 32 left it, and false from the first entry a later PR appends — which
is the only place a later PR may put one, while no PR but a ``benchmark`` one
may edit that file. Until one makes those assertions relative, the module is
handed the benchmark it was written against: ``BENCHMARK.json`` as it is
today, cut off behind PR 32's own last entries, with the cells of later PRs
taken out of the ``workloads`` lists. What it then checks is what it meant
to: nothing was inserted before or among its entries, and what was there is
unchanged.
"""

import pytest

# module -> the last entry of each list as its PR left the benchmark
WRITTEN_AGAINST = {
    'test_chipbench_deepseek_v2': {'configs': 'deepseek-v2',
                                   'workloads': 'serve-dsv2-closed64',
                                   'per_layer': 'expert_imbalance'}}


def cut_off(bench: dict, last: dict) -> dict:
    """``bench`` with each list of ``last`` ending at the entry it names, and
    every metric's ``workloads`` holding only cells that are left."""
    cut = dict(bench)
    for group, name in last.items():
        names = [entry['name'] for entry in bench[group]]
        cut[group] = bench[group][:names.index(name) + 1]
    cells = {cell['name'] for cell in cut['workloads']}
    for group in ('end_to_end', 'per_layer'):
        cut[group] = [
            {**metric, 'workloads': [cell for cell in metric['workloads']
                                     if cell in cells]}
            if 'workloads' in metric else metric for metric in cut[group]]
    return cut


@pytest.fixture(autouse=True, scope='module')
def the_benchmark_the_module_was_written_against(request):
    last = WRITTEN_AGAINST.get(request.module.__name__.rpartition('.')[2])
    if last is None:
        yield
        return
    whole = request.module.BENCH
    request.module.BENCH = cut_off(whole, last)
    yield
    request.module.BENCH = whole
