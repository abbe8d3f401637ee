"""The next PR, rehearsed on this checkout's own benchmark: in a copy of
``BENCHMARK.json``, ``chipbench/`` and ``tests/chipbench_tests/`` a cell is
added the way a later PR has to add it — new files and appended entries,
no edit to a file that is there — and the benchmark's own tests have to
take it: what was there stands, every cell finds its metrics, the tiny
root the other tests build is the same, and the copy's own test files pass
when run from the copy.

Three such PRs: a cell of a configuration and a traffic mix that are there
(an entry, its name in four ``workloads`` lists, its limits: ISSUE 27's
experiment, which ended in ``KeyError`` in ``tiny.build``); a serving cell
of a new family (``toy.add``); the same with a training cell beside it.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from tests.chipbench_tests import tiny, toy

# run from the copy, whole: they read the copy's BENCHMARK.json and files,
# and test_chipbench_family.py builds its tiny root from them
OWN_TESTS = ['test_chipbench_static.py', 'test_chipbench_kv_read_roofline.py',
             'test_chipbench_family.py']


def a_cell_of_what_is_there(root: pathlib.Path) -> dict:
    """``serve-again`` = gpt2-large x closed32-chat once more: nothing new
    but the entry, the name where its metrics are listed, and its limits."""
    name, like = 'serve-again', 'serve-large-closed32'
    joins = ('serve_tokens_per_s', 'ttft_p50_ms', 'itl_p95_ms',
             'kv_read_roofline')
    record = toy.snapshot(root)
    bench = json.loads(json.dumps(record['bench_before']))
    entry = next(w for w in bench['workloads'] if w['name'] == like)
    bench['workloads'].append({**entry, 'name': name})
    toy.join(bench, name, joins)
    limits = f'chipbench/limits/{name}.json'
    shutil.copy(root / 'chipbench' / 'limits' / f'{like}.json', root / limits)
    was = record['was'][like]
    return toy.written(record, bench, {limits}, {name: {
        'config': was.config, 'traffic': was.traffic, 'limits': was.limits,
        'metrics': {'setup_s', *joins}}})


PRS = {'entries-alone': a_cell_of_what_is_there,
       'one-cell': lambda root: toy.add(root, ('toy-serve',)),
       'two-cells': lambda root: toy.add(root, ('toy-serve', 'toy-train'))}


@pytest.fixture(scope='module', params=list(PRS))
def rehearsed(request, tmp_path_factory):
    base = tmp_path_factory.mktemp(f'chipbench-rehearsal-{request.param}')
    record = PRS[request.param](toy.fresh_checkout(base / 'checkout'))
    record['base'] = base
    return record


def test_what_was_there_stands_and_every_cell_finds_its_metrics(rehearsed):
    toy.unchanged(rehearsed)
    toy.found(rehearsed)


def test_the_tiny_root_is_what_it_is_from_the_checkout(rehearsed):
    """``tiny.build`` follows ``BENCHMARK.json``: a cell that stands for no
    tiny cell, and a metric only such cells report, leave no trace."""
    from_copy = tiny.build(rehearsed['base'] / 'tiny-of-copy',
                           source=rehearsed['root'])
    from_here = tiny.build(rehearsed['base'] / 'tiny-of-checkout')
    assert tiny.digests(from_copy) == tiny.digests(from_here)
    assert (from_copy / 'BENCHMARK.json').read_bytes() == (
        from_here / 'BENCHMARK.json').read_bytes()


def test_the_copys_own_tests_pass_when_run_from_the_copy(rehearsed):
    done = subprocess.run(
        [sys.executable, '-m', 'pytest', '-v', '-p', 'no:cacheprovider',
         f'--basetemp={rehearsed["base"] / "pytest"}']
        + [f'tests/chipbench_tests/{name}' for name in OWN_TESTS],
        cwd=rehearsed['root'], capture_output=True, text=True, timeout=300)
    said = done.stdout[-6000:] + done.stderr[-2000:]
    assert done.returncode == 0, said
    assert ' passed' in said and ' failed' not in said
    # what ran is the copy's: each new cell was a case of the static tests
    for name in rehearsed['expects']:
        assert (f'test_every_cell_finds_its_files_and_reports_enough[{name}] '
                'PASSED') in done.stdout, said
