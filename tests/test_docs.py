"""Docs-site consistency: mkdocs.yml nav targets exist and every
mkdocstrings directive names an importable module — so the CI docs job
(`mkdocs build --strict`) cannot fail on references this environment
can't check (mkdocs itself is not installed here) — and every file or
directory a page or a docstring cites is in the checkout."""

import functools
import importlib
import os
import pathlib
import re
import subprocess
import sys

import pytest
import yaml

REPO = pathlib.Path(__file__).parent.parent
DOCS = REPO / 'docs'


def _nav_files(node):
    if isinstance(node, str):
        yield node
    elif isinstance(node, list):
        for item in node:
            yield from _nav_files(item)
    elif isinstance(node, dict):
        for value in node.values():
            yield from _nav_files(value)


def test_mkdocs_nav_targets_exist():
    config = yaml.safe_load((REPO / 'mkdocs.yml').read_text())
    missing = [path for path in _nav_files(config['nav'])
               if not (DOCS / path).exists()]
    assert not missing, f'mkdocs.yml nav references missing pages: {missing}'


def test_api_pages_cover_every_module_and_import():
    directives = set()
    for page in (DOCS / 'api').glob('*.md'):
        directives.update(re.findall(r'^::: (\S+)$', page.read_text(), re.M))
    for module in sorted(directives):
        importlib.import_module(module)   # raises on a stale reference
    # every package module appears on exactly one API page
    modules = {
        str(p.relative_to(REPO)).removesuffix('.py').removesuffix('/__init__')
        .replace('/', '.')
        for p in (REPO / 'tpusystem').rglob('*.py')}
    assert modules == directives, (
        f'API pages out of sync: missing {modules - directives}, '
        f'stale {directives - modules}')


# A citation is a back-quoted token that reads as a path (a file suffix or
# a trailing ``/``; a ``::test`` tail is dropped), or a root document named
# bare in capitals, as this repository names them (``PERF.md``,
# ``BENCHMARK.json``). One that carries line numbers (``compiler.py:164``)
# is not this repository's: that is SURVEY.md's way of citing the fixed
# sources of the project this one was modelled on, and lines move here.
QUOTED = re.compile(r'`+([^`\s]+?)(?:::[^`]*)?`+')
PATH = re.compile(r'[\w./-]*\w(?:/|\.(?:py|md|json|jsonl|yml|toml|txt|cpp))')
ROOT_DOCUMENT = re.compile(r'\b[A-Z][A-Z_]+\.(?:md|json|jsonl)\b')
# that project's files where they are cited without lines; it ships an
# ``examples/tinysys`` of its own
REFERENCE = ('torchsystem/', 'examples/tinysys/')
# what running leaves in a checkout (.gitignore): never walked
UNTRACKED = {'__pycache__', 'chiprun_out'}
PAGES = sorted(DOCS.glob('*.md')) + [REPO / 'README.md', REPO / 'COVERAGE.md']


@functools.cache
def _citable() -> frozenset:
    """Every way to cite a file or a directory (with its trailing ``/``)
    of the checkout: each tail of its path (``parallel/overlap.py`` cites
    ``tpusystem/parallel/overlap.py``). Hidden directories other than
    ``.github`` and ``.claude`` are what runs leave behind."""
    tails = set()
    for root, names, leaves in os.walk(REPO):
        names[:] = [name for name in names if name not in UNTRACKED
                    and (not name.startswith('.')
                         or name in ('.github', '.claude'))]
        parts = pathlib.Path(root).relative_to(REPO).parts
        for last in [*leaves, *(f'{name}/' for name in names)]:
            tails.update('/'.join((*parts[start:], last))
                         for start in range(len(parts) + 1))
    return frozenset(tails)


def _missing(text: str) -> list[str]:
    """The paths ``text`` cites that are not in the checkout."""
    cited = {token for token in QUOTED.findall(text)
             if PATH.fullmatch(token)} | set(ROOT_DOCUMENT.findall(text))
    return sorted(token for token in cited - _citable()
                  if not token.startswith(REFERENCE))


@pytest.mark.parametrize('page', PAGES,
                         ids=lambda page: page.relative_to(REPO).as_posix())
def test_every_path_a_page_cites_exists(page):
    missing = _missing(page.read_text())
    assert not missing, f'{page.name} cites what is not there: {missing}'


def test_every_path_the_package_cites_exists():
    missing = {module.relative_to(REPO).as_posix(): found
               for module in sorted((REPO / 'tpusystem').rglob('*.py'))
               if (found := _missing(module.read_text()))}
    assert not missing, f'the package cites what is not there: {missing}'


@pytest.mark.slow
def test_coverage_md_test_count_matches_collection():
    """COVERAGE.md's "Totals: N tests" line must equal what pytest
    actually collects — the count drifted in two consecutive rounds when
    maintained by hand, so it is now pinned by construction."""
    out = subprocess.run(
        [sys.executable, '-m', 'pytest', 'tests/', '--collect-only', '-q',
         '-m', '', '-p', 'no:cacheprovider'],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'}).stdout
    collected = int(re.search(r'(\d+) tests collected', out).group(1))
    written = int(re.search(r'Totals: (\d+) tests',
                            (REPO / 'COVERAGE.md').read_text()).group(1))
    assert written == collected, (
        f'COVERAGE.md says {written} tests but collection finds '
        f'{collected} — update the Totals line')
