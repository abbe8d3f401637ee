"""``chip_smoke.py`` on the CPU: its train and serve bodies at
``gpt2_tiny`` with the kernels interpreted, the device gate, the
stale-store trap, the compile-cache helper, and launchers that stay off
the backend. The chip run itself is ``python chip_smoke.py`` through the
chip tool."""

from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

import chip_smoke as smoke
from tpusystem.models import gpt2_tiny
from tpusystem.parallel import single_device_mesh

REPO = pathlib.Path(__file__).parent.parent


@pytest.fixture(scope='module')
def trained(tmp_path_factory):
    """One tiny training run through ``chip_smoke.train``; the store it
    leaves behind is the stale store of the trap test."""
    lm = smoke.load_lm()
    # one device: FSDP over 8 virtual CPU devices triples the step time
    lm.provider.override(lm.mesh, single_device_mesh)
    store = tmp_path_factory.mktemp('smoke') / 'store'
    return lm, store, smoke.train(lm, store, full=False)


def test_kernel_checks_pass_interpreted():
    smoke.check_flash(batch=1, seq=64, heads=2, head_dim=16, dtype='float32')
    smoke.check_decode(rows=4, dim=32, dtype='float32')


def test_train_takes_its_steps_and_learns(trained):
    _, _, report = trained
    assert report['steps'] == 2 * smoke.EPOCH_STEPS
    assert report['losses'][1] < report['losses'][0]
    assert report['checkpoint'] == 2 and report['devices'] == 1
    assert report['mosaic_calls'] == 0          # interpreted on the CPU


def test_stale_store_trips_the_step_count(trained):
    """A store that already holds the experiment makes ``lm.main`` resume
    at its epoch and run zero steps — the smoke must not pass on that."""
    lm, store, _ = trained
    with pytest.raises(AssertionError, match='0 train steps taken'):
        smoke.train(lm, store, full=False)


def test_serve_completes_every_request_on_one_trace():
    report = smoke.serve(gpt2_tiny(dtype='float32'))
    assert report['requests'] == len(smoke.PROMPTS)
    assert report['tokens'] == sum(smoke.BUDGETS)
    assert report['trace_count'] == 1
    # what 'auto' resolves to off the chip
    assert (report['decode_impl'], report['stream_dtype']) == ('flax', 'auto')


def test_main_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as refusal:
        smoke.main()
    assert refusal.value.code not in (0, None)
    assert "'platform': 'cpu'" in str(refusal.value.code)
    assert capsys.readouterr().out == ''        # no result line


CACHE_REPORT = ('import jax; from tpusystem.runtime import compile_cache; '
                'before = jax.config.jax_compilation_cache_dir; '
                'print(before, compile_cache(), '
                'jax.config.jax_compilation_cache_dir)')
SUPERVISOR_REPORT = ('import sys\n'
                     'from tpusystem.parallel import Supervisor\n'
                     'from jax._src import xla_bridge\n'
                     "with Supervisor([sys.executable, '-c', 'pass']):\n"
                     '    print(len(xla_bridge._backends))\n')


def _python(code: str, **env) -> subprocess.Popen:
    base = {key: value for key, value in os.environ.items()
            if key != 'JAX_COMPILATION_CACHE_DIR'}
    return subprocess.Popen(
        [sys.executable, '-c', code], cwd=REPO, text=True,
        env={**base, 'JAX_PLATFORMS': 'cpu', **env},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def _output(process: subprocess.Popen) -> str:
    out, err = process.communicate(timeout=120)
    assert process.returncode == 0, err[-2000:]
    return out.strip()


@pytest.fixture(scope='module', autouse=True)
def fresh_processes(tmp_path_factory):
    """The fresh-interpreter probes, started with the module so their
    imports overlap the training run instead of adding to it."""
    placed = str(tmp_path_factory.mktemp('cache'))
    processes = {
        'default': [_python(CACHE_REPORT), _python(CACHE_REPORT)],
        'placed': _python(CACHE_REPORT, JAX_COMPILATION_CACHE_DIR=placed),
        'supervisor': _python(SUPERVISOR_REPORT)}
    yield placed, processes
    for process in [*processes['default'], processes['placed'],
                    processes['supervisor']]:
        if process.poll() is None:
            process.kill()
            process.communicate()


def test_compile_cache_is_placed_from_outside_or_at_the_checkout(
        fresh_processes):
    placed, processes = fresh_processes
    first, second = (_output(run) for run in processes['default'])
    default = str(REPO / '.jax_cache')
    assert first == second == f'None {default} {default}'
    # set from outside: JAX had read the variable itself, nothing changed
    assert _output(processes['placed']) == f'{placed} {placed} {placed}'


def test_a_supervisor_parent_initialises_no_backend(fresh_processes):
    _, processes = fresh_processes
    assert _output(processes['supervisor']) == '0'
