"""The ``kv_read_roofline`` reader on a synthetic trace and request list:
no chip, no profiler."""

from __future__ import annotations

import json
import pathlib

import pytest

from chipbench import trace_reduce
from chipbench.readers import kv_read_roofline

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / 'chipbench' / 'metrics'
                   / 'kv_read_roofline.json').read_text())
CONFIG = {'family': 'gpt2', 'n_layer': 2, 'n_embd': 128}
HBM = 819e9                                  # peaks.json, TPU v5 lite


def records(ops, requests, window=(100.0, 110.0)) -> dict:
    """A 10 s traced window (trace clock 0-10, host clock 100-110)."""
    trace = trace_reduce.Trace(
        ops={0: ops}, modules={0: [('jit_step_fn(1)', 1.0, 9.0)]},
        host=[('chipbench.window', 0.0, 10.0)])
    return {'trace': trace, 'traced_window': window, 'requests': requests,
            'config': CONFIG, 'device_kind': 'TPU v5 lite'}


KERNELS = [('paged_decode_attention.3 [tpu_custom_call]', 1.0, 1.5),
           ('paged_decode_attention [tpu_custom_call]', 2.0, 2.5),
           ('step_fn.7 [tpu_custom_call]', 3.0, 4.0),        # the chain's
           ('paged_decode_attention.9 [tpu_custom_call]', 9.5, 10.5)]  # cut


def test_least_bytes_count_the_positions_a_row_holds_and_no_others(capsys):
    requests = [
        # prompt 10: token 0 is prefill's; tokens 1, 2 decode inside the
        # window at depths 11 and 12; token 3 falls after it
        {'prompt': 10, 'times': [101.0, 102.0, 103.0, 111.0]},
        # prompt 50: decoded before the window but for its last token
        {'prompt': 50, 'times': [90.0, 95.0, 100.5]},
        # never answered
        {'prompt': 700, 'times': []}]
    positions = 11 + 12 + 52
    moved = positions * CONFIG['n_layer'] * 2 * CONFIG['n_embd'] * 2
    spent = 0.5 + 0.5 + 0.5            # the third kernel is cut at 10 s
    got = kv_read_roofline.read(records(KERNELS, requests), SPEC)
    assert got == pytest.approx(100.0 * moved / HBM / spent)
    said = capsys.readouterr().err
    assert f'{positions} positions attended' in said
    assert 'kernels 1.5000 s' in said
    # neither the table's width nor the reserved budget enters: a row
    # that reserved 1024 positions and holds 11 counts 11


def test_a_prefill_only_window_reads_none():
    requests = [{'prompt': 10, 'times': [101.0]}]
    assert kv_read_roofline.read(records(KERNELS, requests), SPEC) is None


@pytest.mark.parametrize('ops', [
    [],                                                   # no device op
    [('step_fn.7 [tpu_custom_call]', 3.0, 4.0),           # the parent:
     ('fusion.12 bf16[32768,20,64]', 4.0, 5.0)],          # gather, no kernel
    [('kv_read_other [tpu_custom_call]', 1.0, 2.0)]],
    ids=['no_ops', 'parent', 'another_kernel'])
def test_no_matching_kernel_reads_none(ops):
    requests = [{'prompt': 10, 'times': [101.0, 102.0, 103.0]}]
    assert kv_read_roofline.read(records(ops, requests), SPEC) is None


def test_a_run_that_was_not_traced_reads_none():
    run = records(KERNELS, [{'prompt': 10, 'times': [101.0, 102.0]}])
    run['traced_window'] = None
    assert kv_read_roofline.read(run, SPEC) is None


def test_the_entry_and_the_file_agree():
    """On the keys both have. Which cells report the metric is the entry's
    to say (its ``workloads``) and how it is read the file's (``reader``,
    ``args``): a cell joins the metric with no edit to the file."""
    (entry,) = [metric for metric in json.loads(
        (ROOT / 'BENCHMARK.json').read_text())['per_layer']
        if metric['name'] == 'kv_read_roofline']
    shared = ('name', 'unit', 'better', 'source', 'layer', 'moves')
    assert {key: SPEC[key] for key in shared} == {
        key: entry[key] for key in shared}
    assert set(entry) == {*shared, 'workloads'}
    assert set(SPEC) == {*shared, 'reader', 'args'}
    assert SPEC['reader'] == 'kv_read_roofline'
