"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for a chip, the compile cache, the per-layer
readers, and the result line.

Nothing here knows a cell, a configuration or a metric by name: a cell is
``{name, config, traffic, chips, why}`` in ``BENCHMARK.json``, its
configuration is the file that entry names, its traffic is
``chipbench/traffic/<traffic>.json``, its limits are
``chipbench/limits/<cell>.json``, and a per-layer metric ``<m>`` is
``chipbench/metrics/<m>.json`` naming a module of ``chipbench/readers/``.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # the BENCHMARK.json entries this cell reports
    per_layer: list


@dataclasses.dataclass
class Run:
    """What a driver is handed."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    scratch: pathlib.Path     # this run's own directory, removed afterwards
    started: float            # perf_counter at process start


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str) -> bool:
    return 'workloads' not in metric or cell in metric['workloads']


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its three files, found by name alone."""
    bench = _json(root / 'BENCHMARK.json')
    cells = {entry['name']: entry for entry in bench['workloads']}
    if name not in cells:
        raise SystemExit(f'no workload {name!r} in BENCHMARK.json; '
                         f'it has {sorted(cells)}')
    entry = cells[name]
    configs = {config['name']: config for config in bench['configs']}
    config = _json(root / configs[entry['config']]['file'])
    traffic = _json(root / 'chipbench' / 'traffic'
                    / f'{entry["traffic"]}.json')
    limits = _json(root / 'chipbench' / 'limits' / f'{name}.json')
    end_to_end = [metric for metric in bench['end_to_end']
                  if _reports(metric, name)]
    reported = {metric['name'] for metric in end_to_end}
    per_layer = [metric for metric in bench['per_layer']
                 if (name in metric['workloads'] if 'workloads' in metric
                     else metric['moves'] in reported)]
    return Cell(name, entry['chips'], config, traffic, limits, end_to_end,
                per_layer)


def require_chips(chips: int) -> dict:
    """The devices as JAX reports them; no TPU, or fewer chips than the
    cell asks for, ends the run with no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < chips:
        raise SystemExit(
            f'chipbench needs {chips} TPU chip(s); jax.devices() found '
            f'{len(devices)} x {devices[0].platform} '
            f'({devices[0].device_kind})')
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax
    peaks = [(device.memory_stats() or {}).get('peak_bytes_in_use', 0)
             for device in jax.devices()]
    return int(max(peaks))


def place_compile_cache() -> str:
    """The program's own cache placement (``<checkout>/.jax_cache`` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set), and every program kept, however
    quick its compile: a run after the first compiles nothing."""
    import jax
    from tpusystem.runtime import compile_cache
    path = compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return path


class Profile:
    """``jax.profiler`` over part of a window, in a run of its own: the
    Python tracer off (it slows the host and swells the trace), and one
    ``chipbench.window`` span so the reduction knows what was traced."""

    def __init__(self, directory: pathlib.Path, wanted: bool) -> None:
        self.directory, self.running = directory, False
        if wanted:
            import jax
            from chipbench.trace_reduce import WINDOW_SPAN
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(directory),
                                     profiler_options=options)
            self.span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self.span.__enter__()
            self.running = True

    def stop(self) -> None:
        if self.running:
            import jax
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.running = False


def read_per_layer(cell: Cell, records: dict,
                   root: pathlib.Path = ROOT) -> dict:
    """Each of the cell's per-layer metrics through its own reader; one
    that finds nothing to read is left out."""
    metrics = {}
    for entry in cell.per_layer:
        spec = _json(root / 'chipbench' / 'metrics' / f'{entry["name"]}.json')
        reader = importlib.import_module(
            f'chipbench.readers.{spec["reader"]}')
        value = reader.read(records, spec)
        if value is not None:
            metrics[entry['name']] = {'value': float(value),
                                      'unit': entry['unit']}
    return metrics


def percentile(samples: list, share: float) -> float:
    """The exact ``share`` quantile of the samples (sorted, nearest rank)."""
    ordered = sorted(samples)
    rank = -(-round(share * 100) * len(ordered) // 100) - 1
    return ordered[max(0, min(len(ordered) - 1, rank))]


def within(value: float, limit: float) -> bool:
    """A value that is not a number has failed its limit."""
    return value == value and value <= limit


def judge(compared: list, failed: int) -> bool:
    """``compared`` is ``[(name, value, limit), ...]``."""
    return failed == 0 and all(within(value, limit)
                               for _, value, limit in compared)


def run(workload: str, *, seed: int, seconds: float, trace: bool,
        started: float, root: pathlib.Path = ROOT) -> str:
    """Drive one run of ``workload`` and return its result line."""
    cell = load_cell(workload, root)
    device = require_chips(cell.chips)
    place_compile_cache()
    driver = importlib.import_module(
        f'chipbench.drivers.{cell.traffic["driver"]}')
    with tempfile.TemporaryDirectory(prefix='chipbench-') as scratch:
        records = driver.run(Run(cell, seed, seconds, trace,
                                 pathlib.Path(scratch), started))
        result = {'correct': judge(records['compared'], records['failed']),
                  'attempted': records['attempted'],
                  'failed': records['failed']}
        device['memory_peak_bytes'] = records['memory_peak_bytes']
        if trace:
            from chipbench import trace_reduce
            began = time.perf_counter()
            records['trace'] = trace_reduce.read(records['trace_dir'])
            summary = trace_reduce.device_summary(records['trace'])
            device['busy_s'] = summary['busy_s']
            device['window_s'] = summary['window_s']
            result['metrics'] = read_per_layer(cell, records, root)
            print(f'trace read in {time.perf_counter() - began:.1f} s',
                  file=sys.stderr)
        else:
            result['metrics'] = {
                entry['name']: {'value': float(
                    records['end_to_end'][entry['name']]),
                    'unit': entry['unit']}
                for entry in cell.end_to_end}
    result['device'] = device
    if trace:
        result['breakdown'] = summary['breakdown']
    result['compared'] = {name: {'value': value, 'limit': limit}
                          for name, value, limit in records['compared']}
    for note in records.get('notes', []):
        print(note, file=sys.stderr)
    for name, value, limit in records['compared']:
        verdict = 'ok' if within(value, limit) else 'FAILED'
        print(f'compared {name}: {value:.6g} (limit {limit:.6g}) {verdict}',
              file=sys.stderr)
    print(f'correct {result["correct"]}: attempted {result["attempted"]}, '
          f'failed {result["failed"]}', file=sys.stderr, flush=True)
    return json.dumps(result)
