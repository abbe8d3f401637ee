"""Read the two ends a limit is set between, on the chip, in one process.

``python chipbench/control.py --workload <cell> --seeds 1,2,3 [--controls 3]
[--seconds 2]`` runs the cell's own driver once per seed with a short window
(the lower reading: what sound runs of the program give) and, for the first
``--controls`` seeds, the readings that have to come out as not correct.
The precision is the one below the configuration's own, which its file
states under ``reference.control``; the reference is its family's:

* training: the reference put in the program's place with both operands
  of every matrix product rounded to ``control.precision`` (fp8 under a
  configuration's bfloat16), and with half of each batch left out and the
  mean taken over the rest;
* serving: at every compared position of the same prompts and served
  tokens, the gap of the token that the reference with its matrices at
  ``control.bits`` (4 under a configuration's int8) puts first.

The benchmark's own runs never run this. It prints one JSON line per seed
and a last line with the largest lower and smallest upper reading of each
number, and writes the same to ``chiprun_out/control_<cell>.json``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def training_controls(config: dict, seed: int, records: dict) -> dict:
    from chipbench import check, families
    family = families.of(config)
    fed, reference = records['fed'], records['reference_reading']
    out = {}
    precision = config['reference']['control']['precision']
    lowered = family.reference_training(config, seed, fed,
                                        precision=precision)
    out[precision], _ = check.compare_training(lowered, reference)
    half = [batch[:batch.shape[0] // 2] for batch in fed]
    halved = family.reference_training(config, seed, half)
    out['half_batch'], _ = check.compare_training(halved, reference)
    return out


def serving_controls(config: dict, seed: int, records: dict) -> dict:
    from chipbench import families
    bits = config['reference']['control']['bits']
    widest, _ = families.of(config).served_gap(
        config, seed, records['sample'], control_bits=bits)
    return {f'int{bits}': {'logit_gap_max': widest}}


CONTROLS = {'train': training_controls, 'serve': serving_controls}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', required=True)
    parser.add_argument('--controls', type=int, default=3)
    parser.add_argument('--seconds', type=float, default=2.0)
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    kind = cell.traffic['driver']
    driver = importlib.import_module(f'chipbench.drivers.{kind}')
    lines = []
    for position, seed in enumerate(int(s) for s in args.seeds.split(',')):
        began = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix='chipbench-') as scratch:
            records = driver.run(harness.Run(
                cell, seed, args.seconds, False, pathlib.Path(scratch),
                time.perf_counter()))
        line = {'seed': seed, 'failed': records['failed'],
                'attempted': records['attempted'],
                'program': {name: value for name, value, _
                            in records['compared']},
                'end_to_end': records['end_to_end']}
        if position < args.controls:
            line['controls'] = CONTROLS[kind](cell.config, seed, records)
        line['seconds'] = time.perf_counter() - began
        for note in records.get('notes', []):
            print(note, file=sys.stderr)
        print(json.dumps(line), flush=True)
        lines.append(line)
    summary = {'workload': args.workload, 'lower': {}, 'upper': {}}
    for line in lines:
        for name, value in line['program'].items():
            summary['lower'][name] = max(summary['lower'].get(name, 0.0),
                                         value)
        for control, numbers in line.get('controls', {}).items():
            seen = summary['upper'].setdefault(control, {})
            for name, value in numbers.items():
                seen[name] = min(seen.get(name, float('inf')), value)
    out = ROOT / 'chiprun_out'
    out.mkdir(exist_ok=True)
    (out / f'control_{args.workload}.json').write_text(
        json.dumps({'summary': summary, 'lines': lines}, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == '__main__':
    sys.exit(main())
