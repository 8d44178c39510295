"""Readings that the limits of `correct` are set from, in one process.

  python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
      --control-seeds 1,2,3 --seconds 3 --out chiprun_out/readings.json

For each seed the cell runs through the harness for a short window at its
own load and the compared numbers are kept (the lower readings). For each
control seed the plain reference, with every matmul operand rounded to fp8
is put in the program's place on the
same windows (the upper readings). Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)


def control_numbers(bench_path: str, workload: str, seed: int,
                    precisions=('fp8',)) -> dict:
  """The compared numbers when the reference at a lower precision serves
  the cell's windows (the sample a full run compares)."""
  import numpy as np

  from benchmark import run
  from benchmark.lib import compare

  loaded = run.load_cell(bench_path, workload)
  family, traffic = loaded.family, loaded.traffic
  shape = family.shape_of(loaded.config)
  params = family.make_params(shape, seed)
  generator = run.load_by_name(loaded.bench_dir, 'generators',
                               traffic['generator'])
  pool = generator.make(shape, traffic, seed)
  rng = np.random.default_rng(int(seed))
  take = min(int(traffic['compare_windows']), len(pool))
  sample = np.sort(rng.choice(len(pool), size=take, replace=False))
  windows = pool[sample]
  ref_logits = family.reference_logits(params, windows, shape)
  yard = family.reference_logits(params, windows, shape, 'bfloat16')
  out = {}
  for precision in precisions:
    low = family.reference_logits(params, windows, shape, precision)
    out[precision] = compare.numbers(
        ref_logits, *compare.served_from_logits(low), yard)
  return out


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seeds', default='')
  parser.add_argument('--control-seeds', default='')
  parser.add_argument('--seconds', type=float, default=3.0)
  parser.add_argument('--out', default='')
  parser.add_argument('--bench', default=os.path.join(ROOT, 'BENCHMARK.json'))
  args = parser.parse_args(argv)
  from benchmark import run

  ints = lambda text: [int(x) for x in text.split(',') if x]
  report = {'workload': args.workload, 'program': {}, 'control': {}}
  for seed in ints(args.seeds):
    result = run.run_cell(args.bench, args.workload, seed, args.seconds,
                          trace=False)
    report['program'][str(seed)] = dict(
        result['numbers'], failed=result['failed'], correct=result['correct'],
        windows_per_s=result['metrics']['windows_per_s']['value'])
    print('program', seed, json.dumps(report['program'][str(seed)]),
          flush=True)
  for seed in ints(args.control_seeds):
    report['control'][str(seed)] = control_numbers(
        args.bench, args.workload, seed)
    print('control', seed, json.dumps(report['control'][str(seed)]),
          flush=True)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, 'w') as f:
      json.dump(report, f, indent=1)
  return 0


if __name__ == '__main__':
  sys.exit(main())
