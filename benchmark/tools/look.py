"""A look at a cell, not a run of it: another batch size, or where the
host's time goes inside the window.

  python3 benchmark/tools/look.py --workload teacher_polish --seed 7 \
      --seconds 10 --trace 1 --batch_size 2048 --profile 1

The cell runs through the harness and its own entry (`run.run_cell`), with
the configuration's `batch_size` overridden and/or cProfile around the
measured window (its 25 heaviest functions by own time go to standard
error; profiling slows the window by a few percent, so the rate printed
is not the cell's). Prints the result line. Not run by the benchmark.
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


def main(argv=None):
  parser = argparse.ArgumentParser()
  parser.add_argument('--workload', required=True)
  parser.add_argument('--seed', type=int, required=True)
  parser.add_argument('--seconds', type=float, default=10.0)
  parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
  parser.add_argument('--batch_size', type=int, default=0)
  parser.add_argument('--profile', type=int, choices=(0, 1), default=0)
  args = parser.parse_args(argv)
  from benchmark import run

  look = {'batch_size': args.batch_size} if args.batch_size else None
  result = run.run_cell(os.path.join(ROOT, 'BENCHMARK.json'), args.workload,
                        args.seed, args.seconds, bool(args.trace), look=look,
                        profile=bool(args.profile))
  print(json.dumps(dict(result, look=look, profiled=bool(args.profile))),
        flush=True)
  return 0


if __name__ == '__main__':
  sys.exit(main())
