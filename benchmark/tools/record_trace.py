"""Records the small chip trace that the xplane tests reduce.

  python3 benchmark/tools/record_trace.py --workload teacher_polish \
      --out chiprun_out/recorded_trace_v5e.json

Runs the cell traced for about two seconds, then writes the events the
reduction reads (device `XLA Modules` and `XLA Ops` lines, the harness's
host annotations) as JSON, operation names cut to their short form, and
the side table of the operations' scopes (`xplane.to_recording`), with the
numbers the reduction gave on the spot as `expected`: among them the device
seconds under each pattern of `--scopes` (`scope_seconds`).
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
  parser.add_argument('--out', required=True)
  parser.add_argument('--seconds', type=float, default=2.0)
  parser.add_argument('--scopes', default='',
                      help="comma-separated patterns; '' is always read")
  args = parser.parse_args(argv)
  from benchmark import run
  from benchmark.lib import xplane

  run.run_cell(os.path.join(ROOT, 'BENCHMARK.json'), args.workload, 7,
               args.seconds, trace=True)
  trace_dir = os.path.join(ROOT, 'bench_out', f'trace.{args.workload}')
  names = ('bench_window', 'bench_submit', 'bench_flush')
  planes = xplane.load(xplane.find_trace(trace_dir), names)
  lo, hi = xplane.window_of(planes, 'bench_window')
  durations = xplane.module_durations(planes, xplane.FORWARD_MODULE_PREFIX, lo, hi)
  expected = {
      'busy_s': xplane.busy_seconds(planes, lo, hi),
      'n_forward': len(durations),
      'forward_median_s': xplane.median(durations),
      'top_op': xplane.top_ops(planes, lo, hi)[0][0],
      'scope_s': {pattern: xplane.scope_seconds(planes, lo, hi, pattern)
                  for pattern in [''] + [p for p in args.scopes.split(',')
                                         if p]},
  }
  with open(args.out, 'w') as f:
    json.dump({'device': 'TPU v5 lite', 'workload': args.workload,
               **xplane.to_recording(planes), 'expected': expected}, f)
  print(json.dumps(expected))
  return 0


if __name__ == '__main__':
  sys.exit(main())
