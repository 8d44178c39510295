"""End-to-end inference benchmark: BAM -> FASTQ ZMW/s on real hardware.

Drives the full `run_inference` pipeline (BAM decode, featurization,
skip triage, jit'd model forward, stitch, FASTQ write) over the bundled
human_1m testdata, repeated --repeats times so the jit compile and BAM
open amortize out of the steady-state number. Prints one JSON line with
ZMW/s, windows/s, and the per-stage runtime split from the runtime CSV.

The reference's end-to-end anchor is 178 ZMWs in 234.95 s (~0.76
ZMW/s) on an n1-standard-16 (reference docs/quick_start.md:315-320);
vs_baseline is against that. The full-size model runs on whatever
backend jax selects; featurization
runs on the host, so on a 1-core host this measures the host-bound
configuration — rerun on a many-core host with --cpus for the
chip-bound one.
"""
import argparse
import csv
import json
import os
import tempfile
import time

REFERENCE_ZMW_PER_SEC = 178 / 234.95


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--testdata',
                  default='/root/reference/deepconsensus/testdata/human_1m')
  ap.add_argument('--repeats', type=int, default=8)
  ap.add_argument('--cpus', type=int, default=0)
  ap.add_argument('--batch_size', type=int, default=1024)
  ap.add_argument('--depth', type=int, default=8,
                  help='dispatch pipeline depth (batches in flight); '
                  'sweep this on hardware')
  ap.add_argument('--batch_zmws', type=int, default=100)
  ap.add_argument('--cpu', action='store_true', help='force CPU backend')
  args = ap.parse_args()
  if args.repeats < 1:
    ap.error('--repeats must be >= 1 (repeat 0 is the compile warmup)')

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib

  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params, is_training=False)
  model = model_lib.get_model(params)
  rows = jnp.zeros((1, params.total_rows, params.max_length, 1))
  variables = model.init(jax.random.PRNGKey(0), rows)
  options = runner_lib.InferenceOptions(
      batch_size=args.batch_size, batch_zmws=args.batch_zmws,
      cpus=args.cpus, dispatch_depth=args.depth,
      min_quality=0,  # untrained weights: keep the writer path honest
  )
  runner = runner_lib.ModelRunner(params, variables, options)

  td = args.testdata
  out_dir = tempfile.mkdtemp(prefix='dc_e2e_')
  totals = {}
  n_zmws = n_windows = 0
  warm_plus_timed = args.repeats + 1
  t_steady = None
  for rep in range(warm_plus_timed):
    if rep == 1:  # repeat 0 pays jit compile; steady state starts here
      t_steady = time.perf_counter()
    out = os.path.join(out_dir, f'out_{rep}.fastq')
    counters = runner_lib.run_inference(
        subreads_to_ccs=f'{td}/subreads_to_ccs.bam',
        ccs_bam=f'{td}/ccs.bam',
        checkpoint=None,
        output=out,
        options=options,
        runner=runner,
    )
    if rep == 0:
      continue
    n_zmws += counters['n_zmw_pass']
    with open(out + '.runtime.csv') as f:
      for row in csv.DictReader(f):
        totals[row['stage']] = (
            totals.get(row['stage'], 0.0) + float(row['runtime'])
        )
        if row['stage'] == 'run_model':
          n_windows += int(row.get('n_examples', 0) or 0)
  elapsed = time.perf_counter() - t_steady
  result = {
      'metric': 'e2e_inference_zmw_per_sec',
      'value': round(n_zmws / elapsed, 2),
      'unit': (f'ZMW/s e2e (backend={jax.default_backend()}, '
               f'cpus={args.cpus}, depth={args.depth}, '
               f'{os.cpu_count()} host cores)'),
      'dispatch_depth': args.depth,
      'batch_zmws': args.batch_zmws,
      'vs_baseline': round(n_zmws / elapsed / REFERENCE_ZMW_PER_SEC, 1),
      'windows_per_sec': round(n_windows / elapsed, 1),
      'stage_seconds': {k: round(v, 2) for k, v in sorted(totals.items())},
      'n_zmws': n_zmws,
  }
  print(json.dumps(result), flush=True)


if __name__ == '__main__':
  main()
