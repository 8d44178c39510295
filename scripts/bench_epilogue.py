"""Device-epilogue A/B: D2H bytes/pack + windows/s, on vs off.

Drives the same depth-2 dispatch/finalize pipeline the ConsensusEngine
uses, once with the device-resident output plane (uint8 ids + quals
drained, 2 bytes/position) and once with the host quality path (int32
ids + f32 max_prob, 8 bytes/position), and prints one JSON line per
variant plus a summary line with the measured reduction and a
byte-identity verdict. The bytes ratio is backend-independent; the
windows/s delta means something only on a TPU and is not measured yet
(on CPU it mostly measures the host log10/round work the epilogue
removes).
"""
import argparse
import json
import time
from collections import deque


def _run_variant(runner_lib, params, variables, args, pool, device_epilogue,
                 mesh=None):
  options = runner_lib.InferenceOptions(
      batch_size=args.batch, device_epilogue=device_epilogue)
  runner = runner_lib.ModelRunner(params, dict(variables), options,
                                  mesh=mesh)
  for i in range(args.warmup):
    runner.finalize(runner.dispatch(pool[i % len(pool)]))
  pending = deque()
  last = None
  t0 = time.perf_counter()
  for i in range(args.packs):
    pending.append(runner.dispatch(pool[i % len(pool)]))
    if len(pending) >= 2:  # engine dispatch_depth pattern
      last = runner.finalize(pending.popleft())
  while pending:
    last = runner.finalize(pending.popleft())
  dt = time.perf_counter() - t0
  stats = runner.dispatch_stats()
  return {
      'device_epilogue': bool(device_epilogue),
      'windows_per_sec': round(args.batch * args.packs / dt, 1),
      'd2h_bytes_per_pack': stats['d2h_bytes_per_pack'],
      'd2h_bytes_per_position': round(
          stats['d2h_bytes_per_pack'] / (args.batch * params.max_length),
          2),
      'n_epilogue_packs': stats['n_epilogue_packs'],
  }, last


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batch', type=int, default=1024)
  ap.add_argument('--packs', type=int, default=8)
  ap.add_argument('--warmup', type=int, default=2)
  ap.add_argument('--config', default='transformer_learn_values_distill+test')
  ap.add_argument('--fused', action='store_true',
                  help='route through the fused encoder blocks (the '
                       'Pallas epilogue rides the fused hot path)')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib
  from scripts._bench_common import make_rows

  params = config_lib.get_config(args.config)
  if args.fused:
    with params.unlocked():
      params.use_fused_hotpath = True
  config_lib.finalize_params(params, is_training=False)
  model = model_lib.get_model(params)
  variables = model.init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))

  rng = np.random.default_rng(0)
  pool = [make_rows(params, args.batch, rng=rng)
          for _ in range(min(4, args.packs))]

  results = {}
  outputs = {}
  for device_epilogue in (True, False):
    line, last = _run_variant(runner_lib, params, variables, args, pool,
                              device_epilogue)
    line.update({'backend': jax.devices()[0].platform,
                 'batch': args.batch, 'packs': args.packs,
                 'config': args.config, 'fused': args.fused})
    results[device_epilogue] = line
    outputs[device_epilogue] = last
    print(json.dumps(line), flush=True)

  on, off = results[True], results[False]
  identical = bool(
      np.array_equal(np.asarray(outputs[True][0], np.int64),
                     np.asarray(outputs[False][0], np.int64))
      and np.array_equal(np.asarray(outputs[True][1], np.int64),
                         np.asarray(outputs[False][1], np.int64)))
  print(json.dumps({
      'summary': 'd2h_epilogue_ab',
      'd2h_reduction': round(
          off['d2h_bytes_per_pack'] / on['d2h_bytes_per_pack'], 2),
      'speedup_epilogue': round(
          on['windows_per_sec'] / off['windows_per_sec'], 3),
      'byte_identical': identical,
  }), flush=True)
  return 0 if identical else 1


if __name__ == '__main__':
  raise SystemExit(main())
