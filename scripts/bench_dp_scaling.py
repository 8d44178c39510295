"""dp-scaling bench: windows/s + transfer-overlap fraction per dp.

Drives the runner's double-buffered dispatch path (dp-sharded
`jax.device_put` of the compact uint8 pack, forward launched by the
NEXT pack's dispatch) through a depth-2 pipeline — the same pattern
the ConsensusEngine uses — and prints one JSON line.

Run ONE dp per process: jax pins the device count at backend init, and
a chip belongs to one process at a time, so a sweep is one invocation
after another, never children of a process that holds the device.
With --force_host_devices the dp axis spans virtual CPU devices
sharing one host core — windows/s is then an overhead/parity number,
NOT a speedup claim. The real sweep runs this script on a multi-chip
host, where the overlap fraction measures genuine host->device
transfer hiding; it is not measured yet.
"""
import argparse
import json
import time
from collections import deque


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--dp', type=int, default=1)
  ap.add_argument('--batch', type=int, default=256)
  ap.add_argument('--packs', type=int, default=12)
  ap.add_argument('--warmup', type=int, default=2)
  ap.add_argument('--force_host_devices', type=int, default=0,
                  help='force N virtual CPU devices before backend '
                       'init')
  args = ap.parse_args()

  if args.force_host_devices:
    # XLA reads this at backend init — set it before jax imports.
    import os

    flag = ('--xla_force_host_platform_device_count='
            f'{args.force_host_devices}')
    os.environ['XLA_FLAGS'] = (
        f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip())
  import jax

  if args.force_host_devices:
    try:
      jax.config.update('jax_platforms', 'cpu')
    except RuntimeError:
      pass  # backend already initialized; device check below decides
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib
  from deepconsensus_tpu.parallel import mesh as mesh_lib
  from scripts._bench_common import make_rows

  devices = jax.devices()
  if len(devices) < args.dp:
    print(json.dumps({
        'dp': args.dp, 'error': f'only {len(devices)} devices; need '
        f'{args.dp} (fresh process or --force_host_devices)'}))
    return 1
  if args.batch % args.dp:
    print(json.dumps({
        'dp': args.dp,
        'error': f'batch {args.batch} not divisible by dp={args.dp}'}))
    return 1
  mesh = None
  if args.dp > 1:
    mesh = mesh_lib.make_mesh(dp=args.dp, tp=1,
                              devices=devices[:args.dp])

  params = config_lib.get_config('transformer_learn_values+test')
  config_lib.finalize_params(params, is_training=False)
  model = model_lib.get_model(params)
  variables = model.init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))
  options = runner_lib.InferenceOptions(batch_size=args.batch)
  runner = runner_lib.ModelRunner(params, variables, options, mesh=mesh)

  # A small rotating pool of distinct packs: inputs vary between
  # dispatches without holding args.packs full batches on the host.
  rng = np.random.default_rng(0)
  pool = [make_rows(params, args.batch, rng=rng)
          for _ in range(min(4, args.packs))]

  for i in range(args.warmup):  # compile + steady-state transfers
    runner.finalize(runner.dispatch(pool[i % len(pool)]))

  before = runner.dispatch_stats()
  pending = deque()
  t0 = time.perf_counter()
  for i in range(args.packs):
    pending.append(runner.dispatch(pool[i % len(pool)]))
    if len(pending) >= 2:  # engine dispatch_depth pattern
      runner.finalize(pending.popleft())
  while pending:
    runner.finalize(pending.popleft())
  dt = time.perf_counter() - t0

  after = runner.dispatch_stats()
  overlapped = (after['n_transfer_overlapped']
                - before['n_transfer_overlapped'])
  direct = after['n_transfer_direct'] - before['n_transfer_direct']
  launches = overlapped + direct
  print(json.dumps({
      'dp': args.dp,
      'n_devices': len(devices),
      'backend': devices[0].platform,
      'batch': args.batch,
      'packs': args.packs,
      'sharded': mesh is not None,
      'windows_per_sec': round(args.batch * args.packs / dt, 1),
      'transfer_overlap_fraction': (
          round(overlapped / launches, 4) if launches else 0.0),
      'n_transfer_overlapped': overlapped,
      'n_transfer_direct': direct,
  }), flush=True)
  return 0


if __name__ == '__main__':
  raise SystemExit(main())
