"""Train-step throughput: batch-size sweep and dp-scaling mode.

Default mode times the full train step (forward + AlignmentLoss DP +
LAMB update) at several batch sizes with the Pallas wavefront loss (the
TPU default), transfer-free timing: the step returns only scalars, with
a parameter fingerprint keeping the update live against DCE.

--dp N switches to the pod-scaling mode: a short REAL run_training
(synthetic shards, pjit step, prefetch-overlapped transfers) on a
dp=N mesh at a FIXED global batch, reporting wall time, the prefetch
overlap counters from the metrics sidecar, and a loss-curve digest —
the digest is the cross-dp identity observable (equal global batch =>
equal curve). jax pins the device count at backend init, so a dp sweep
runs this script once per dp, one process after another.

--window_buckets W1,W2,... (dp mode only) makes the run bucketed: the
synthetic stream mixes windows at every bucket width, the model is the
transformer (the fc head is width-locked), and the row additionally
reports n_train_forward_shapes (the compile-once-per-bucket gate:
must equal the bucket count), per-bucket batch counters, the measured
train_padding_fraction, and padding_fraction_padmax — the waste the
same stream would pay under the old single-shape pad-to-max policy.
The padding delta is stream arithmetic (backend-independent); the
windows/s A/B against pad-to-max is not measured.

Prints one JSON line per run so an interrupted sweep keeps completed
rows.
"""
import argparse
import hashlib
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
  sys.path.insert(0, _REPO)


def _run_dp_mode(args):
  """One dp point: tiny real training run, counters from the sidecar."""
  import shutil
  import tempfile

  import jax

  from scripts import inject_faults
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import train as train_lib
  from deepconsensus_tpu.parallel import mesh as mesh_lib

  buckets = tuple(args.window_buckets or ())
  work = tempfile.mkdtemp(prefix=f'dc_bench_train_dp{args.dp}_')
  row = {'dp': args.dp, 'global_batch': args.global_batch,
         'steps': args.train_steps,
         'n_devices_visible': jax.device_count()}
  if buckets:
    row['window_buckets'] = list(buckets)
  try:
    train_patterns = []
    if buckets:
      # One shard set per bucket width so the stream genuinely mixes
      # widths; steps split evenly across buckets.
      n_per_width = args.global_batch * max(
          1, args.train_steps // len(buckets))
      for width in buckets:
        shard_dir = os.path.join(work, f'shards_w{width}')
        inject_faults.write_synthetic_tfrecords(
            shard_dir, n_shards=1, n_examples=n_per_width,
            max_passes=5, max_length=width)
        train_patterns.append(shard_dir + '/*')
      n_examples = n_per_width * len(buckets)
      # The fc head is width-locked; bucketed runs need the
      # length-agnostic transformer family.
      params = config_lib.get_config('transformer_learn_values+test')
    else:
      shard_dir = os.path.join(work, 'shards')
      n_examples = args.global_batch * args.train_steps
      inject_faults.write_synthetic_tfrecords(
          shard_dir, n_shards=2, n_examples=n_examples,
          max_passes=5, max_length=20)
      train_patterns.append(shard_dir + '/*')
      params = config_lib.get_config('fc+test')
    with params.unlocked():
      params.max_passes = 5
      params.max_length = buckets[0] if buckets else 20
    config_lib.finalize_params(params)
    with params.unlocked():
      params.dtype = 'float32'
      params.batch_size = args.global_batch
      params.log_every_n_steps = 1
      params.seed = 7
      if buckets:
        params.window_buckets = buckets
        params.num_hidden_layers = 1
        params.filter_size = 32
    out_dir = os.path.join(work, 'out')
    mesh = mesh_lib.make_mesh(
        dp=args.dp, tp=1, devices=jax.devices()[:args.dp])
    t0 = time.perf_counter()
    train_lib.run_training(
        params=params, out_dir=out_dir,
        train_patterns=train_patterns,
        eval_patterns=train_patterns[:1],
        num_epochs=1, mesh=mesh, eval_every=1_000_000)
    row['wall_s'] = round(time.perf_counter() - t0, 2)
    with open(os.path.join(out_dir, 'metrics.jsonl')) as f:
      entries = [json.loads(line) for line in f]
    losses = [e['loss'] for e in entries if e['split'] == 'train']
    faults = [e for e in entries if e['split'] == 'faults'][-1]
    row['examples_per_sec'] = round(n_examples / row['wall_s'], 1)
    row['loss_first'] = round(losses[0], 6) if losses else None
    row['loss_last'] = round(losses[-1], 6) if losses else None
    # The cross-dp identity observable: same global batch + same seed
    # reproduces this digest at every dp. Quantized at 1e-4 because
    # the cross-shard loss all-reduce changes summation order — curves
    # agree to ~1e-6 relative, not bitwise (the exact first/last
    # values above carry the raw comparison).
    row['loss_curve_digest_1e4'] = hashlib.sha256(
        json.dumps([round(l, 4) for l in losses]).encode()
    ).hexdigest()[:16]
    row['n_batches_prefetched'] = faults.get('n_batches_prefetched')
    row['train_transfer_overlap_fraction'] = faults.get(
        'train_transfer_overlap_fraction')
    if buckets:
      # Compile-once gate + the padding-waste A/B: measured fraction
      # under bucketing vs the arithmetic waste of padding the same
      # stream to the widest bucket (the old single-shape policy).
      row['n_train_forward_shapes'] = faults.get('n_train_forward_shapes')
      for width in buckets:
        row[f'n_train_batches_by_bucket_{width}'] = faults.get(
            f'n_train_batches_by_bucket_{width}')
      row['train_padding_fraction'] = faults.get('train_padding_fraction')
      wmax = max(buckets)
      padmax_pos = sum(
          (faults.get(f'n_train_batches_by_bucket_{w}', 0) or 0)
          * args.global_batch * wmax for w in buckets)
      real_pos = faults.get('n_train_window_positions', 0.0)
      padded = faults.get('n_train_padded_positions', 0.0)
      if padmax_pos:
        row['padding_fraction_padmax'] = round(
            1.0 - (real_pos - padded) / padmax_pos, 4)
  except Exception as e:  # keep the row; a failed point is a result
    row['error'] = repr(e)[:200]
  finally:
    shutil.rmtree(work, ignore_errors=True)
  print(json.dumps(row), flush=True)


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batches', type=int, nargs='+',
                  default=[256, 512, 1024])
  ap.add_argument('--steps', type=int, default=6)
  ap.add_argument('--scan', action='store_true',
                  help='pin the lax.scan DP instead of Pallas')
  ap.add_argument('--cpu', action='store_true')
  ap.add_argument('--dp', type=int, default=None,
                  help='dp-scaling mode: short real training run on a '
                  'dp=N mesh (one dp per process; sweep via fresh '
                  'subprocesses).')
  ap.add_argument('--global_batch', type=int, default=16,
                  help='dp mode: FIXED global batch across the sweep.')
  ap.add_argument('--train_steps', type=int, default=8,
                  help='dp mode: training steps per point.')
  ap.add_argument('--window_buckets', type=lambda s: tuple(
      int(w) for w in s.split(',')), default=None,
                  help='dp mode: comma-separated ascending bucket '
                  'widths (e.g. 100,200). Mixes one synthetic shard '
                  'set per width and reports the per-bucket compile '
                  'and padding counters.')
  ap.add_argument('--force_host_devices', type=int, default=None,
                  help='Fake N CPU devices (sets XLA_FLAGS; must be '
                  'set before jax initializes, i.e. via this flag, '
                  'not after).')
  args = ap.parse_args()

  if args.force_host_devices:
    os.environ['XLA_FLAGS'] = (
        os.environ.get('XLA_FLAGS', '')
        + f' --xla_force_host_platform_device_count='
        f'{args.force_host_devices}')
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')

  if args.dp:
    _run_dp_mode(args)
    return

  import numpy as np

  from scripts import _bench_common

  for batch in args.batches:
    trainer, state, rows_t, label = _bench_common.make_trainer_and_batch(
        batch, use_scan_dp=args.scan,
        out_dir='/tmp/dc_bench_train_scaling',
    )
    step_fn = _bench_common.make_scalar_step(state, trainer.loss_fn)
    row = {'batch': batch,
           'dp': 'scan' if args.scan else 'pallas(auto)'}
    try:
      t0 = time.perf_counter()
      out = step_fn(state, rows_t, label)
      [np.asarray(o) for o in out]
      row['compile_plus_first_step_s'] = round(time.perf_counter() - t0, 1)
      t0 = time.perf_counter()
      for i in range(args.steps):
        out = step_fn(state, rows_t.at[0, 0, 0, 0].set(float(i)), label)
        vals = [np.asarray(o) for o in out]
      dt = time.perf_counter() - t0
      row['examples_per_sec'] = round(batch * args.steps / dt, 1)
      row['loss'] = round(float(vals[0]), 3)
    except Exception as e:
      row['error'] = repr(e)[:200]
    print(json.dumps(row), flush=True)


if __name__ == '__main__':
  main()
