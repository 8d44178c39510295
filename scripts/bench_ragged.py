"""Ragged-dispatch A/B: per-bucket packer fleet vs one ragged stream.

Drives one mixed-length window stream (default 70% L=100, 30% L=200)
through the ConsensusEngine twice on the same weights: once with the
per-bucket packers (the round-12 policy — one compiled forward per
bucket) and once with use_ragged_kernel (ONE pack stream, every width
packed back-to-back into fixed [n_slots, R, slot_len] slots, a single
compiled forward for the whole run). Prints one JSON line per variant
(windows/s, padded-position fraction, per-bucket pack counts,
n_forward_shapes) plus a summary
line with the measured speedup, the padding delta, and a delivery
byte-identity verdict: every window's (ids, quals) from the ragged run
must be identical to the bucketed run's. Exit 1 = identity violation
or the ragged run compiled more than one forward shape — investigate
before reading the perf numbers.

The padded-position fraction and n_forward_shapes are stream
arithmetic (backend-independent); the windows/s delta means something
only on a TPU and is not measured yet. Where the host's time goes a
pack is `dctpu trace` of a traced run (self time per stage); whether the
device idles is read off the device's own trace (benchmark/).
"""
import argparse
import json
import time


def _fake_rows(params, np, width, batch, seed):
  """Featurized rows at an arbitrary width with the SN rows constant
  per window across positions, as the real featurizer emits them (the
  ragged dispatch ships one SN scalar per window)."""
  rng = np.random.default_rng(seed)
  rows = np.zeros((batch, params.total_rows, width, 1), dtype=np.float32)
  mp = params.max_passes
  rows[:, :mp] = rng.integers(0, 5, size=rows[:, :mp].shape)
  rows[:, mp:2 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 2 * mp:3 * mp] = rng.integers(0, 256, size=rows[:, :mp].shape)
  rows[:, 3 * mp:4 * mp] = rng.integers(0, 3, size=rows[:, :mp].shape)
  rows[:, 4 * mp] = rng.integers(0, 5, size=rows[:, 4 * mp].shape)
  if params.use_ccs_bq:
    rows[:, 4 * mp + 1] = rng.integers(
        -1, params.CCS_BQ_MAX - 1, size=rows[:, 4 * mp + 1].shape)
    sn_lo = 4 * mp + 2
  else:
    sn_lo = 4 * mp + 1
  sn = rng.integers(0, 501, size=(batch, rows.shape[1] - sn_lo, 1, 1))
  rows[:, sn_lo:] = np.broadcast_to(sn, rows[:, sn_lo:].shape)
  return rows


def _mixed_stream(params, np, buckets, n_windows, long_frac, seed=12):
  """n_windows featurized rows with widths drawn from buckets
  (long_frac at the largest), interleaved pseudo-randomly."""
  rng = np.random.default_rng(seed)
  probs = np.full(len(buckets),
                  (1 - long_frac) / max(1, len(buckets) - 1))
  probs[-1] = long_frac
  widths = rng.choice(buckets, size=n_windows, p=probs)
  pools = {int(b): list(_fake_rows(params, np, int(b),
                                   int((widths == b).sum()), 100 + i))
           for i, b in enumerate(buckets) if (widths == b).any()}
  stream = [pools[int(w)].pop() for w in widths]
  return stream, widths


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batch', type=int, default=1024)
  ap.add_argument('--windows', type=int, default=4096)
  ap.add_argument('--long_frac', type=float, default=0.3,
                  help='fraction of windows at the largest bucket')
  ap.add_argument('--buckets', default='',
                  help='comma-separated lengths; default from config')
  ap.add_argument('--config', default='transformer_learn_values+test')
  ap.add_argument('--depth', type=int, default=2,
                  help='dispatch_depth (packs in flight)')
  ap.add_argument('--out', default='',
                  help='also write the summary dict to this JSON path')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.inference import engine as engine_lib
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib

  params = config_lib.get_config(args.config)
  config_lib.finalize_params(params, is_training=False)
  buckets = (tuple(int(b) for b in args.buckets.split(','))
             if args.buckets else config_lib.DEFAULT_WINDOW_BUCKETS)
  buckets = config_lib.normalize_window_buckets(buckets, params.max_length)
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))

  stream, widths = _mixed_stream(params, np, buckets, args.windows,
                                 args.long_frac)
  useful = int(widths.sum())

  results = {}
  deliveries = {}
  for name, use_ragged in (('bucketed', False), ('ragged', True)):
    options = runner_lib.InferenceOptions(
        batch_size=args.batch, max_passes=params.max_passes,
        max_length=params.max_length, use_ccs_bq=params.use_ccs_bq,
        dispatch_depth=args.depth, window_buckets=buckets,
        use_ragged_kernel=use_ragged)
    runner = runner_lib.ModelRunner(params, dict(variables), options,
                                    mesh=None)
    delivered = {}
    engine = engine_lib.ConsensusEngine(
        runner, options,
        deliver=lambda t, ids, quals, d=delivered: d.__setitem__(
            t, (ids.copy(), quals.copy())))
    # Warm every executable BEFORE the trace starts so compile time
    # lands in neither the windows/s number nor the gap spans. The
    # ragged warmup must dispatch at the packer's exact slot geometry
    # or it would add a second entry to n_forward_shapes.
    if use_ragged:
      packer = engine._packer_for(buckets[0])
      wps = packer.slot_len // buckets[0]
      warm_rows = np.zeros(
          (packer.n_slots, params.total_rows, packer.slot_len, 1),
          np.float32)
      warm_lengths = np.full((packer.n_slots, wps), buckets[0], np.int32)
      runner.finalize(runner.dispatch_ragged(warm_rows, warm_lengths))
    else:
      for b in buckets:
        runner.predict(
            np.zeros((args.batch, params.total_rows, b, 1), np.float32))
    t0 = time.perf_counter()
    engine.submit_formatted(stream, list(range(args.windows)))
    engine.flush()
    dt = time.perf_counter() - t0
    stats = engine.stats()
    if use_ragged:
      rp = engine._ragged_packer
      dispatched = stats['n_packs_by_bucket'][rp.slot_len] * (
          rp.n_slots * rp.slot_len)
    else:
      dispatched = sum(stats['n_packs_by_bucket'][b] * args.batch * b
                       for b in stats['n_packs_by_bucket'])
    line = {
        'variant': name,
        'backend': jax.devices()[0].platform,
        'batch': args.batch,
        'windows': args.windows,
        'windows_per_sec': round(args.windows / dt, 1),
        'padded_position_fraction': round(1 - useful / dispatched, 4),
        'n_packs_by_bucket': {int(b): int(n) for b, n
                              in stats['n_packs_by_bucket'].items()},
        'n_forward_shapes': stats.get('n_forward_shapes', 0),
        'n_starvation_flushes': stats.get('n_starvation_flushes', 0),
        'config': args.config,
    }
    results[name] = line
    deliveries[name] = dict(delivered)
    print(json.dumps(line), flush=True)

  # Delivery byte identity: the ragged stream must hand back exactly
  # the bucketed fleet's (ids, quals) for every window.
  identical = len(deliveries['bucketed']) == len(deliveries['ragged'])
  if identical:
    for t, (ids, quals) in deliveries['bucketed'].items():
      got = deliveries['ragged'].get(t)
      if got is None or not (np.array_equal(ids, got[0])
                             and np.array_equal(quals, got[1])):
        identical = False
        break

  buck, rag = results['bucketed'], results['ragged']
  one_shape = rag['n_forward_shapes'] == 1
  summary = {
      'summary': 'ragged_ab',
      'speedup_ragged': round(
          rag['windows_per_sec'] / buck['windows_per_sec'], 3),
      'padding_reduction': round(
          buck['padded_position_fraction']
          - rag['padded_position_fraction'], 4),
      'forward_shapes_collapsed': f'{buck["n_forward_shapes"]} -> '
                                  f'{rag["n_forward_shapes"]}',
      'byte_identical': identical,
      'ragged_single_shape': one_shape,
  }
  print(json.dumps(summary), flush=True)
  if args.out:
    with open(args.out, 'w') as f:
      json.dump({'variants': results, **summary}, f, indent=2)
  return 0 if identical and one_shape else 1


if __name__ == '__main__':
  raise SystemExit(main())
