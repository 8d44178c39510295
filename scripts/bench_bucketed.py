"""Bucketed-dispatch A/B: pad-to-max vs per-bucket packing.

Drives one mixed-length window stream (default 70% L=100, 30% L=200)
through the ConsensusEngine twice on the same weights: once with a
single max-width bucket (every window padded to the largest length —
the pre-round-12 policy) and once with the configured buckets. Prints
one JSON line per variant (windows/s, padded-position fraction,
per-bucket pack counts, compile count) plus a summary line with the
measured speedup, the padding reduction, and a per-bucket
byte-identity verdict: each bucket's windows must come back identical
to the same windows run through a dedicated single-bucket engine.
Exit 1 = identity violation — investigate before reading the perf
numbers. The padded-position fraction is stream arithmetic
(backend-independent); the windows/s delta means something only on a
TPU and is not measured yet.
"""
import argparse
import json
import time


def _make_engine(engine_lib, runner_lib, params, variables, batch, buckets):
  options = runner_lib.InferenceOptions(
      batch_size=batch, max_passes=params.max_passes,
      max_length=params.max_length, use_ccs_bq=params.use_ccs_bq)
  options.window_buckets = buckets
  runner = runner_lib.ModelRunner(params, dict(variables), options,
                                  mesh=None)
  delivered = {}
  engine = engine_lib.ConsensusEngine(
      runner, options,
      deliver=lambda t, ids, quals: delivered.__setitem__(t, (ids, quals)))
  return engine, delivered


def _run_stream(engine, delivered, stream, warmup_shapes, params, np):
  import numpy as _np

  del np
  for b, batch in warmup_shapes:
    engine.runner.predict(
        _np.zeros((batch, params.total_rows, b, 1), _np.float32))
  delivered.clear()
  t0 = time.perf_counter()
  engine.submit(stream, list(range(len(stream))))
  engine.flush()
  return time.perf_counter() - t0


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batch', type=int, default=1024)
  ap.add_argument('--windows', type=int, default=4096)
  ap.add_argument('--long_frac', type=float, default=0.3,
                  help='fraction of windows at the largest bucket')
  ap.add_argument('--buckets', default='',
                  help='comma-separated lengths; default from config')
  ap.add_argument('--config', default='transformer_learn_values+test')
  ap.add_argument('--fused', action='store_true',
                  help='enable the fused hot path (per-bucket eligible: '
                       'only traces at L <= the VMEM limit use it)')
  args = ap.parse_args()

  import jax
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.inference import engine as engine_lib
  from deepconsensus_tpu.inference import runner as runner_lib
  from deepconsensus_tpu.models import config as config_lib
  from deepconsensus_tpu.models import model as model_lib

  params = config_lib.get_config(args.config)
  if args.fused:
    with params.unlocked():
      params.use_fused_hotpath = True
  config_lib.finalize_params(params, is_training=False)
  buckets = (tuple(int(b) for b in args.buckets.split(','))
             if args.buckets else config_lib.DEFAULT_WINDOW_BUCKETS)
  buckets = config_lib.normalize_window_buckets(buckets, params.max_length)
  max_b = max(buckets)
  variables = model_lib.get_model(params).init(
      jax.random.PRNGKey(0),
      jnp.zeros((1, params.total_rows, params.max_length, 1)))

  rng = np.random.default_rng(12)
  probs = np.full(len(buckets), (1 - args.long_frac) / max(1, len(buckets) - 1))
  probs[-1] = args.long_frac
  widths = rng.choice(buckets, size=args.windows, p=probs)
  wins = [rng.integers(0, 5, size=(params.total_rows, int(w), 1))
          .astype(np.float32) for w in widths]
  padded = [np.pad(w, ((0, 0), (0, max_b - w.shape[1]), (0, 0)))
            for w in wins]
  useful = int(widths.sum())

  results = {}
  deliveries = {}
  for name, variant_buckets, stream in (
      ('pad_to_max', (max_b,), padded),
      ('bucketed', buckets, wins)):
    engine, delivered = _make_engine(
        engine_lib, runner_lib, params, variables, args.batch,
        variant_buckets)
    dt = _run_stream(engine, delivered,
                     stream, [(b, args.batch) for b in variant_buckets],
                     params, np)
    stats = engine.stats()
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
        'config': args.config,
        'fused': args.fused,
    }
    results[name] = line
    deliveries[name] = dict(delivered)
    print(json.dumps(line), flush=True)

  # Per-bucket byte identity: each width's windows through a dedicated
  # single-bucket engine must match the bucketed run's deliveries.
  identical = True
  for b in buckets:
    idx = [i for i, w in enumerate(widths) if w == b]
    if not idx:
      continue
    solo_engine, solo_delivered = _make_engine(
        engine_lib, runner_lib, params, variables, args.batch, (int(b),))
    _run_stream(solo_engine, solo_delivered, [wins[i] for i in idx],
                [(int(b), args.batch)], params, np)
    for k, i in enumerate(idx):
      got = deliveries['bucketed'][i]
      want = solo_delivered[k]
      if not (np.array_equal(got[0], want[0])
              and np.array_equal(got[1], want[1])):
        identical = False
        break

  pad, buck = results['pad_to_max'], results['bucketed']
  print(json.dumps({
      'summary': 'bucketed_ab',
      'speedup_bucketed': round(
          buck['windows_per_sec'] / pad['windows_per_sec'], 3),
      'padding_reduction': round(
          pad['padded_position_fraction']
          - buck['padded_position_fraction'], 4),
      'byte_identical_per_bucket': identical,
  }), flush=True)
  return 0 if identical else 1


if __name__ == '__main__':
  raise SystemExit(main())
