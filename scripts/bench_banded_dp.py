"""Banded alignment-DP A/B: lax.scan vs the Pallas band kernel
(VERDICT r4 #4).

Times forward and forward+grad at a production-ish shape on whatever
backend jax selects (Pallas kernels run in interpret mode on CPU, so
CPU numbers measure correctness plumbing, not kernel speed; the
decision number is the TPU run). Prints one JSON
line per leg.
"""
import argparse
import json
import time


def bench(fn, args, steps):
  import jax

  out = fn(*args)
  jax.block_until_ready(out)
  t0 = time.perf_counter()
  for _ in range(steps):
    out = fn(*args)
  jax.block_until_ready(out)
  return (time.perf_counter() - t0) / steps


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--batch', type=int, default=256)
  ap.add_argument('--m', type=int, default=120)
  ap.add_argument('--widths', type=int, nargs='+', default=[2, 4, 8])
  ap.add_argument('--loss_reg', type=float, default=0.1)
  ap.add_argument('--steps', type=int, default=5)
  ap.add_argument('--cpu', action='store_true',
                  help='force the CPU backend')
  args = ap.parse_args()

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  import numpy as np

  from deepconsensus_tpu.ops import wavefront, wavefront_pallas as wp

  backend = jax.devices()[0].platform
  rng = np.random.default_rng(0)
  b, m = args.batch, args.m
  subs = jnp.asarray(rng.uniform(0, 5, size=(b, m, m)).astype(np.float32))
  ins = jnp.asarray(rng.uniform(0, 5, size=(b, m)).astype(np.float32))
  lens = jnp.asarray(rng.integers(m // 2, m + 1, size=b).astype(np.int32))
  reg = args.loss_reg
  minop = lambda t: -reg * jax.nn.logsumexp(-t / reg, axis=0)

  for width in args.widths:
    legs = {
        'scan_fwd': jax.jit(lambda s, i, w=width: wavefront.
                            banded_alignment_scan(
                                s, i, jnp.float32(3.0), lens, w, minop)),
        'pallas_fwd': jax.jit(lambda s, i, w=width: wp.
                              banded_alignment_scores(
                                  s, i, 3.0, lens, w, loss_reg=reg,
                                  interpret=backend != 'tpu')),
        'scan_grad': jax.jit(jax.grad(
            lambda s, i, w=width: jnp.sum(wavefront.banded_alignment_scan(
                s, i, jnp.float32(3.0), lens, w, minop)), argnums=(0, 1))),
        'pallas_grad': jax.jit(jax.grad(
            lambda s, i, w=width: jnp.sum(wp.banded_alignment_scores_vjp(
                s, i, lens, 3.0, reg, w)), argnums=(0, 1))),
    }
    times = {}
    for name, fn in legs.items():
      try:
        times[name] = bench(fn, (subs, ins), args.steps)
      except Exception as e:  # pragma: no cover
        times[name] = None
        print(json.dumps({'leg': name, 'width': width,
                          'error': repr(e)[:200]}), flush=True)
    row = {
        'backend': backend, 'batch': b, 'm': m, 'width': width,
        'loss_reg': reg, 'steps': args.steps,
        'interpret_mode': backend != 'tpu',
    }
    for name, t in times.items():
      if t is not None:
        row[f'{name}_ms'] = round(t * 1e3, 2)
    if times.get('scan_grad') and times.get('pallas_grad'):
      row['pallas_grad_speedup'] = round(
          times['scan_grad'] / times['pallas_grad'], 3)
    if times.get('scan_fwd') and times.get('pallas_fwd'):
      row['pallas_fwd_speedup'] = round(
          times['scan_fwd'] / times['pallas_fwd'], 3)
    print(json.dumps(row), flush=True)
  return 0


if __name__ == '__main__':
  raise SystemExit(main())
