"""A/B the banded-attention implementations across window lengths.

Times, per window length L (constant total tokens B*L):
  * xla      — reference_banded_attention (XLA fuses the dense band)
  * fused    — whole-L VMEM kernel (ops/banded_attention.py)
  * flash    — block-banded flash kernel (ops/flash_band_attention.py)

The flagship pileup window is L=100 where XLA wins (measured 0.82x for
the fused kernel); the flash kernel is the long-window path, where the
dense [L, L] band becomes O(L^2) waste. Prints one JSON line per L so
partial runs keep completed rows.
"""
import argparse
import json
import time


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--tokens', type=int, default=1 << 17,
                  help='total tokens per call: batch = tokens // L')
  ap.add_argument('--heads', type=int, default=2)
  ap.add_argument('--dim', type=int, default=140,
                  help='per-head width (flagship: hidden 280 / 2 heads)')
  ap.add_argument('--win', type=int, default=12)
  ap.add_argument('--lengths', type=int, nargs='+',
                  default=[100, 256, 512, 1024, 2048, 4096])
  ap.add_argument('--iters', type=int, default=20)
  ap.add_argument('--cpu', action='store_true')
  args = ap.parse_args()

  import jax

  if args.cpu:
    jax.config.update('jax_platforms', 'cpu')
  import jax.numpy as jnp
  import numpy as np
  from deepconsensus_tpu.ops import banded_attention as ba
  from deepconsensus_tpu.ops import flash_band_attention as fba

  def timed(fn, q, k, v):
    out = fn(q, k, v)
    np.asarray(out)
    t0 = time.perf_counter()
    for i in range(args.iters):
      out = fn(q.at[0, 0, 0, 0].set(float(i)), k, v)
    np.asarray(out)
    return (time.perf_counter() - t0) / args.iters

  for l in args.lengths:
    b = max(1, args.tokens // l)
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.normal(size=(b, l, args.heads, args.dim)).astype(np.float32)
    ).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    row = {'L': l, 'batch': b, 'tokens': b * l}
    impls = {
        'xla': jax.jit(
            lambda q, k, v: ba.reference_banded_attention(q, k, v, args.win)
        ),
        'flash': jax.jit(
            lambda q, k, v: fba.flash_band_attention(q, k, v, args.win)
        ),
    }
    if l <= 512:  # whole-L kernel: [G, L, L] must fit VMEM
      impls['fused'] = jax.jit(
          lambda q, k, v: ba.banded_attention(q, k, v, args.win)
      )
    for name, fn in impls.items():
      try:
        dt = timed(fn, q, k, v)
        row[f'{name}_us'] = round(dt * 1e6, 1)
        row[f'{name}_tokens_per_s'] = round(b * l / dt)
      except Exception as e:
        row[f'{name}_error'] = repr(e)[:120]
    if 'xla_us' in row and 'flash_us' in row:
      row['flash_speedup_vs_xla'] = round(row['xla_us'] / row['flash_us'], 3)
    print(json.dumps(row), flush=True)


if __name__ == '__main__':
  main()
