"""Generator `pileup_windows`: featurized window tensors from a seed.

One general generator; a traffic file gives its parameters. A window is
what the featurizer hands the engine: float32 [4*max_passes+5, L, 1] with
bases, PW, IP and strand rows for up to max_passes subreads, the CCS row
and four SN rows. Values keep to the ranges real data has, and absent
passes stay zero as the featurizer leaves them; it is not uniform noise.

The pool is drawn in one jitted call on the device (numpy takes tens of
seconds for 16,384 windows) and brought to the host once as uint8 rows
plus float32 SN scalars, which is every value the rows can hold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed


@functools.partial(jax.jit, static_argnames=(
    'n', 'max_passes', 'length', 'passes_min', 'passes_max'))
def _draw(key, *, n, max_passes, length, passes_min, passes_max, error_rate,
          insert_col_rate, partial_pass_rate, kinetics_mean, sn_min, sn_max):
  p = max_passes
  ks = list(jax.random.split(key, 16))
  uni = lambda shape: jax.random.uniform(ks.pop(), shape)
  n_pass = jax.random.randint(ks.pop(), (n,), passes_min, passes_max + 1)
  present = jnp.arange(p)[None, :] < n_pass[:, None]
  # Draft: ACGT ids 1..4; insertion columns are gaps (0) in the CCS row.
  draft = jax.random.randint(ks.pop(), (n, length), 1, 5)
  insert_col = uni((n, length)) < insert_col_rate
  ccs = jnp.where(insert_col, 0, draft)
  # Subreads: the draft with substitutions and deletions; at insertion
  # columns only a minority of passes carries a base.
  u = uni((n, p, length))
  sub = jax.random.randint(ks.pop(), (n, p, length), 1, 5)
  bases = jnp.broadcast_to(draft[:, None, :], (n, p, length))
  bases = jnp.where(u < error_rate * 0.3, sub, bases)
  bases = jnp.where((u >= error_rate * 0.3) & (u < error_rate), 0, bases)
  bases = jnp.where(insert_col[:, None, :] & (u > 0.25), 0, bases)
  # A share of passes covers only a prefix or a suffix of the window.
  partial = uni((n, p)) < partial_pass_rate
  cut = jax.random.randint(ks.pop(), (n, p), 1, length)
  left = uni((n, p)) < 0.5
  col = jnp.arange(length)[None, None, :]
  covered = jnp.where(left[:, :, None], col < cut[:, :, None],
                      col >= cut[:, :, None])
  covered = jnp.where(partial[:, :, None], covered, True)
  covered &= present[:, :, None]
  bases = jnp.where(covered, bases, 0)

  def kinetics():
    # Gamma(2, mean/2) as a sum of two exponentials: skewed, positive.
    e = -jnp.log1p(-uni((n, p, length))) - jnp.log1p(-uni((n, p, length)))
    k = jnp.minimum(1 + e * (kinetics_mean / 2.0), 255).astype(jnp.int32)
    return jnp.where(bases > 0, k, 0)

  strand = jnp.where(jnp.arange(p)[None, :] % 2 == 0, 1, 2)
  strand = jnp.where((uni((n,)) < 0.5)[:, None], 3 - strand, strand)
  strand = jnp.where(covered, strand[:, :, None], 0)
  main = jnp.concatenate(
      [bases, kinetics(), kinetics(), strand, ccs[:, None, :]], axis=1)
  sn = jax.random.uniform(ks.pop(), (n, 4), minval=sn_min, maxval=sn_max)
  return main.astype(jnp.uint8), sn


def make_windows(n: int, *, seed: int, max_passes: int, length: int,
                 passes_min: int, passes_max: int, error_rate: float,
                 insert_col_rate: float, partial_pass_rate: float,
                 kinetics_mean: float, sn_min: float, sn_max: float,
                 ) -> np.ndarray:
  """Returns [n, 4*max_passes+5, length, 1] float32 on the host."""
  main, sn = _draw(
      jax.random.fold_in(key_from_seed(seed), 0x77696E),
      n=n, max_passes=max_passes, length=length, passes_min=passes_min,
      passes_max=passes_max, error_rate=error_rate,
      insert_col_rate=insert_col_rate, partial_pass_rate=partial_pass_rate,
      kinetics_mean=kinetics_mean, sn_min=sn_min, sn_max=sn_max)
  main, sn = np.asarray(main), np.asarray(sn)
  rows = np.empty((n, 4 * max_passes + 5, length, 1), np.float32)
  rows[:, :4 * max_passes + 1, :, 0] = main
  rows[:, 4 * max_passes + 1:, :, 0] = sn[:, :, None]
  return rows


def make(shape: dict, traffic: dict, seed: int) -> np.ndarray:
  """The generator's entry: the traffic file's `pool_windows` windows."""
  return make_windows(
      int(traffic['pool_windows']), seed=seed,
      max_passes=shape['max_passes'], length=shape['max_length'],
      **traffic['generator_params'])
