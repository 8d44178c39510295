"""PRNG keys from `--seed`, for whatever the benchmark draws from it."""
from __future__ import annotations

import jax


def key_from_seed(seed: int):
  """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
  seed = int(seed)
  key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
  return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
