"""Gated power retention of degree 2 in its two-direction quadratic form.

Power retention is a linear-attention layer: with `phi` the symmetric
second power of a vector (d(d+1)/2 features, so that
phi(a) . phi(b) = (a . b)^2) and a per-position gate g_t in (0, 1) it is
the recurrence

  S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
  y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps)

which, written over all pairs, is an attention with the weights

  a_ij = (q_i . k_j / sqrt(d))^2 * exp(G_i - G_j),  j <= i,
  G_t = sum_{m <= t} log g_m.

An encoder has no causal mask, so this operator runs the recurrence left
to right plus the same recurrence right to left, the j = i term counted
once, numerators and normalisers each summed before one division:

  a_ij = (q_i . k_j / sqrt(d))^2 * exp(-|G_i - G_j|)  over all i, j
  y_i  = sum_j a_ij v_j / (sum_j a_ij + eps)

Only the quadratic form is here, in plain jnp as XLA compiles it. Below
the state form's switch-over length (about 8 k positions at d = 128,
where 8,256 features a head pay off) the published operator takes the
quadratic form too, and this system feeds windows of 100 to 500
positions. Query head h reads key-value head h // (Hq // Hkv); the
repeat of k and v is never materialised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DEGREE = 2
NORMALISER_EPS = 1e-6


def power_retention_bidirectional(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, log_g: jnp.ndarray,
    eps: float = NORMALISER_EPS) -> jnp.ndarray:
  """q [B, L, Hq, D]; k, v [B, L, Hkv, D]; log_g [B, L, Hkv] float32
  (log of the gate, <= 0) -> y [B, L, Hq, D] in q's dtype.

  Scores, decay and normaliser are float32 whatever the operands' type;
  the weights meet v in v's type with a float32 accumulator."""
  b, length, n_q, d = q.shape
  n_kv = k.shape[2]
  if n_q % n_kv:
    raise ValueError(f'{n_q} query heads do not group over {n_kv} '
                     'key-value heads')
  qg = q.reshape(b, length, n_kv, n_q // n_kv, d)
  scores = jnp.einsum('blkgd,bmkd->bkglm', qg, k,
                      preferred_element_type=jnp.float32)
  scores = jnp.square(scores * jnp.float32(d ** -0.5))
  cum = jnp.cumsum(log_g.astype(jnp.float32), axis=1)  # G [B, L, Hkv]
  cum = jnp.transpose(cum, (0, 2, 1))
  decay = jnp.exp(-jnp.abs(cum[:, :, :, None] - cum[:, :, None, :]))
  weights = scores * decay[:, :, None]  # [B, Hkv, G, L, L]
  norm = jnp.sum(weights, axis=-1)  # [B, Hkv, G, L]
  out = jnp.einsum('bkglm,bmkd->blkgd', weights.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
  out = out / (jnp.transpose(norm, (0, 3, 1, 2))[..., None] + eps)
  return out.reshape(b, length, n_q, d).astype(q.dtype)
