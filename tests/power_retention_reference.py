"""Test-local plain reference of the power-retention encoder block kind
(`config.BLOCK_POWER_RETENTION`): float32 jax.numpy for the forward,
float64 numpy for the token-by-token recurrence. It imports nothing from
deepconsensus_tpu/models or deepconsensus_tpu/ops; the benchmark keeps a
copy of its own (benchmark/families/power_retention_encoder.py), which
benchmark/tests hold to the same identities.

Per window (x [L, H] from the condenser, positions 0..L-1), a layer is

  u = RMSNorm(x); q = u W_q [L, Hq, D]; k = u W_k, v = u W_v [L, Hkv, D]
  q, k: RMSNorm over D, then rotate-half rotary positions
  log g = logsigmoid(u W_g + b_g) [L, Hkv]; G_t = sum_{m<=t} log g_m
  a_ij = (q_i . k_j / sqrt(D))^2 * exp(-|G_i - G_j|)   (two directions)
       = (q_i . k_j / sqrt(D))^2 * exp(G_i - G_j), j <= i  (causal, as
         published)
  y_i = sum_j a_ij v_j / (sum_j a_ij + eps), head h reads kv head h // group
  h = x + y W_o; out = h + (silu(n W_gate) * (n W_up)) W_down, n = RMSNorm(h)
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def rms_norm(x, scale, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rotary(x, theta):
  """x [B, L, N, D], positions 0..L-1."""
  length, d = x.shape[1], x.shape[3]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)[None, :, None, :]
  rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
  return (x * np.cos(angles).astype(np.float32)
          + rotated * np.sin(angles).astype(np.float32))


def retention_quadratic(q, k, v, log_g, causal=False, eps=EPS):
  """q [B, L, Hq, D]; k, v [B, L, Hkv, D]; log_g [B, L, Hkv]."""
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  cum = jnp.transpose(
      jnp.repeat(jnp.cumsum(log_g, axis=1), group, axis=2), (0, 2, 1))
  scores = jnp.square(
      jnp.einsum('bihd,bjhd->bhij', q, k) * q.shape[3] ** -0.5)
  diff = cum[:, :, :, None] - cum[:, :, None, :]
  if causal:
    i = np.arange(q.shape[1])
    decay = jnp.where(i[:, None] >= i[None, :], jnp.exp(diff), 0.0)
  else:
    decay = jnp.exp(-jnp.abs(diff))
  weights = scores * decay
  out = jnp.einsum('bhij,bjhd->bihd', weights, v)
  norm = jnp.transpose(jnp.sum(weights, axis=-1), (0, 2, 1))
  return out / (norm[..., None] + eps)


def power_features(a):
  """phi(a): [..., D] -> [..., D(D+1)/2], phi(a) . phi(b) = (a . b)^2."""
  i, j = np.triu_indices(a.shape[-1])
  return a[..., i] * a[..., j] * np.where(i == j, 1.0, math.sqrt(2.0))


def retention_recurrence(q, k, v, log_g, causal=False, eps=EPS):
  """S_t = g_t S_{t-1} + phi(k_t) v_t^T, z_t = g_t z_{t-1} + phi(k_t),
  y_t = phi(q_t)^T S_t / (phi(q_t) . z_t + eps), q and k scaled by
  D^(-1/4); float64. Two directions: the run left to right plus the run
  right to left (a state decays by the gate of the position it leaves),
  the j = i term once, one division."""
  q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
  gate = np.exp(np.asarray(log_g, np.float64))
  b, length, n_q, d = q.shape
  group = n_q // k.shape[2]
  phi_q, phi_k = (power_features(a * d ** -0.25) for a in (q, k))
  num = np.zeros((b, length, n_q, d))
  den = np.zeros((b, length, n_q))
  for h in range(n_q):
    kv = h // group
    for step in ((1,) if causal else (1, -1)):
      state = np.zeros((b, phi_k.shape[-1], d))
      z = np.zeros((b, phi_k.shape[-1]))
      for t in (range(length) if step == 1 else range(length - 1, -1, -1)):
        if step == 1:
          g = gate[:, t, kv]
        else:
          g = gate[:, t + 1, kv] if t + 1 < length else np.ones(b)
        state = (g[:, None, None] * state
                 + phi_k[:, t, kv, :, None] * v[:, t, kv, None, :])
        z = g[:, None] * z + phi_k[:, t, kv]
        num[:, t, h] += np.einsum('bf,bfd->bd', phi_q[:, t, h], state)
        den[:, t, h] += np.einsum('bf,bf->b', phi_q[:, t, h], z)
    if not causal:
      own = np.einsum('blf,blf->bl', phi_q[:, :, h], phi_k[:, :, kv])
      num[:, :, h] -= own[..., None] * v[:, :, kv]
      den[:, :, h] -= own
  return num / (den[..., None] + eps)


def _embed(table, ids):
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(table.shape[1]))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  emb = _embed(table, rows[:, lo:hi, :].astype(jnp.int32))
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits(params, rows, *, max_passes, num_layers, rope_theta, eps):
  """rows [B, 4*max_passes+5, L] float32 -> logits [B, L, 5]."""
  p = max_passes
  table = lambda name: params[name + '_embedding']['embedding']
  x = jnp.concatenate([
      _family(table('bases'), rows, 0, p),
      _family(table('pw'), rows, p, 2 * p),
      _family(table('ip'), rows, 2 * p, 3 * p),
      _family(table('strand'), rows, 3 * p, 4 * p),
      _family(table('bases'), rows, 4 * p, 4 * p + 1),
      _family(table('sn'), rows, 4 * p + 1, 4 * p + 5),
  ], axis=-1) @ params['condenser']['kernel']
  enc = params['encoder']
  mm = lambda a, w: jnp.einsum('blh,h...->bl...', a, w)
  for n in range(num_layers):
    att, ffn = enc[f'self_attention_{n}'], enc[f'ffn_{n}']
    u = rms_norm(x, enc[f'attention_wrapper_{n}']['rms_norm']['scale'], eps)
    q = rms_norm(mm(u, att['query']['kernel']), att['query_norm']['scale'],
                 eps)
    k = rms_norm(mm(u, att['key']['kernel']), att['key_norm']['scale'], eps)
    v = mm(u, att['value']['kernel'])
    log_g = jax.nn.log_sigmoid(
        mm(u, att['gate']['kernel']) + att['gate']['bias'])
    y = retention_quadratic(rotary(q, rope_theta), rotary(k, rope_theta), v,
                            log_g)
    x = x + jnp.einsum('blnd,ndh->blh', y, att['output_transform']['kernel'])
    h = rms_norm(x, enc[f'ffn_wrapper_{n}']['rms_norm']['scale'], eps)
    x = x + mm(jax.nn.silu(mm(h, ffn['gate_layer']['kernel']))
               * mm(h, ffn['up_layer']['kernel']),
               ffn['output_layer']['kernel'])
  x = rms_norm(x, enc['output_normalization']['scale'], eps)
  return x @ params['logits']['kernel'] + params['logits']['bias']
