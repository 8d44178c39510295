"""Test-local plain reference of the third encoder block kind
(`config.BLOCK_GATED_DELTA_MOE`): float32 jax.numpy, the Gated DeltaNet
mixer as the token-by-token recurrence, the experts as a plain loop. It
imports nothing from deepconsensus_tpu/models or deepconsensus_tpu/ops;
the benchmark keeps a copy of its own
(benchmark/families/gdn_moe_encoder.py).

norm(x, w) = x * rsqrt(mean(x^2) + eps) * (1 + w). A layer is
h = x + mixer(norm(x)); out = h + moe(norm(h)); the mixer by the pattern.

Gated DeltaNet mixer (u [L, H]): [q | k | v | z] = u W_qkvz, [b | a] =
u W_ba; [q | k | v] <- silu(causal depthwise convolution of concat(q, k,
v)); beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias); q, k
L2-normalised over the head, q scaled by Dk^-1/2; per value head (key head
h serving value heads h*G ... h*G + G - 1), S [Dk, Dv] from zero:

  S <- exp(g_t) S; d_t = beta_t (v_t - S^T k_t); S <- S + k_t d_t^T;
  o_t = S^T q_t

run over the window and (convolution and recurrence both) over the window
reversed, the two o added; y = o * rsqrt(mean(o^2) + eps) * w_o * silu(z)
over each head; mixer = concat(y) W_out.

Gated softmax attention: [q | gate] = u W_q per head, k = u W_k,
v = u W_v; q, k norm-ed over the head; rotate-half rotary on the first
`rotary_dim` of the head; softmax(q k^T / sqrt(D)) over the window, query
head h reading key-value head h // group; (attn * sigmoid(gate)) W_o.

Sparse experts: p = softmax(n W_r) over all E, the k largest renormalised;
moe(n) = sum over the top-k experts that lie in [first, first + held) of
p_e expert_e(n), plus sigmoid(n w_s) * shared(n); every expert a SwiGLU.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def norm(x, w, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta, rotary_dim):
  """x [B, L, N, D], positions 0..L-1: the first rotary_dim of D."""
  length = x.shape[1]
  inv = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)[None, :, None, :]
  head, rest = x[..., :rotary_dim], x[..., rotary_dim:]
  half = rotary_dim // 2
  rotated = jnp.concatenate([-head[..., half:], head[..., :half]], axis=-1)
  head = (head * np.cos(angles).astype(np.float32)
          + rotated * np.sin(angles).astype(np.float32))
  return jnp.concatenate([head, rest], axis=-1)


def causal_conv(x, kernel):
  """x [B, L, C], kernel [K, C]: y_t = sum_i kernel[i] x_{t-(K-1)+i}."""
  taps, length = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
  return sum(padded[:, i:i + length] * kernel[i] for i in range(taps))


def delta_recurrence(q, k, v, g, beta, correct=True):
  """The causal rule, token by token. q, k [B, L, Hk, Dk]; v
  [B, L, Hv, Dv]; g, beta [B, L, Hv] -> o [B, L, Hv, Dv]. `correct`
  False drops the delta correction (d_t = beta_t v_t)."""
  group = v.shape[2] // k.shape[2]
  q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)

  def step(state, xs):
    q_t, k_t, v_t, g_t, beta_t = xs  # [B, Hv, D], [B, Hv]
    state = state * jnp.exp(g_t)[..., None, None]
    seen = jnp.einsum('bhkv,bhk->bhv', state, k_t) if correct else 0.0
    d_t = beta_t[..., None] * (v_t - seen)
    state = state + k_t[..., :, None] * d_t[..., None, :]
    return state, jnp.einsum('bhkv,bhk->bhv', state, q_t)

  along = lambda a: jnp.moveaxis(a, 1, 0)
  zero = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                   jnp.float32)
  _, out = jax.lax.scan(step, zero, tuple(along(a) for a in (q, k, v, g, beta)))
  return jnp.moveaxis(out, 0, 1)


def gdn_mixer(w, u, *, hk, hv, dk, dv, eps, directions=(1, -1),
              correct=True):
  batch, length, _ = u.shape
  key_dim, value_dim = hk * dk, hv * dv
  qkvz = u @ w['in_proj_qkvz']['kernel']
  mixed, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., -value_dim:]
  b, a = jnp.split(u @ w['in_proj_ba']['kernel'], 2, axis=-1)
  beta = jax.nn.sigmoid(b)
  g = -jnp.exp(w['A_log']) * jax.nn.softplus(a + w['dt_bias'])
  unit = lambda t: t * jax.lax.rsqrt(
      jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
  out = 0.0
  for direction in directions:
    turn = (lambda t: t) if direction == 1 else (
        lambda t: jnp.flip(t, axis=1))
    conv = jax.nn.silu(causal_conv(turn(mixed), w['conv_kernel']))
    q = unit(conv[..., :key_dim].reshape(batch, length, hk, dk)) * dk ** -0.5
    k = unit(conv[..., key_dim:2 * key_dim].reshape(batch, length, hk, dk))
    v = conv[..., 2 * key_dim:].reshape(batch, length, hv, dv)
    out = out + turn(delta_recurrence(q, k, v, turn(g), turn(beta),
                                      correct=correct))
  out = out * jax.lax.rsqrt(
      jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
  out = out * w['norm_scale'] * jax.nn.silu(
      z.reshape(batch, length, hv, dv))
  return out.reshape(batch, length, value_dim) @ w['out_proj']['kernel']


def gated_attention(w, u, *, rotary_dim, theta, eps):
  q_gate = jnp.einsum('blh,hnd->blnd', u, w['query']['kernel'])
  d = q_gate.shape[-1] // 2
  q, gate = q_gate[..., :d], q_gate[..., d:]
  k = jnp.einsum('blh,hnd->blnd', u, w['key']['kernel'])
  v = jnp.einsum('blh,hnd->blnd', u, w['value']['kernel'])
  q = rotary(norm(q, w['query_norm']['scale'], eps), theta, rotary_dim)
  k = rotary(norm(k, w['key_norm']['scale'], eps), theta, rotary_dim)
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', q, k) * d ** -0.5
  out = jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), v)
  out = out * jax.nn.sigmoid(gate)
  return jnp.einsum('blnd,ndh->blh', out, w['output_transform']['kernel'])


def swiglu(x, gate, up, down):
  return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routed_experts(w, n, *, top_k, renormalise=True, first=0, shared=True):
  """n [T, H] tokens -> (moe(n) [T, H], assignments per held expert). The
  experts as a plain loop: rows routed to e, its three products,
  scatter-add. w's expert leaves hold experts first ... first + held - 1
  of the router's width."""
  probs = jax.nn.softmax(n @ w['router']['kernel'], axis=-1)
  top_p, top_e = jax.lax.top_k(probs, top_k)
  if renormalise:
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
  top_p, top_e = np.asarray(top_p), np.asarray(top_e)
  held = w['experts_gate'].shape[0]
  out = np.zeros(n.shape, np.float32)
  counts = np.zeros(held, np.int64)
  for e in range(held):
    token, slot = np.nonzero(top_e == first + e)
    counts[e] = len(token)
    if len(token):
      y = swiglu(n[token], w['experts_gate'][e], w['experts_up'][e],
                 w['experts_down'][e])
      # A token names an expert at most once: plain indexed addition.
      out[token] += top_p[token, slot][:, None] * np.asarray(y)
  out = jnp.asarray(out)
  if shared:
    s = w['shared_expert']
    out = out + jax.nn.sigmoid(n @ w['shared_expert_gate']['kernel']) * swiglu(
        n, s['gate_layer']['kernel'], s['up_layer']['kernel'],
        s['output_layer']['kernel'])
  return out, counts


def _embed(table, ids):
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(table.shape[1]))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  emb = _embed(table, rows[:, lo:hi, :].astype(jnp.int32))
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits(params, rows, *, max_passes, pattern, hk, hv, dk, dv, rotary_dim,
           theta, eps, top_k, renormalise=True, first=0):
  """rows [B, 4*max_passes+5, L] float32 -> (logits [B, L, 5], assignments
  [layers, held]). `pattern`: one letter a layer, 'S' gated softmax
  attention, anything else the Gated DeltaNet mixer. Not jitted: the
  experts' loop reads the routing on the host."""
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
  counts = []
  for n, letter in enumerate(pattern):
    u = norm(x, enc[f'attention_wrapper_{n}']['rms_norm']['scale'], eps)
    if letter == 'S':
      x = x + gated_attention(enc[f'gated_attention_{n}'], u,
                              rotary_dim=rotary_dim, theta=theta, eps=eps)
    else:
      x = x + gdn_mixer(enc[f'gdn_{n}'], u, hk=hk, hv=hv, dk=dk, dv=dv,
                        eps=eps)
    h = norm(x, enc[f'ffn_wrapper_{n}']['rms_norm']['scale'], eps)
    routed, took = routed_experts(
        enc[f'moe_{n}'], h.reshape(-1, h.shape[-1]), top_k=top_k,
        renormalise=renormalise, first=first)
    x = x + routed.reshape(x.shape)
    counts.append(took)
  x = norm(x, enc['output_normalization']['scale'], eps)
  return (x @ params['logits']['kernel'] + params['logits']['bias'],
          np.stack(counts))
