"""Test-local plain reference of the seventh encoder block kind
(`config.BLOCK_MAMBA_HYBRID`): float32 jax.numpy, written from the published
Jamba modelling (`transformers` `modeling_jamba.py`: `JambaMambaMixer.
slow_forward`, `JambaAttention`, `JambaMLP`, `JambaRMSNorm`) with the scan
as a plain loop over positions. It imports nothing from
deepconsensus_tpu/models or deepconsensus_tpu/ops; the benchmark keeps a
copy of its own (benchmark/families/ssm_hybrid_encoder.py).

RMS(x, w) = x * rsqrt(mean(x^2) + eps) * w (plain weights). A layer is
h = x + mixer_n(RMS(x)), out = h + SwiGLU(RMS(h)); a final RMS. Layer n's
mixer is attention where n % period == offset ('F'), Mamba otherwise ('M').

Mamba (u [L, H], inner width I, state S, dt rank R):
  [x | z] = u W_in
  per direction: c = silu(conv(x) + b_conv), the depthwise causal
  convolution of K taps (out[t] = sum_k w[k] x[t - K + 1 + k]);
  [delta | B | C] = c W_x; delta, B, C each RMS'd with a weight of its own;
  Delta = softplus(delta W_dt + b_dt); A = -exp(A_log);
  h_t = exp(Delta_t A) h_{t-1} + Delta_t c_t B_t, y_t = h_t C_t + D c_t
  (from a zero state, position by position);
  out = (y_fwd + y_bwd) * silu(z) W_out.

Attention: q = u W_q, k = u W_k, v = u W_v, no biases, no positions; k
and v repeated to the query heads; softmax(q_h k^T * D^-1/2) v over the
whole window; concat_h W_o.

Departures (the program's and this reference's alike): an encoder has no
causal mask, so the Mamba mixer runs the published causal mixer over the
window and over the window reversed (its output reversed back), the same
convolution weights and every leaf shared, each direction with its own D
term, the two outputs added before the gate; the attention has no mask;
no vocabulary: the pile-up embedding and the 5-way head of this system in
front and behind.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

MAMBA, ATTENTION = 'M', 'F'


def rms_norm(x, w, eps):
  return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                           + eps) * w


def causal_conv(x, kernel, bias):
  """x [B, L, C], kernel [K, C], bias [C] -> the published depthwise causal
  convolution cut to the window: out[t] = sum_k kernel[k] x[t - K + 1 + k]
  + bias, positions before the window zero."""
  taps, length = kernel.shape[0], x.shape[1]
  out = jnp.zeros(x.shape, jnp.float32) + bias
  for k in range(taps):
    shift = taps - 1 - k  # tap k reads `shift` positions back
    behind = jnp.concatenate(
        [jnp.zeros((x.shape[0], shift, x.shape[2]), x.dtype),
         x[:, :length - shift]], axis=1)
    out = out + kernel[k] * behind
  return out


def scan(x, delta, a, b, c, d, *, state_dtype=jnp.float32):
  """The published selective scan over one direction, position by
  position from a zero state: x, delta [B, L, I]; a [I, S]; b, c
  [B, L, S]; d [I] -> y [B, L, I]. `state_dtype` bfloat16, a fault: the
  decay and the state held in bfloat16."""
  rd = lambda t: t.astype(state_dtype).astype(jnp.float32)
  h = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
  ys = []
  for t in range(x.shape[1]):
    decay = rd(jnp.exp(delta[:, t, :, None] * a))
    h = rd(decay * h + (delta[:, t] * x[:, t])[..., None] * b[:, t, None, :])
    ys.append(jnp.einsum('bis,bs->bi', h, c[:, t]) + d * x[:, t])
  return jnp.stack(ys, axis=1)


def mamba(w, u, *, eps, directions=2, skip=True, state_dtype=jnp.float32):
  """The mixer on the normed stream u [B, L, H]. Faults: `directions` 1
  (the published causal mixer alone), `skip` False (no D term),
  `state_dtype` (see `scan`)."""
  inner = w['A_log'].shape[0]
  rank = w['dt_proj']['kernel'].shape[0]
  s = w['A_log'].shape[1]
  xz = u @ w['in_proj']['kernel']
  x, z = xz[..., :inner], xz[..., inner:]
  a = -jnp.exp(w['A_log'])
  d = w['D'] if skip else jnp.zeros_like(w['D'])
  flip = lambda t: jnp.flip(t, axis=1)
  out = 0.0
  for direction in range(directions):
    turned = flip if direction else (lambda t: t)
    c = jax.nn.silu(causal_conv(turned(x), w['conv1d']['kernel'],
                                w['conv1d']['bias']))
    parts = c @ w['x_proj']['kernel']
    delta = rms_norm(parts[..., :rank], w['dt_layernorm']['scale'], eps)
    b = rms_norm(parts[..., rank:rank + s], w['b_layernorm']['scale'], eps)
    cc = rms_norm(parts[..., rank + s:], w['c_layernorm']['scale'], eps)
    delta = jax.nn.softplus(delta @ w['dt_proj']['kernel']
                            + w['dt_proj']['bias'])
    out = out + turned(scan(c, delta, a, b, cc, d, state_dtype=state_dtype))
  return (out * jax.nn.silu(z)) @ w['out_proj']['kernel']


def attention(w, u):
  """The attention on the normed stream u [B, L, H]: no positions, no
  mask."""
  q = jnp.einsum('blh,hnd->blnd', u, w['query']['kernel'])
  k = jnp.einsum('blh,hnd->blnd', u, w['key']['kernel'])
  v = jnp.einsum('blh,hnd->blnd', u, w['value']['kernel'])
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', q, k) * q.shape[-1] ** -0.5
  out = jnp.einsum('bhij,bjhd->bihd', jax.nn.softmax(scores, axis=-1), v)
  return jnp.einsum('blnd,ndh->blh', out, w['output_transform']['kernel'])


def swiglu(w, x):
  return (jax.nn.silu(x @ w['gate_layer']['kernel'])
          * (x @ w['up_layer']['kernel'])) @ w['output_layer']['kernel']


def pattern(layers, period, offset):
  """The published layer order: attention where n % period == offset."""
  return ''.join(ATTENTION if n % period == offset else MAMBA
                 for n in range(layers))


def _embed(table, ids):
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(table.shape[1]))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _family(table, rows, lo, hi):
  emb = _embed(table, rows[:, lo:hi, :].astype(jnp.int32))
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def logits(params, rows, *, max_passes, layers, eps, **faults):
  """rows [B, 4*max_passes+5, L] float32 -> logits [B, L, 5]. `layers`: one
  letter a layer ('M' or 'F'). `faults` go to every Mamba mixer
  (`mamba`)."""
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
  for n, letter in enumerate(layers):
    u = rms_norm(x, enc[f'attention_wrapper_{n}']['rms_norm']['scale'], eps)
    if letter == MAMBA:
      x = x + mamba(enc[f'mamba_{n}'], u, eps=eps, **faults)
    else:
      x = x + attention(enc[f'self_attention_{n}'], u)
    x = x + swiglu(enc[f'ffn_{n}'],
                   rms_norm(x, enc[f'ffn_wrapper_{n}']['rms_norm']['scale'],
                            eps))
  x = rms_norm(x, enc['output_normalization']['scale'], eps)
  return x @ params['logits']['kernel'] + params['logits']['bias']
