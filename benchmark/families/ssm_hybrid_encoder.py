"""Family `ssm_hybrid_encoder`: the block of a public 3B dense hybrid model
(`jamba` architecture: a sequential pre-RMSNorm stack of Mamba-1 mixers
with softmax attention where n % attn_layer_period == attn_layer_offset,
14 and 7: `MMMMMMMFMMMMMM` twice; 20 query heads over one key-value head of
128 without positions; a SwiGLU of 8192 in every layer, one expert being a
dense layer; hidden 2560) behind this system's pile-up embedding and 5-way
head, as the program's preset `transformer_learn_values_mamba_hybrid`
serves it, the whole 28 layers on this chip.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test; it shares the embedding, the rounding and the draws of
parallel_moe_encoder.py.

RMS(x, w) = x * rsqrt(mean(x^2) + eps) * w, float32 inside, plain weights.
Per window (x [L, H] from the condenser, positions 0..L-1) a layer is
h = x + mixer_n(RMS_1(x)), out = h + SwiGLU(RMS_2(h)); a final RMS.

Mamba (`M`, from the published `JambaMambaMixer.slow_forward`; inner width
I = expand x H, state S, dt rank R, K taps): [x | z] = u W_in; for each
direction (the window as it lies, and the window reversed, its output
reversed back: an encoder has no causal mask) c = silu(conv(x) + b_conv),
the depthwise causal convolution (out[t] = sum_k w[k] x[t - K + 1 + k]);
[delta | B | C] = c W_x, each RMS'd with a weight of its own (eps as the
block's); Delta = softplus(delta W_dt + b_dt); A = -exp(A_log); from a zero
state h_t = exp(Delta_t A) h_{t-1} + Delta_t c_t B_t, y_t = h_t C_t + D c_t;
the mixer's output (y_fwd + y_bwd) silu(z) W_out.

Attention (`F`): q = u W_q, k = u W_k, v = u W_v, no biases, no positions;
k and v repeated to the query heads; softmax(q_h k_h^T * D^-1/2) v_h over
the whole window; concat_h W_o.

Weights from the seed (`make_params`): matmul kernels (the convolution's
taps among them) uniform with variance 1/fan_in; norm weights and D
uniform [0.5, 1.5); A_log = log(1..S) for every channel, as published;
b_dt such that softplus(b_dt) spreads log-uniformly over Mamba's published
[0.001, 0.1]: the decays exp(Delta A) then lie where a trained model's do;
the convolution's bias, like the head's, of std 0.02; embeddings normal
with std E**-0.5 as published for the pile-up model, the head Glorot
uniform. All leaves bfloat16, which is what the preset's `inference_dtype`
leaves resident; the reference upcasts them, one layer at a time, so the
rounding of the weights is not part of what is compared.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families import parallel_moe_encoder as shared
from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = shared.VOCAB
SN_ROWS = shared.SN_ROWS
WEIGHT_BYTES = shared.WEIGHT_BYTES  # bfloat16 leaves
LAYER_MAMBA, LAYER_ATTENTION = 'M', 'F'  # the letters of `layer_pattern`
# Per state cell and position of one direction the scan takes one
# exponential and five other operations (Delta A, the decay's product with
# the state, Delta x B, the sum, the product with C, which is then summed
# over the states): six, counted as FLOPs.
SCAN_OPS_PER_STEP = 6
# Mamba's published range of softplus(b_dt).
DT_MIN, DT_MAX = 1e-3, 1e-1

SIZE_KEYS = ('num_hidden_layers', 'hidden_size', 'intermediate_size',
             'num_attention_heads', 'num_key_value_heads', 'head_dim',
             'attn_layer_period', 'attn_layer_offset', 'layer_pattern',
             'mamba_d_state', 'mamba_d_conv', 'mamba_expand', 'mamba_dt_rank',
             'mamba_conv_bias', 'rms_norm_eps', 'max_passes', 'max_length',
             'total_rows', 'condense_input_size', 'embedding', 'PW_MAX',
             'IP_MAX', 'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def pattern_of(layers: int, period: int, offset: int) -> str:
  """Attention where n % period == offset, Mamba elsewhere."""
  return ''.join(LAYER_ATTENTION if n % period == offset else LAYER_MAMBA
                 for n in range(layers))


def stated(params) -> dict:
  """The program's sizes under the file's keys: the published
  config.json's names for what it publishes, the program's own for the
  rest."""
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'num_hidden_layers': params.num_hidden_layers,
      'hidden_size': params.hidden_size,
      'intermediate_size': params.filter_size,
      'filter_size': params.filter_size,
      'num_attention_heads': params.num_heads,
      'num_key_value_heads': params.num_kv_heads,
      'head_dim': params.head_dim,
      'attn_layer_period': params.attn_layer_period,
      'attn_layer_offset': params.attn_layer_offset,
      'expert_layer_period': params.expert_layer_period,
      'expert_layer_offset': params.expert_layer_offset,
      'num_experts': params.num_experts,
      'num_experts_per_tok': params.num_experts_per_tok,
      'layer_pattern': pattern_of(params.num_hidden_layers,
                                  params.attn_layer_period,
                                  params.attn_layer_offset),
      'mamba_d_state': params.mamba_d_state,
      'mamba_d_conv': params.mamba_d_conv,
      'mamba_expand': params.mamba_expand,
      'mamba_dt_rank': params.mamba_dt_rank,
      'mamba_conv_bias': params.mamba_conv_bias,
      'mamba_proj_bias': params.mamba_proj_bias,
      'rms_norm_eps': params.rms_norm_eps,
      'add_pos_encoding': params.add_pos_encoding,
      'max_passes': params.max_passes,
      'max_length': params.max_length,
      'total_rows': params.total_rows,
      'use_ccs_bq': params.use_ccs_bq,
      'PW_MAX': params.PW_MAX, 'IP_MAX': params.IP_MAX,
      'STRAND_MAX': params.STRAND_MAX, 'SN_MAX': params.SN_MAX,
      'dtype': params.dtype,
      'inference_dtype': params.inference_dtype,
      'rezero': params.rezero,
      'use_fused_hotpath': params.use_fused_hotpath,
      'embedding': {
          'bases': params.per_base_hidden_size, 'pw': params.pw_hidden_size,
          'ip': params.ip_hidden_size, 'strand': params.strand_hidden_size,
          'sn': params.sn_hidden_size},
  }


def _sizes(shape: dict):
  """(H, F, query heads, key-value heads, D, I, S, R, K)."""
  h = shape['hidden_size']
  return (h, shape['intermediate_size'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['mamba_expand'] * h, shape['mamba_d_state'],
          shape['mamba_dt_rank'], shape['mamba_d_conv'])


def mamba_layers(shape: dict) -> int:
  return shape['layer_pattern'].count(LAYER_MAMBA)


def attention_layers(shape: dict) -> int:
  return shape['layer_pattern'].count(LAYER_ATTENTION)


# ------------------------------------------------------------------ the tree

def layer_specs(shape: dict, n: int):
  """The leaves of layer n: two norms, the mixer (Mamba or attention) and
  the SwiGLU."""
  h, f, heads, kv_heads, d, inner, s, rank, taps = _sizes(shape)
  enc = ('encoder',)
  norm = lambda wrapper: (enc + (f'{wrapper}_{n}', 'rms_norm', 'scale'),
                          (h,), 'norm', 0)
  ffn = enc + (f'ffn_{n}',)
  if shape['layer_pattern'][n] == LAYER_MAMBA:
    mix = enc + (f'mamba_{n}',)
    mixer = [
        (mix + ('in_proj', 'kernel'), (h, 2 * inner), 'fan_in', h),
        (mix + ('conv1d', 'kernel'), (taps, inner), 'fan_in', taps),
        (mix + ('conv1d', 'bias'), (inner,), 'bias', 0),
        (mix + ('x_proj', 'kernel'), (inner, rank + 2 * s), 'fan_in', inner),
        (mix + ('dt_layernorm', 'scale'), (rank,), 'norm', 0),
        (mix + ('b_layernorm', 'scale'), (s,), 'norm', 0),
        (mix + ('c_layernorm', 'scale'), (s,), 'norm', 0),
        (mix + ('dt_proj', 'kernel'), (rank, inner), 'fan_in', rank),
        (mix + ('dt_proj', 'bias'), (inner,), 'dt_bias', 0),
        (mix + ('A_log',), (inner, s), 'a_log', 0),
        (mix + ('D',), (inner,), 'norm', 0),
        (mix + ('out_proj', 'kernel'), (inner, h), 'fan_in', inner),
    ]
  else:
    att = enc + (f'self_attention_{n}',)
    mixer = [
        (att + ('query', 'kernel'), (h, heads, d), 'fan_in', h),
        (att + ('key', 'kernel'), (h, kv_heads, d), 'fan_in', h),
        (att + ('value', 'kernel'), (h, kv_heads, d), 'fan_in', h),
        (att + ('output_transform', 'kernel'), (heads, d, h), 'fan_in',
         heads * d),
    ]
  return [norm('attention_wrapper')] + mixer + [
      norm('ffn_wrapper'),
      (ffn + ('gate_layer', 'kernel'), (h, f), 'fan_in', h),
      (ffn + ('up_layer', 'kernel'), (h, f), 'fan_in', h),
      (ffn + ('output_layer', 'kernel'), (f, h), 'fan_in', f),
  ]


def leaf_specs(shape: dict):
  """(path, shape, kind, fan_in) for every leaf, in a fixed order."""
  h = shape['hidden_size']
  emb = shape['embedding']
  condense_in = shape['condense_input_size']
  specs = [
      (('bases_embedding', 'embedding'), (VOCAB, emb['bases']), 'embed', 0),
      (('pw_embedding', 'embedding'), (shape['PW_MAX'] + 1, emb['pw']),
       'embed', 0),
      (('ip_embedding', 'embedding'), (shape['IP_MAX'] + 1, emb['ip']),
       'embed', 0),
      (('strand_embedding', 'embedding'),
       (shape['STRAND_MAX'] + 1, emb['strand']), 'embed', 0),
      (('sn_embedding', 'embedding'), (shape['SN_MAX'] + 1, emb['sn']),
       'embed', 0),
      (('condenser', 'kernel'), (condense_in, h), 'fan_in', condense_in),
      (('logits', 'kernel'), (h, VOCAB), 'glorot', h + VOCAB),
      (('logits', 'bias'), (VOCAB,), 'bias', 0),
      (('encoder', 'output_normalization', 'scale'), (h,), 'norm', 0),
  ]
  for n in range(shape['num_hidden_layers']):
    specs += layer_specs(shape, n)
  return specs


def _draw(key, shp, kind, fan):
  if kind == 'a_log':
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shp[1] + 1, dtype=jnp.float32), shp))
  if kind == 'dt_bias':
    dt = jnp.exp(jax.random.uniform(key, shp, jnp.float32, math.log(DT_MIN),
                                    math.log(DT_MAX)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
  return shared._draw(key, shp, kind, fan)


def make_params(shape: dict, seed: int):
  """The parameter tree on the device, every leaf bfloat16: one jitted
  call, one key per leaf, each leaf drawn in float32 and rounded once."""
  specs = leaf_specs(shape)

  def build(key):
    tree: dict = {}
    for i, (path, shp, kind, fan) in enumerate(specs):
      node = tree
      for part in path[:-1]:
        node = node.setdefault(part, {})
      node[path[-1]] = _draw(jax.random.fold_in(key, i), shp, kind,
                             fan).astype(jnp.bfloat16)
    return tree

  return jax.jit(build)(key_from_seed(seed))


# ------------------------------------------------------------------ the work

def param_count(shape: dict) -> int:
  return sum(math.prod(shp) for _p, shp, _k, _f in leaf_specs(shape))


def layer_counts(shape: dict) -> dict:
  """Parameters of a Mamba layer, of its mixer, and of an attention
  layer, each with its two norms and its SwiGLU."""
  count = lambda specs: sum(math.prod(shp) for _p, shp, _k, _f in specs)
  pattern = shape['layer_pattern']
  mamba = layer_specs(shape, pattern.index(LAYER_MAMBA))
  counts = {'mamba_layer': count(mamba),
            'mamba_mixer': count([s for s in mamba if 'mamba_' in s[0][1]])}
  if LAYER_ATTENTION in pattern:
    counts['attention_layer'] = count(
        layer_specs(shape, pattern.index(LAYER_ATTENTION)))
  return counts


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, the convolution, the softmax and the scan count as nothing (the
  scan's own operations are `part_work`'s 'ssm_scan'); x_proj and dt_proj
  run once a direction."""
  length = shape['max_length']
  h, f, heads, kv_heads, d, inner, s, rank, _taps = _sizes(shape)
  m, a = mamba_layers(shape), attention_layers(shape)
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'mamba_in': m * 2 * length * h * 2 * inner,
      'mamba_x_dt': m * 2 * 2 * length * inner * (rank + 2 * s + rank),
      'mamba_out': m * 2 * length * inner * h,
      'attention_projections': a * 2 * length * h * d * (
          2 * heads + 2 * kv_heads),
      'gqa_scores': a * 2 * length * length * heads * d,
      'gqa_values': a * 2 * length * length * heads * d,
      'ffn': shape['num_hidden_layers'] * 2 * length * 3 * h * f,
      'head': 2 * length * h * VOCAB,
  }
  parts['total'] = sum(parts.values())
  return parts


def bytes_per_pack(shape: dict, batch: int) -> dict:
  """Bytes the algorithm has to move for one pack: the uint8 rows and
  float32 SN scalars in, two uint8 planes out, the bfloat16 weights once."""
  length = shape['max_length']
  parts = {
      'rows_in': batch * (shape['total_rows'] - SN_ROWS) * length,
      'sn_in': batch * SN_ROWS * 4,
      'planes_out': batch * length * 2,
      'weights': param_count(shape) * WEIGHT_BYTES,
  }
  parts['total'] = sum(parts.values())
  return parts


def least_seconds_per_pack(shape: dict, batch: int, peaks: dict) -> dict:
  return least_seconds(flops_per_window(shape)['total'] * batch,
                       bytes_per_pack(shape, batch)['total'], peaks)


def part_work(shape: dict, batch: int, part: str) -> dict:
  """{'flops', 'bytes'} one pack needs of one part of the block, all
  layers together, as the device scope of that name covers it:

  'ssm_scan'  the scan of both directions with its D terms and gate
              (scope `ssm_scan`): SCAN_OPS_PER_STEP a state cell, position
              and direction as FLOPs; x and dt of both directions and z in
              (bfloat16), B and C of both directions in (float32), y out
              (bfloat16) as bytes, which bound it on the roofline;
  'mamba'     the whole mixer (scope `mamba`, in_proj to out_proj): its
              products and the scan's operations; its bfloat16 weights once
              a pack and the stream in and out as bytes (what passes
              between its products is the program's choice)."""
  length = shape['max_length']
  h, _f, _heads, _kv, _d, inner, s, _rank, _taps = _sizes(shape)
  m, positions = mamba_layers(shape), batch * length
  scan_flops = m * positions * 2 * inner * s * SCAN_OPS_PER_STEP
  if part == 'ssm_scan':
    per_position = WEIGHT_BYTES * 6 * inner + 4 * 2 * 2 * s
    return {'flops': scan_flops, 'bytes': m * positions * per_position}
  if part == 'mamba':
    flops = flops_per_window(shape)
    mixer = layer_counts(shape)['mamba_mixer']
    return {'flops': batch * (flops['mamba_in'] + flops['mamba_x_dt']
                              + flops['mamba_out']) + scan_flops,
            'bytes': m * WEIGHT_BYTES * (mixer + 2 * positions * h)}
  raise KeyError(part)


ssm_scan_work = functools.partial(part_work, part='ssm_scan')
mamba_work = functools.partial(part_work, part='mamba')


# ------------------------------------------------------------- the reference

def rms_norm(x, w, eps):
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def causal_conv(x, kernel, bias):
  """x [B, L, C], kernel [K, C], bias [C]: the published depthwise causal
  convolution cut to the window, tap k on x[t - K + 1 + k]."""
  taps, length = kernel.shape[0], x.shape[1]
  out = bias
  for k in range(taps):
    shift = taps - 1 - k
    behind = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :length]
    out = out + kernel[k] * behind
  return out


def scan(x, delta, a, b, c, d, *, state_dtype=jnp.float32):
  """The published selective scan over one direction, position by
  position from a zero state: x, delta [B, L, I]; a [I, S]; b, c
  [B, L, S]; d [I] -> y [B, L, I]. `state_dtype` bfloat16, a fault: the
  decay and the state held in bfloat16."""
  rd = lambda t: t.astype(state_dtype).astype(jnp.float32)
  positions = lambda t: jnp.moveaxis(t, 1, 0)

  def step(h, inputs):
    x_t, delta_t, b_t, c_t = inputs
    h = rd(rd(jnp.exp(delta_t[..., None] * a)) * h
           + (delta_t * x_t)[..., None] * b_t[:, None, :])
    return h, jnp.sum(h * c_t[:, None, :], axis=-1) + d * x_t

  h = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
  _, y = jax.lax.scan(step, h, tuple(positions(t) for t in (x, delta, b, c)))
  return jnp.moveaxis(y, 0, 1)


def mamba_fn(w, norm_w, x, *, eps: float, precision: str, directions=2,
             skip=True, state_dtype=jnp.float32):
  """mixer(RMS_1(x)) for one block of windows (the branch alone). Every
  matmul operand is rounded to `precision`; the convolution, the norms and
  the scan stay float32. Faults: `directions` 1 (the published causal mixer
  alone), `skip` False (no D term), `state_dtype` (see `scan`)."""
  rd = shared._rounder(precision)
  w = shared._f32(w)
  mm = lambda a, b: jnp.matmul(rd(a), rd(b))
  inner, s = w['A_log'].shape
  rank = w['dt_proj']['kernel'].shape[0]
  u = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  xz = mm(u, w['in_proj']['kernel'])
  xs, z = xz[..., :inner], xz[..., inner:]
  a = -jnp.exp(w['A_log'])
  d = w['D'] if skip else jnp.zeros_like(w['D'])
  flip = lambda t: jnp.flip(t, axis=1)
  out = 0.0
  for direction in range(directions):
    turned = flip if direction else (lambda t: t)
    c = jax.nn.silu(causal_conv(turned(xs), w['conv1d']['kernel'],
                                w['conv1d']['bias']))
    parts = mm(c, w['x_proj']['kernel'])
    delta = rms_norm(parts[..., :rank], w['dt_layernorm']['scale'], eps)
    b = rms_norm(parts[..., rank:rank + s], w['b_layernorm']['scale'], eps)
    cc = rms_norm(parts[..., rank + s:], w['c_layernorm']['scale'], eps)
    delta = jax.nn.softplus(mm(delta, w['dt_proj']['kernel'])
                            + w['dt_proj']['bias'])
    out = out + turned(scan(c, delta, a, b, cc, d, state_dtype=state_dtype))
  return mm(out * jax.nn.silu(z), w['out_proj']['kernel'])


def attention_fn(w, norm_w, x, *, eps: float, precision: str):
  """attn(RMS_1(x)) for one block of windows: no positions, no mask; k and
  v repeated to the query heads."""
  rd = shared._rounder(precision)
  w = shared._f32(w)
  u = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  q = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['query']['kernel']))
  k = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['key']['kernel']))
  v = jnp.einsum('blh,hnd->blnd', rd(u), rd(w['value']['kernel']))
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', rd(q), rd(k)) * q.shape[-1] ** -0.5
  out = jnp.einsum('bhij,bjhd->bihd', rd(jax.nn.softmax(scores, axis=-1)),
                   rd(v))
  return jnp.einsum('blnd,ndh->blh', rd(out),
                    rd(w['output_transform']['kernel']))


def ffn_fn(w, norm_w, x, *, eps: float, precision: str):
  """SwiGLU(RMS_2(x)) for one block of windows."""
  rd = shared._rounder(precision)
  w = shared._f32(w)
  n = rms_norm(x, norm_w['rms_norm']['scale'].astype(jnp.float32), eps)
  return shared.swiglu(w['gate_layer']['kernel'], w['up_layer']['kernel'],
                       w['output_layer']['kernel'], n, rd)


def head_fn(params, x, *, eps: float):
  """Final RMSNorm and the 5-way head: float32 whatever the compute type,
  as the program keeps it, so no rounding."""
  x = rms_norm(x, params['encoder']['output_normalization']['scale'].astype(
      jnp.float32), eps)
  return jnp.matmul(x, params['logits']['kernel'].astype(
      jnp.float32)) + params['logits']['bias'].astype(jnp.float32)


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 32, **faults):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]:
  plain float32 under `jax.default_matmul_precision('highest')`, in blocks
  of windows, input clipping included. `precision` 'bfloat16' or 'fp8'
  rounds every matmul operand (activations and weights; for the attention
  q, k, the softmax weights and v) to that type before a float32-
  accumulated product; the convolution, the scan, the softmax and every
  norm stay float32. `faults` (directions=1, skip=False,
  state_dtype=jnp.bfloat16) go to every Mamba mixer, for the tests that
  show what the comparison sees."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  eps = float(shape['rms_norm_eps'])
  embed = jax.jit(functools.partial(shared.embed_fn, max_passes=p,
                                    precision=precision))
  mixers = {
      LAYER_MAMBA: jax.jit(functools.partial(
          mamba_fn, eps=eps, precision=precision, **faults)),
      LAYER_ATTENTION: jax.jit(functools.partial(
          attention_fn, eps=eps, precision=precision))}
  names = {LAYER_MAMBA: 'mamba', LAYER_ATTENTION: 'self_attention'}
  ffn = jax.jit(functools.partial(ffn_fn, eps=eps, precision=precision))
  head = jax.jit(functools.partial(head_fn, eps=eps))
  enc = params['encoder']
  out = []
  with jax.default_matmul_precision('highest'):
    for lo in range(0, len(rows), block):
      chunk = rows[lo:lo + block]
      n = len(chunk)
      if n < block:  # keep one compiled shape
        chunk = np.concatenate(
            [chunk, np.zeros((block - n,) + chunk.shape[1:], np.float32)])
      x = embed(params, jnp.asarray(chunk))
      for i, letter in enumerate(shape['layer_pattern']):
        x = x + mixers[letter](enc[f'{names[letter]}_{i}'],
                               enc[f'attention_wrapper_{i}'], x)
        x = x + ffn(enc[f'ffn_{i}'], enc[f'ffn_wrapper_{i}'], x)
      out.append(np.asarray(head(params, x))[:n])
  return np.concatenate(out)
