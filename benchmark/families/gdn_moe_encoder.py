"""Family `gdn_moe_encoder`: the block of a public 80B sparse-expert
language model with 3B active parameters (three Gated DeltaNet layers to
one gated softmax-attention layer; 512 routed experts, 10 a token, and a
gated shared expert in every layer; hidden 2048) behind this system's
pile-up embedding and 5-way head, as the program's preset
`transformer_learn_values_gdn_moe` serves it, with the share of the
experts that one chip holds.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test.

norm(x, w) = x * rsqrt(mean(x^2) + eps) * (1 + w), float32 inside. Per
window (x [L, H] from the condenser, positions 0..L-1) a layer is
h = x + mixer(norm(x)); out = h + moe(norm(h)); the mixer by the pattern.

Gated DeltaNet mixer (layers with (n + 1) % interval != 0), Hk key heads
and Hv value heads of D: [q | k | v | z] = u W_qkvz, [b | a] = u W_ba;
[q | k | v] <- silu(causal depthwise convolution over positions, kernel K,
of concat(q, k, v)); beta = sigmoid(b), g = -exp(A_log) softplus(a +
dt_bias); q, k L2-normalised over the head, q scaled by D^-1/2; per value
head (key head h serving value heads h*G ... h*G + G - 1), S [D, D] from
zero, token by token:

  S <- exp(g_t) S; d_t = beta_t (v_t - S^T k_t); S <- S + k_t d_t^T;
  o_t = S^T q_t

An encoder has no causal mask: convolution and recurrence run over the
window and over the window reversed with the same weights, and the two o
are added. y = o * rsqrt(mean(o^2) + eps) * w_o * silu(z) over each head;
mixer = concat(y) W_out.

Gated softmax attention (layers with (n + 1) % interval == 0): per head
[q | gate] = u W_q; k = u W_k, v = u W_v; q and k norm-ed over the head;
rotate-half rotary on the first `rotary_dim` of the head; softmax(q k^T /
sqrt(Dh)) over the whole window, query head h reading key-value head
h // group; mixer = (attn * sigmoid(gate)) W_o.

Sparse experts (every layer): p = softmax(n W_r) over all E, the k largest
renormalised to sum 1; expert e is (silu(n W_gate_e) * (n W_up_e))
W_down_e; moe(n) = sum over the top-k experts that this chip holds,
[first, first + held), of p_e expert_e(n), plus sigmoid(n w_s) * shared(n).
What the experts held elsewhere would add is left out, here as in the
program.

Weights from the seed (`make_params`), so that every part counts in the
logits: matmul kernels uniform with variance 1/fan_in (each residual
branch then has an RMS of the order of the stream's); zero-centred norm
weights uniform [-0.5, 0.5) and the mixer's plain norm weight uniform
[0.5, 1.5); A_log uniform over log [0.1, 0.2) and dt_bias uniform
[-1, 0.5), so that exp(g) spreads over about (0.6, 1); b is a unit-variance
product, so beta spreads over about (0.1, 0.9); the convolution's taps
uniform with variance 1/K; embeddings normal with std E**-0.5 as published
for the pile-up model, the head Glorot uniform with a bias of std 0.02.
The router is drawn like every other kernel at ROUTER_SCALE times the
spread (decisive, as a trained one) and then balanced as training
balances one (`balance_routers`): tokens share a mean direction, and a
random column that happens to point along it takes 5 to 7 times its share
of every pack, which no trained router does. All leaves bfloat16, which is
what the preset's `inference_dtype` leaves resident; the reference upcasts
them, one layer or one expert at a time, so the rounding of the weights is
not part of what is compared.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = 5
SN_ROWS = 4
L2_EPS = 1e-6
WEIGHT_BYTES = 2  # bfloat16 leaves
GATED_SOFTMAX = 'S'  # the letter of a softmax layer in `layer_pattern`
# A router's logits have this standard deviation (other kernels' products
# have 1): the first of a token's ten experts then carries about 0.45 of
# its weight and the tenth 0.03, as a trained router's do. At 1 all ten
# carry about a tenth, and a rounding that swaps the tenth expert for the
# eleventh moves a tenth of the layer's output: the comparison would
# measure how often that happens, in the program and in its controls
# alike (the fp8 control stood 46x over the bfloat16 yardstick at 1 and
# 314x at 3; my CPU run of the reference at the published widths, PR 32).
ROUTER_SCALE = 3.0

SIZE_KEYS = ('num_hidden_layers', 'hidden_size', 'full_attention_interval',
             'layer_pattern', 'linear_num_key_heads',
             'linear_num_value_heads', 'linear_key_head_dim',
             'linear_value_head_dim', 'linear_conv_kernel_dim',
             'num_attention_heads', 'num_key_value_heads', 'head_dim',
             'partial_rotary_factor', 'rope_theta', 'rms_norm_eps',
             'num_experts', 'num_experts_published', 'experts_held',
             'num_experts_per_tok', 'moe_intermediate_size',
             'shared_expert_intermediate_size', 'norm_topk_prob',
             'max_passes', 'max_length', 'total_rows', 'condense_input_size',
             'embedding', 'PW_MAX', 'IP_MAX', 'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def stated(params) -> dict:
  """The program's sizes under the file's keys: the published
  config.json's names for what it publishes, the program's own for the
  rest. `num_experts` is what runs (the experts held), as `reduced` says;
  the router's width is `num_experts_published`."""
  first = params.experts_held_first
  pattern = ''.join(
      GATED_SOFTMAX if (n + 1) % params.full_attention_interval == 0 else 'G'
      for n in range(params.num_hidden_layers))
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'num_hidden_layers': params.num_hidden_layers,
      'hidden_size': params.hidden_size,
      'full_attention_interval': params.full_attention_interval,
      'layer_pattern': pattern,
      'linear_num_key_heads': params.linear_num_key_heads,
      'linear_num_value_heads': params.linear_num_value_heads,
      'linear_key_head_dim': params.linear_key_head_dim,
      'linear_value_head_dim': params.linear_value_head_dim,
      'linear_conv_kernel_dim': params.linear_conv_kernel_dim,
      'num_attention_heads': params.num_heads,
      'num_key_value_heads': params.num_kv_heads,
      'head_dim': params.head_dim,
      'partial_rotary_factor': params.partial_rotary_factor,
      'rope_theta': params.rope_theta,
      'rms_norm_eps': params.rms_norm_eps,
      'num_experts': params.experts_held_count,
      'num_experts_published': params.num_experts,
      'experts_held': [first, first + params.experts_held_count],
      'num_experts_per_tok': params.num_experts_per_tok,
      'moe_intermediate_size': params.moe_intermediate_size,
      'shared_expert_intermediate_size':
          params.shared_expert_intermediate_size,
      'norm_topk_prob': params.norm_topk_prob,
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
  """(H, Hk, Hv, Dk, Dv, heads, kv heads, Dh, E, held, F, Fs)."""
  first, end = shape['experts_held']
  assert end - first == shape['num_experts']
  return (shape['hidden_size'], shape['linear_num_key_heads'],
          shape['linear_num_value_heads'], shape['linear_key_head_dim'],
          shape['linear_value_head_dim'], shape['num_attention_heads'],
          shape['num_key_value_heads'], shape['head_dim'],
          shape['num_experts_published'], shape['num_experts'],
          shape['moe_intermediate_size'],
          shape['shared_expert_intermediate_size'])


# ------------------------------------------------------------------ the tree

def mixer_specs(shape: dict, n: int):
  """Layer n's mixer leaves, by the pattern."""
  h, hk, hv, dk, dv, n_q, n_kv, dh, *_ = _sizes(shape)
  if shape['layer_pattern'][n] == GATED_SOFTMAX:
    att = ('encoder', f'gated_attention_{n}')
    return [
        (att + ('query', 'kernel'), (h, n_q, 2 * dh), 'fan_in', h),
        (att + ('key', 'kernel'), (h, n_kv, dh), 'fan_in', h),
        (att + ('value', 'kernel'), (h, n_kv, dh), 'fan_in', h),
        (att + ('query_norm', 'scale'), (dh,), 'norm', 0),
        (att + ('key_norm', 'scale'), (dh,), 'norm', 0),
        (att + ('output_transform', 'kernel'), (n_q, dh, h), 'fan_in',
         n_q * dh),
    ]
  gdn = ('encoder', f'gdn_{n}')
  channels = 2 * hk * dk + hv * dv
  taps = shape['linear_conv_kernel_dim']
  return [
      (gdn + ('in_proj_qkvz', 'kernel'), (h, channels + hv * dv), 'fan_in', h),
      (gdn + ('in_proj_ba', 'kernel'), (h, 2 * hv), 'fan_in', h),
      (gdn + ('conv_kernel',), (taps, channels), 'fan_in', taps),
      (gdn + ('A_log',), (hv,), 'a_log', 0),
      (gdn + ('dt_bias',), (hv,), 'dt_bias', 0),
      (gdn + ('norm_scale',), (dv,), 'plain_norm', 0),
      (gdn + ('out_proj', 'kernel'), (hv * dv, h), 'fan_in', hv * dv),
  ]


def experts_specs(shape: dict, n: int):
  h, *_, n_experts, held, f, fs = _sizes(shape)
  moe = ('encoder', f'moe_{n}')
  return [
      (moe + ('router', 'kernel'), (h, n_experts), 'router', h),
      (moe + ('experts_gate',), (held, h, f), 'fan_in', h),
      (moe + ('experts_up',), (held, h, f), 'fan_in', h),
      (moe + ('experts_down',), (held, f, h), 'fan_in', f),
      (moe + ('shared_expert', 'gate_layer', 'kernel'), (h, fs), 'fan_in', h),
      (moe + ('shared_expert', 'up_layer', 'kernel'), (h, fs), 'fan_in', h),
      (moe + ('shared_expert', 'output_layer', 'kernel'), (fs, h), 'fan_in',
       fs),
      (moe + ('shared_expert_gate', 'kernel'), (h, 1), 'fan_in', h),
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
    specs.append((('encoder', f'attention_wrapper_{n}', 'rms_norm', 'scale'),
                  (h,), 'norm', 0))
    specs += mixer_specs(shape, n)
    specs.append((('encoder', f'ffn_wrapper_{n}', 'rms_norm', 'scale'), (h,),
                  'norm', 0))
    specs += experts_specs(shape, n)
  return specs


def _draw(key, shp, kind, fan):
  uniform = lambda lo, hi: jax.random.uniform(key, shp, jnp.float32, lo, hi)
  if kind == 'embed':
    return jax.random.normal(key, shp, jnp.float32) * shp[1] ** -0.5
  if kind == 'fan_in':
    lim = math.sqrt(3.0 / fan)
    return uniform(-lim, lim)
  if kind == 'router':
    lim = ROUTER_SCALE * math.sqrt(3.0 / fan)
    return uniform(-lim, lim)
  if kind == 'glorot':
    lim = math.sqrt(6.0 / fan)
    return uniform(-lim, lim)
  if kind == 'bias':
    return jax.random.normal(key, shp, jnp.float32) * 0.02
  if kind == 'norm':  # zero-centred: multiplies as 1 + w
    return uniform(-0.5, 0.5)
  if kind == 'plain_norm':
    return uniform(0.5, 1.5)
  if kind == 'a_log':
    return uniform(math.log(0.1), math.log(0.2))
  if kind == 'dt_bias':
    return uniform(-1.0, 0.5)
  raise ValueError(kind)


# The windows the routers are balanced on: the generator of the cells'
# traffic at its parameters (benchmark/traffic/window_stream*.json), from
# the seed; pass counts no higher than the shape holds.
CALIBRATION_WINDOWS = 32
CALIBRATION_TRAFFIC = dict(
    passes_min=3, passes_max=20, error_rate=0.1, insert_col_rate=0.08,
    partial_pass_rate=0.15, kinetics_mean=30.0, sn_min=4.0, sn_max=20.0)


def make_params(shape: dict, seed: int):
  """The parameter tree on the device, every leaf bfloat16: drawn from the
  seed, then the routers balanced on calibration windows from the same
  seed."""
  from benchmark.generators import pileup_windows

  p = shape['max_passes']
  windows = pileup_windows.make_windows(
      CALIBRATION_WINDOWS, seed=seed, max_passes=p,
      length=shape['max_length'],
      **dict(CALIBRATION_TRAFFIC, passes_min=min(3, p), passes_max=min(20, p)))
  return balance_routers(draw_params(shape, seed), windows, shape)


def draw_params(shape: dict, seed: int):
  """The tree as drawn: one jitted call, one key per leaf, each leaf drawn
  in float32 and rounded once."""
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
  """Parameters of the parts of a layer, norms included with their part:
  a DeltaNet and a softmax layer outside their experts, what lies beside
  the routed experts (router, shared expert and its gate), one expert."""
  h = shape['hidden_size']
  count = lambda specs: sum(math.prod(shp) for _p, shp, _k, _f in specs)
  pattern = shape['layer_pattern']
  out = {}
  for name, letter in (('delta', 'G'), ('softmax', GATED_SOFTMAX)):
    if letter in pattern:
      out[name] = count(mixer_specs(shape, pattern.index(letter))) + 2 * h
  beside = [s for s in experts_specs(shape, 0) if 'experts_' not in s[0][-1]]
  out['beside_experts'] = count(beside)
  out['expert'] = 3 * h * shape['moe_intermediate_size']
  return out


def held_mean(shape: dict) -> float:
  """Assignments a token makes to held experts, on average: k times the
  share held (5.0 of 10 where half are held)."""
  return (shape['num_experts_per_tok'] * shape['num_experts']
          / shape['num_experts_published'])


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, rotary, the convolution, gates and softmax count as nothing. The
  delta rule is counted in its chunked form over one chunk, triangular
  halves as triangles: key.key and the substitution over pairs j < t,
  query.key and the read over pairs j <= t, in two directions. The routed
  experts are counted at the MEAN number of held assignments a token
  (`held_mean`), which is what `forward_mfu` then means in this family;
  `moe_work` takes the count of a window instead."""
  length = shape['max_length']
  h, hk, hv, dk, dv, n_q, n_kv, dh, n_experts, _held, f, fs = _sizes(shape)
  pattern = shape['layer_pattern']
  n_softmax = pattern.count(GATED_SOFTMAX)
  n_delta, layers = len(pattern) - n_softmax, len(pattern)
  before, upto = length * (length - 1) // 2, length * (length + 1) // 2
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'delta_projections': n_delta * 2 * length * h * (
          2 * hk * dk + 2 * hv * dv + 2 * hv + hv * dv),
      'delta_rule': n_delta * 2 * 2 * (
          hk * dk * (before + upto) + hv * dv * (before + upto)),
      'softmax_projections': n_softmax * 2 * length * h * (
          2 * n_q * dh + 2 * n_kv * dh + n_q * dh),
      'softmax_scores': n_softmax * 2 * length * length * n_q * dh,
      'softmax_values': n_softmax * 2 * length * length * n_q * dh,
      'router': layers * 2 * length * h * n_experts,
      'shared_expert': layers * 2 * length * (3 * h * fs + h),
      'experts': int(layers * 2 * length * held_mean(shape) * 3 * h * f),
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


def moe_work(shape: dict, positions: int, assignments_held: int,
             packs: int) -> dict:
  """{'flops', 'bytes'} of the routed experts as device scope `moe`
  covers them (router, dispatch, grouped products, combine; not the shared
  expert), all layers together, for `positions` routed positions of which
  `assignments_held` (token, expert) pairs fell on held experts, over
  `packs` packs: the router's product and three products an assignment;
  the held experts' and the router's bfloat16 weights once a pack, the
  stream in and out. The sorted copy of the tokens is the program's
  choice and counts no bytes."""
  h, *_, n_experts, held, f, _fs = _sizes(shape)
  layers = shape['num_hidden_layers']
  return {
      'flops': (layers * positions * 2 * h * n_experts
                + assignments_held * 3 * 2 * h * f),
      'bytes': WEIGHT_BYTES * layers * (
          packs * (held * 3 * h * f + h * n_experts) + 2 * positions * h),
  }


def part_work(shape: dict, batch: int, part: str) -> dict:
  """{'flops', 'bytes'} one pack needs of one part of the block, all
  layers together, as the device scope of that name covers it:

  'gdn'  the delta rule alone, both directions: q, k, v of each direction
         (bfloat16), g and beta (float32) in, o (float32) out; the [L, L]
         matrices are the algorithm's temporaries and count no bytes.
  'moe'  the routed experts at the mean held share (`moe_work` with
         `held_mean` assignments a position): what a window is expected to
         need, not what a given one did."""
  length = shape['max_length']
  _h, hk, hv, dk, dv, *_ = _sizes(shape)
  positions = batch * length
  if part == 'gdn':
    n_delta = len(shape['layer_pattern']) - shape['layer_pattern'].count(
        GATED_SOFTMAX)
    per_position = (2 * (2 * hk * dk + hv * dv) * WEIGHT_BYTES
                    + 2 * hv * 4 + hv * dv * 4)
    return {'flops': batch * flops_per_window(shape)['delta_rule'],
            'bytes': n_delta * positions * per_position}
  if part == 'moe':
    layers = shape['num_hidden_layers']
    return moe_work(shape, positions,
                    int(layers * positions * held_mean(shape)), 1)
  raise KeyError(part)


# ------------------------------------------------------------- the reference

def row_ranges(max_passes: int):
  """(start, end) rows of bases, pw, ip, strand, ccs, sn in a window."""
  p = max_passes
  return ((0, p), (p, 2 * p), (2 * p, 3 * p), (3 * p, 4 * p),
          (4 * p, 4 * p + 1), (4 * p + 1, 4 * p + 1 + SN_ROWS))


def _rounder(precision: str):
  if precision == 'float32':
    return lambda a: a
  dtype = {'bfloat16': jnp.bfloat16, 'fp8': jnp.float8_e4m3fn}[precision]
  return lambda a: a.astype(dtype).astype(jnp.float32)


def _f32(tree):
  return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _embed(table, ids):
  """Masked embedding: row 0 is the zero vector, output scaled by sqrt(E)."""
  e = table.shape[1]
  out = jnp.take(table, jnp.clip(ids, 0, table.shape[0] - 1), axis=0)
  out = out * jnp.float32(math.sqrt(e))
  return out * (ids != 0)[..., None].astype(jnp.float32)


def _feature_family(table, rows, lo, hi):
  ids = rows[:, lo:hi, :].astype(jnp.int32)
  emb = _embed(table.astype(jnp.float32), ids)  # [B, r, L, E]
  b, r, l, e = emb.shape
  return jnp.transpose(emb, (0, 2, 1, 3)).reshape(b, l, r * e)


def norm(x, w, eps):
  """Zero-centred RMSNorm: the weight multiplies as 1 + w."""
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotary(x, theta: float, rotary_dim: int):
  """x [B, L, N, D], positions 0..L-1: rotate-half rotary embedding on the
  first `rotary_dim` of D, at frequencies theta**(-2i/rotary_dim)."""
  length = x.shape[1]
  inv = theta ** (-np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)[None, :, None, :]
  cos, sin = np.cos(angles).astype(np.float32), np.sin(angles).astype(np.float32)
  head, rest = x[..., :rotary_dim], x[..., rotary_dim:]
  half = rotary_dim // 2
  rotated = jnp.concatenate([-head[..., half:], head[..., :half]], axis=-1)
  return jnp.concatenate([head * cos + rotated * sin, rest], axis=-1)


def causal_conv(x, kernel):
  """x [B, L, C], kernel [K, C]: y_t = sum_i kernel[i] x_{t-(K-1)+i}."""
  taps, length = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
  return sum(padded[:, i:i + length] * kernel[i] for i in range(taps))


def delta_recurrence(q, k, v, g, beta, *, correct: bool = True):
  """The published causal rule, token by token. q, k [B, L, Hk, D]; v
  [B, L, Hv, D]; g, beta [B, L, Hv] -> o [B, L, Hv, D]. Key heads are
  repeated outright. `correct` False drops the delta correction
  (d_t = beta_t v_t), which the fault tests turn."""
  group = v.shape[2] // k.shape[2]
  q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)

  def step(state, xs):
    q_t, k_t, v_t, g_t, beta_t = xs  # [B, Hv, D] and [B, Hv]
    state = state * jnp.exp(g_t)[..., None, None]
    seen = jnp.einsum('bhkv,bhk->bhv', state, k_t) if correct else 0.0
    d_t = beta_t[..., None] * (v_t - seen)
    state = state + k_t[..., :, None] * d_t[..., None, :]
    return state, jnp.einsum('bhkv,bhk->bhv', state, q_t)

  along = lambda a: jnp.moveaxis(a, 1, 0)
  zero = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                   jnp.float32)
  _, out = jax.lax.scan(step, zero,
                        tuple(along(a) for a in (q, k, v, g, beta)))
  return jnp.moveaxis(out, 0, 1)


def gdn_mixer(w, u, *, hk, hv, dk, dv, eps, rd, directions=(1, -1),
              correct=True):
  """The Gated DeltaNet mixer on the normed stream u [B, L, H]."""
  batch, length, _ = u.shape
  key_dim, value_dim = hk * dk, hv * dv
  qkvz = jnp.matmul(rd(u), rd(w['in_proj_qkvz']['kernel']))
  mixed, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., -value_dim:]
  b, a = jnp.split(jnp.matmul(rd(u), rd(w['in_proj_ba']['kernel'])), 2,
                   axis=-1)
  beta = jax.nn.sigmoid(b)
  g = -jnp.exp(w['A_log']) * jax.nn.softplus(a + w['dt_bias'])
  unit = lambda t: t * jax.lax.rsqrt(
      jnp.sum(jnp.square(t), axis=-1, keepdims=True) + L2_EPS)
  out = 0.0
  for direction in directions:
    turn = (lambda t: t) if direction == 1 else (
        lambda t: jnp.flip(t, axis=1))
    conv = jax.nn.silu(causal_conv(turn(mixed), w['conv_kernel']))
    q = unit(conv[..., :key_dim].reshape(batch, length, hk, dk)) * dk ** -0.5
    k = unit(conv[..., key_dim:2 * key_dim].reshape(batch, length, hk, dk))
    v = conv[..., 2 * key_dim:].reshape(batch, length, hv, dv)
    out = out + turn(delta_recurrence(rd(q), rd(k), rd(v), turn(g),
                                      turn(beta), correct=correct))
  out = out * jax.lax.rsqrt(
      jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
  out = out * w['norm_scale'] * jax.nn.silu(z.reshape(batch, length, hv, dv))
  return jnp.matmul(rd(out.reshape(batch, length, value_dim)),
                    rd(w['out_proj']['kernel']))


def gated_attention(w, u, *, rotary_dim, theta, eps, rd):
  """Gated softmax attention on the normed stream u [B, L, H]."""
  mm = lambda a, kernel: jnp.einsum('blh,hnd->blnd', rd(a), rd(kernel))
  q_gate = mm(u, w['query']['kernel'])
  d = q_gate.shape[-1] // 2
  q, gate = q_gate[..., :d], q_gate[..., d:]
  k, v = mm(u, w['key']['kernel']), mm(u, w['value']['kernel'])
  q = rotary(norm(q, w['query_norm']['scale'], eps), theta, rotary_dim)
  k = rotary(norm(k, w['key_norm']['scale'], eps), theta, rotary_dim)
  group = q.shape[2] // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  scores = jnp.einsum('bihd,bjhd->bhij', rd(q), rd(k)) * d ** -0.5
  out = jnp.einsum('bhij,bjhd->bihd', rd(jax.nn.softmax(scores, axis=-1)),
                   rd(v))
  out = out * jax.nn.sigmoid(gate)
  return jnp.einsum('blnd,ndh->blh', rd(out),
                    rd(w['output_transform']['kernel']))


def mixer_fn(w, norm_w, x, *, softmax: bool, sizes: dict, precision: str,
             **faults):
  """x + mixer(norm(x)) for one block of windows; the layer's leaves are
  upcast here, one layer at a time."""
  rd = _rounder(precision)
  w = _f32(w)
  u = norm(x, _f32(norm_w)['rms_norm']['scale'], sizes['eps'])
  if softmax:
    return x + gated_attention(w, u, rotary_dim=sizes['rotary_dim'],
                               theta=sizes['theta'], eps=sizes['eps'], rd=rd)
  return x + gdn_mixer(w, u, hk=sizes['hk'], hv=sizes['hv'], dk=sizes['dk'],
                       dv=sizes['dv'], eps=sizes['eps'], rd=rd, **faults)


def route_fn(w, norm_w, x, *, eps: float, top_k: int, renormalise: bool,
             precision: str):
  """The normed tokens [T, H], their top-k (probabilities, experts) and
  the gated shared expert's share of moe(n), for all T tokens."""
  rd = _rounder(precision)
  n = norm(x, _f32(norm_w)['rms_norm']['scale'], eps)
  probs = jax.nn.softmax(
      jnp.matmul(rd(n), rd(w['router']['kernel'].astype(jnp.float32))),
      axis=-1)
  top_p, top_e = jax.lax.top_k(probs, top_k)
  if renormalise:
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
  s = _f32(w['shared_expert'])
  shared = jnp.matmul(
      rd(jax.nn.silu(jnp.matmul(rd(n), rd(s['gate_layer']['kernel'])))
         * jnp.matmul(rd(n), rd(s['up_layer']['kernel']))),
      rd(s['output_layer']['kernel']))
  share = jax.nn.sigmoid(jnp.matmul(
      rd(n), rd(w['shared_expert_gate']['kernel'].astype(jnp.float32))))
  return n, top_p, top_e, share * shared


def expert_fn(gate, up, down, e, rows, *, precision: str):
  """Expert e of the stacked leaves on its rows [R, H]; its three
  matrices are upcast here, one expert at a time."""
  rd = _rounder(precision)
  pick = lambda w: rd(w[e].astype(jnp.float32))
  hidden = jax.nn.silu(jnp.matmul(rd(rows), pick(gate))) * jnp.matmul(
      rd(rows), pick(up))
  return jnp.matmul(rd(hidden), pick(down))


def routed_experts(w, n, top_p, top_e, first: int, expert, row_step: int = 128):
  """sum over the held experts of p_e expert_e(n): a plain loop over the
  experts, each on the rows routed to it (padded with zero rows to a
  multiple of `row_step`, so that few shapes compile), scatter-added on
  the host. -> (float32 [T, H], assignments per held expert)."""
  n_host = np.asarray(n)
  top_p, top_e = np.asarray(top_p), np.asarray(top_e)
  held = w['experts_gate'].shape[0]
  out = np.zeros(n_host.shape, np.float32)
  counts = np.zeros(held, np.int64)
  for e in range(held):
    token, slot = np.nonzero(top_e == first + e)
    counts[e] = len(token)
    if not len(token):
      continue
    rows = np.zeros((-(-len(token) // row_step) * row_step, n_host.shape[1]),
                    np.float32)
    rows[:len(token)] = n_host[token]
    y = np.asarray(expert(w['experts_gate'], w['experts_up'],
                          w['experts_down'], e, jnp.asarray(rows)))
    # A token names an expert at most once: plain indexed addition.
    out[token] += top_p[token, slot][:, None] * y[:len(token)]
  return out, counts


def embed_fn(params, rows, *, max_passes: int, precision: str):
  """rows [B, 4*max_passes+5, L] float32 -> the stream [B, L, H]."""
  rd = _rounder(precision)
  base_r, pw_r, ip_r, st_r, ccs_r, sn_r = row_ranges(max_passes)
  table = lambda name: params[name + '_embedding']['embedding']
  x = jnp.concatenate([
      _feature_family(table('bases'), rows, *base_r),
      _feature_family(table('pw'), rows, *pw_r),
      _feature_family(table('ip'), rows, *ip_r),
      _feature_family(table('strand'), rows, *st_r),
      _feature_family(table('bases'), rows, *ccs_r),
      _feature_family(table('sn'), rows, *sn_r),
  ], axis=-1)
  return jnp.matmul(rd(x), rd(params['condenser']['kernel'].astype(
      jnp.float32)))


def head_fn(params, x, *, eps: float):
  """Final norm and the 5-way head: float32 whatever the compute type, as
  the program keeps it, so no rounding."""
  x = norm(x, params['encoder']['output_normalization']['scale'].astype(
      jnp.float32), eps)
  return jnp.matmul(x, params['logits']['kernel'].astype(
      jnp.float32)) + params['logits']['bias'].astype(jnp.float32)


def balanced_router(w, norm_w, x, *, eps: float):
  """The layer's router kernel with every column made orthogonal to the
  mean of the normed tokens x [T, H] it is about to route: the offset
  that the tokens' common direction gives each expert's logit is gone,
  and what ranks the experts is what tells tokens apart."""
  n = norm(x, _f32(norm_w)['rms_norm']['scale'], eps)
  mean = jnp.mean(n, axis=0)
  kernel = w['router']['kernel'].astype(jnp.float32)
  kernel = kernel - jnp.outer(mean, mean @ kernel) / jnp.dot(mean, mean)
  return kernel.astype(w['router']['kernel'].dtype)


def balance_routers(params, windows: np.ndarray, shape: dict):
  """The tree with its routers balanced, layer after layer, on what the
  plain reference makes of `windows` up to each layer (a router moves
  every later layer's tokens, so each is balanced on the tokens the
  balanced ones before it leave)."""
  return reference_forward(params, windows, shape, balance=True)[2]


def reference_forward(params, windows: np.ndarray, shape: dict,
                      precision: str = 'float32', block: int = 32,
                      renormalise=None, balance: bool = False, **faults):
  """(logits [S, L, 5], assignments [layers, held], the tree) of the plain
  reference; `reference_logits` says how. `balance` replaces each layer's
  router by `balanced_router` on its own tokens before it routes them, and
  the tree returned is the balanced one."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  _h, hk, hv, dk, dv, _nq, _nkv, dh, *_ = _sizes(shape)
  eps = float(shape['rms_norm_eps'])
  sizes = dict(hk=hk, hv=hv, dk=dk, dv=dv, eps=eps,
               rotary_dim=int(dh * shape['partial_rotary_factor']),
               theta=float(shape['rope_theta']))
  if renormalise is None:
    renormalise = bool(shape['norm_topk_prob'])
  embed = jax.jit(functools.partial(embed_fn, max_passes=p,
                                    precision=precision))
  mixers = {softmax: jax.jit(functools.partial(
      mixer_fn, softmax=softmax, sizes=sizes, precision=precision,
      **({} if softmax else faults))) for softmax in (False, True)}
  route = jax.jit(functools.partial(
      route_fn, eps=eps, top_k=shape['num_experts_per_tok'],
      renormalise=renormalise, precision=precision))
  expert = jax.jit(functools.partial(expert_fn, precision=precision))
  head = jax.jit(functools.partial(head_fn, eps=eps))
  balanced = jax.jit(functools.partial(balanced_router, eps=eps))
  enc = dict(params['encoder'])
  n_windows, short = len(rows), -len(rows) % block
  if short:  # keep one compiled shape
    rows = np.concatenate(
        [rows, np.zeros((short,) + rows.shape[1:], np.float32)])
  blocks = range(0, len(rows), block)
  counts = []
  with jax.default_matmul_precision('highest'):
    x = np.concatenate([np.asarray(embed(params, jnp.asarray(rows[lo:lo + block])))
                        for lo in blocks])
    for i, letter in enumerate(shape['layer_pattern']):
      softmax = letter == GATED_SOFTMAX
      mixer = enc[f'gated_attention_{i}' if softmax else f'gdn_{i}']
      x = np.concatenate([np.asarray(mixers[softmax](
          mixer, enc[f'attention_wrapper_{i}'], jnp.asarray(x[lo:lo + block])))
                          for lo in blocks])
      # The experts see every token of the sample at once, the padding
      # windows left out: a held expert then has rows enough to count.
      moe = enc[f'moe_{i}']
      tokens = jnp.asarray(x[:n_windows].reshape(-1, x.shape[-1]))
      if balance:
        moe = enc[f'moe_{i}'] = dict(moe, router={'kernel': balanced(
            moe, enc[f'ffn_wrapper_{i}'], tokens)})
      n, top_p, top_e, shared = route(moe, enc[f'ffn_wrapper_{i}'], tokens)
      routed, took = routed_experts(moe, n, top_p, top_e,
                                    shape['experts_held'][0], expert)
      x[:n_windows] += (routed + np.asarray(shared)).reshape(
          (n_windows,) + x.shape[1:])
      counts.append(took)
    logits = np.concatenate([np.asarray(head(params, jnp.asarray(x[lo:lo + block])))
                             for lo in blocks])
  return logits[:n_windows], np.stack(counts), dict(params, encoder=enc)


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 32, **faults):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]:
  plain float32 under `jax.default_matmul_precision('highest')`, input
  clipping included; embedding, mixers and head in blocks of windows, the
  experts of a layer over all the sample's tokens, one expert at a time.
  `precision` 'bfloat16' or 'fp8' rounds every matmul operand (activations
  and weights; for the delta rule q, k and v, its state being an
  accumulator) to that type before a float32-accumulated product.
  `faults` (correct=False: the delta correction dropped; renormalise=False:
  top-k weights left as the softmax gave them; directions=(1,): one
  direction only) are for the tests that show the comparison sees them."""
  return reference_forward(params, windows, shape, precision, block,
                           **faults)[0]
