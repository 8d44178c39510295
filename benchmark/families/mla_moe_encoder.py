"""Family `mla_moe_encoder`: the block of a public 30B sparse-expert
language model with 3B active parameters (`deepseek_v3` architecture:
multi-head latent attention without a query latent in every layer; one
leading dense layer, then 128 routed experts, 6 a token, chosen by sigmoid
scores plus a balancing bias, and two ungated shared experts; hidden 2048)
behind this system's pile-up embedding and 5-way head, as the program's
preset `transformer_learn_values_mla_moe` serves it, every expert of a layer
on this chip.

What a family brings (benchmark/families/gap_aware_encoder.py lists the
functions): sizes, the stated-size check, the seeded tree in the type it
is served in (bfloat16, on the device), the work from shapes alone, and
the plain reference. This file is all of it, and imports nothing of the
program under test.

norm(x, w) = x * rsqrt(mean(x^2) + eps) * w, float32 inside. Per window
(x [L, H] from the condenser, positions 0..L-1) a layer is
h = x + attn(norm(x)); out = h + ffn_n(norm(h)); a final norm.

Attention, as published (N heads): q = u W_q, a head [q_nope | q_rope];
[c | k_rope] = u W_kva, c <- norm(c) over the latent; [k_nope | v] = c W_kvb
a head; q_rope and the ONE k_rope rotated by position over interleaved
pairs (2i, 2i + 1); k_h = [k_nope_h | k_rope], the rotary key expanded to
every head; softmax(q_h k_h^T * (nope + rope)^-1/2) v_h over the whole
window (an encoder has no causal mask); concat_h W_o.

Feed-forward of layer n: (silu(n W_gate) * (n W_up)) W_down in the
`first_k_dense_replace` leading layers; behind them s = sigmoid(n W_r) over
all E, top = the k largest of s + b (the published `get_topk_indices`; its
group step keeps `topk_group` of `n_group` groups, which at one group masks
nothing), p_e = s_e / (sum_top s + 1e-20) * factor, moe(n) = sum over the
top-k experts this chip holds, [first, first + held), of p_e expert_e(n),
plus shared(n), one SwiGLU of n_shared x the expert width, no gate.

The program rotates halves (i, i + rope / 2) and its leaves hold the
rotary columns of W_q (a head) and W_kva in that order; `published_order`
puts them back in the published order (pairs) before the reference
computes anything, so the reference is the published arithmetic on the
published layout of the same weights.

Weights from the seed (`make_params`), so that every part counts in the
logits: matmul kernels uniform with variance 1/fan_in (each residual
branch then has an RMS of the order of the stream's); norm weights uniform
[0.5, 1.5); embeddings normal with std E**-0.5 as published for the
pile-up model, the head Glorot uniform with a bias of std 0.02. The router
is drawn like every other kernel (ROUTER_SCALE) and then balanced as
training balances one (`balance_routers`): each column made orthogonal to
the mean of the tokens its layer routes, and the selection bias b set by
the published rule itself, a few rounds of b_e += u sign(mean load -
load_e) on calibration windows. All leaves bfloat16, which is what the
preset's `inference_dtype` leaves resident; the reference upcasts them, one
layer or one expert at a time, so the rounding of the weights is not part
of what is compared.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.seeds import key_from_seed
from benchmark.lib.work import least_seconds

VOCAB = 5
SN_ROWS = 4
WEIGHT_BYTES = 2  # bfloat16 leaves
FFN_EXPERTS = 'E'  # the letter of an expert layer in `ffn_pattern`
# A router's logits have the standard deviation of every other kernel's
# product, 1: the six chosen of 128 then score about 0.85-0.95 and the
# seventh about 0.01 under the sixth, which is the size a balancing bias
# has. At 3, as the softmax family draws its router, every candidate scores
# over 0.99, the gaps between them fall under 0.002, and the bias alone
# would choose.
ROUTER_SCALE = 1.0
# The published balancing rule's step and how often it is taken: b moves
# by BIAS_STEP a round, so it ends within +-BIAS_ROUNDS * BIAS_STEP, and
# once the loads are even it swings by one step (a load by about 7%). At 16
# rounds of 0.004 the fullest experts' b stood at the end of its reach and
# one took 2.77 times the mean of a pack (my chip run, PR 34).
BIAS_STEP = 0.005
BIAS_ROUNDS = 40
# The program takes a pack's (token, expert) assignments this many at a
# time, and reads the held experts' weights once a turn.
TURN_ASSIGNMENTS = 1 << 18

SIZE_KEYS = ('num_hidden_layers', 'hidden_size', 'num_attention_heads',
             'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
             'kv_lora_rank', 'rope_theta', 'rms_norm_eps',
             'intermediate_size', 'first_k_dense_replace', 'layer_pattern',
             'ffn_pattern', 'n_routed_experts', 'experts_held',
             'num_experts_per_tok', 'moe_intermediate_size',
             'n_shared_experts', 'norm_topk_prob', 'routed_scaling_factor',
             'scoring_func', 'topk_method', 'n_group', 'topk_group',
             'max_passes', 'max_length', 'total_rows', 'condense_input_size',
             'embedding', 'PW_MAX', 'IP_MAX', 'STRAND_MAX', 'SN_MAX')


def shape_of(config: dict) -> dict:
  return {k: config[k] for k in SIZE_KEYS}


def stated(params) -> dict:
  """The program's sizes under the file's keys: the published
  config.json's names for what it publishes, the program's own for the
  rest."""
  first = params.experts_held_first
  leading = params.first_k_dense_replace
  layers = params.num_hidden_layers
  return {
      'model_name': params.model_name,
      'block_kind': params.block_kind,
      'num_hidden_layers': layers,
      'hidden_size': params.hidden_size,
      'num_attention_heads': params.num_heads,
      'qk_nope_head_dim': params.qk_nope_head_dim,
      'qk_rope_head_dim': params.qk_rope_head_dim,
      'qk_head_dim': params.qk_nope_head_dim + params.qk_rope_head_dim,
      'v_head_dim': params.v_head_dim,
      'kv_lora_rank': params.kv_lora_rank,
      'q_lora_rank': params.q_lora_rank,
      'rope_theta': params.rope_theta,
      'rms_norm_eps': params.rms_norm_eps,
      'intermediate_size': params.filter_size,
      'first_k_dense_replace': leading,
      'layer_pattern': 'L' * layers,
      'ffn_pattern': ''.join(
          'D' if n < leading else FFN_EXPERTS for n in range(layers)),
      'n_routed_experts': params.num_experts,
      'experts_held': [first, first + params.experts_held_count],
      'num_experts_per_tok': params.num_experts_per_tok,
      'moe_intermediate_size': params.moe_intermediate_size,
      'n_shared_experts': (params.shared_expert_intermediate_size
                           / params.moe_intermediate_size),
      'shared_expert_intermediate_size':
          params.shared_expert_intermediate_size,
      'shared_expert_gated': params.shared_expert_gated,
      'norm_topk_prob': params.norm_topk_prob,
      'routed_scaling_factor': params.routed_scaling_factor,
      'scoring_func': params.router_scoring,
      'topk_method': ('noaux_tc' if params.router_selection_bias
                      else 'greedy'),
      'n_group': params.n_group,
      'topk_group': params.topk_group,
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
  """(H, heads, nope, rope, Dv, rank, dense width, E, held, F, Fs)."""
  first, end = shape['experts_held']
  f = shape['moe_intermediate_size']
  return (shape['hidden_size'], shape['num_attention_heads'],
          shape['qk_nope_head_dim'], shape['qk_rope_head_dim'],
          shape['v_head_dim'], shape['kv_lora_rank'],
          shape['intermediate_size'], shape['n_routed_experts'], end - first,
          f, int(shape['n_shared_experts'] * f))


def expert_layers(shape: dict) -> int:
  return shape['ffn_pattern'].count(FFN_EXPERTS)


# ------------------------------------------------------------------ the tree

def attention_specs(shape: dict, n: int):
  h, heads, nope, rope, dv, rank, *_ = _sizes(shape)
  att = ('encoder', f'latent_attention_{n}')
  return [
      (att + ('query', 'kernel'), (h, heads, nope + rope), 'fan_in', h),
      (att + ('kv_a', 'kernel'), (h, rank + rope), 'fan_in', h),
      (att + ('kv_a_norm', 'scale'), (rank,), 'norm', 0),
      (att + ('kv_b', 'kernel'), (rank, heads, nope + dv), 'fan_in', rank),
      (att + ('output_transform', 'kernel'), (heads, dv, h), 'fan_in',
       heads * dv),
  ]


def swiglu_specs(path: tuple, h: int, width: int):
  return [
      (path + ('gate_layer', 'kernel'), (h, width), 'fan_in', h),
      (path + ('up_layer', 'kernel'), (h, width), 'fan_in', h),
      (path + ('output_layer', 'kernel'), (width, h), 'fan_in', width),
  ]


def ffn_specs(shape: dict, n: int):
  """Layer n's feed-forward leaves, by the pattern."""
  h, *_, dense, n_experts, held, f, fs = _sizes(shape)
  if shape['ffn_pattern'][n] != FFN_EXPERTS:
    return swiglu_specs(('encoder', f'ffn_{n}'), h, dense)
  moe = ('encoder', f'moe_{n}')
  return [
      (moe + ('router', 'kernel'), (h, n_experts), 'router', h),
      (moe + ('router_selection_bias',), (n_experts,), 'zeros', 0),
      (moe + ('experts_gate',), (held, h, f), 'fan_in', h),
      (moe + ('experts_up',), (held, h, f), 'fan_in', h),
      (moe + ('experts_down',), (held, f, h), 'fan_in', f),
  ] + swiglu_specs(moe + ('shared_expert',), h, fs)


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
    specs += attention_specs(shape, n)
    specs.append((('encoder', f'ffn_wrapper_{n}', 'rms_norm', 'scale'), (h,),
                  'norm', 0))
    specs += ffn_specs(shape, n)
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
  if kind == 'norm':
    return uniform(0.5, 1.5)
  if kind == 'zeros':  # the selection bias: `balance_routers` sets it
    return jnp.zeros(shp, jnp.float32)
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
  """Parameters of the parts of a layer: the attention with the layer's
  two norms, the dense feed-forward, what lies beside the routed experts
  of an expert layer (router, bias, shared expert), one expert."""
  h = shape['hidden_size']
  count = lambda specs: sum(math.prod(shp) for _p, shp, _k, _f in specs)
  pattern = shape['ffn_pattern']
  out = {'attention': count(attention_specs(shape, 0)) + 2 * h}
  if 'D' in pattern:
    out['dense_ffn'] = count(ffn_specs(shape, pattern.index('D')))
  if FFN_EXPERTS in pattern:
    specs = ffn_specs(shape, pattern.index(FFN_EXPERTS))
    out['beside_experts'] = count(
        [s for s in specs if 'experts_' not in s[0][-1]])
    out['expert'] = 3 * h * shape['moe_intermediate_size']
  return out


def flops_per_window(shape: dict) -> dict:
  """Matrix-multiply FLOPs (2 x multiply-adds) one window needs, by part.
  Norms, rotary, the sigmoid, the top-k and the softmax count as nothing.
  The scores are counted once over the whole query/key head (nope + rope):
  two products or one over concatenated keys are the same multiply-adds.
  Every expert is held, so every assignment of every token is counted."""
  length = shape['max_length']
  h, heads, nope, rope, dv, rank, dense, n_experts, held, f, fs = _sizes(shape)
  if held != n_experts:
    raise ValueError('the work is counted with every expert held')
  layers = shape['num_hidden_layers']
  n_moe = expert_layers(shape)
  parts = {
      'condense': 2 * length * shape['condense_input_size'] * h,
      'attention_projections': layers * 2 * length * (
          h * heads * (nope + rope) + h * (rank + rope)
          + rank * heads * (nope + dv) + heads * dv * h),
      'latent_scores': layers * 2 * length * length * heads * (nope + rope),
      'latent_values': layers * 2 * length * length * heads * dv,
      'dense_ffn': (layers - n_moe) * 2 * length * 3 * h * dense,
      'router': n_moe * 2 * length * h * n_experts,
      'shared_expert': n_moe * 2 * length * 3 * h * fs,
      'experts': n_moe * 2 * length * shape['num_experts_per_tok'] * 3 * h * f,
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
  """{'flops', 'bytes'} of the routed experts as device scope `moe` covers
  them (router, dispatch, grouped products, combine; not the shared
  expert), all expert layers together, for `positions` routed positions of
  which `assignments_held` (token, expert) pairs fell on held experts,
  over `packs` packs: the router's product and three products an
  assignment; the stream in and out, the router's bfloat16 weights once a
  pack and the held experts' once a turn of TURN_ASSIGNMENTS assignments,
  as the program takes a pack. The sorted copy of the tokens is the
  program's choice and counts no bytes."""
  h, *_, n_experts, held, f, _fs = _sizes(shape)
  layers = expert_layers(shape)
  per_pack = positions // packs * shape['num_experts_per_tok']
  turns = packs * -(-per_pack // TURN_ASSIGNMENTS)
  return {
      'flops': (layers * positions * 2 * h * n_experts
                + assignments_held * 3 * 2 * h * f),
      'bytes': WEIGHT_BYTES * layers * (
          turns * held * 3 * h * f + packs * h * n_experts
          + 2 * positions * h),
  }


def part_work(shape: dict, batch: int, part: str) -> dict:
  """{'flops', 'bytes'} one pack needs of one part of the block, all
  layers together, as the device scope of that name covers it:

  'latent'  the attention operator alone: both score products and the
            values; q (nope + rope a head), k_nope, the one k_rope and v
            in, o out, once, in bfloat16; the [L, L] scores are the
            algorithm's temporaries and count no bytes.
  'moe'     the routed experts with every assignment held (`moe_work`)."""
  length = shape['max_length']
  _h, heads, nope, rope, dv, *_ = _sizes(shape)
  positions = batch * length
  if part == 'latent':
    flops = flops_per_window(shape)
    per_position = WEIGHT_BYTES * (
        heads * (nope + rope) + heads * nope + rope + 2 * heads * dv)
    return {'flops': batch * (flops['latent_scores'] + flops['latent_values']),
            'bytes': shape['num_hidden_layers'] * positions * per_position}
  if part == 'moe':
    return moe_work(
        shape, positions,
        expert_layers(shape) * positions * shape['num_experts_per_tok'], 1)
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
  return x * jax.lax.rsqrt(
      jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def rotary_pairs(x, theta: float):
  """x [B, L, N, D], positions 0..L-1: the published rotation, pair
  (2i, 2i + 1) turned by position * theta**(-2i / D)."""
  length, d = x.shape[1], x.shape[3]
  inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  cos = np.cos(angles).astype(np.float32)[None, :, None, :]
  sin = np.sin(angles).astype(np.float32)[None, :, None, :]
  even, odd = x[..., 0::2], x[..., 1::2]
  return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                   axis=-1).reshape(x.shape)


def published_order(columns):
  """[..., D] rotary columns in the program's order (halves: i, i + D/2)
  -> in the published one (pairs: 2i, 2i + 1)."""
  half = columns.shape[-1] // 2
  return jnp.stack([columns[..., :half], columns[..., half:]],
                   axis=-1).reshape(columns.shape)


def latent_attention(w, u, *, nope, rope, rank, theta, eps, rd, rotary=True):
  """The attention on the normed stream u [B, L, H], as published: the
  rotary key expanded to every head and concatenated, one product over
  keys of nope + rope. `rotary` False leaves the rotary part out of the
  score, which the fault tests turn."""
  w_q = w['query']['kernel']
  w_q = jnp.concatenate([w_q[..., :nope], published_order(w_q[..., nope:])],
                        axis=-1)
  w_kva = w['kv_a']['kernel']
  w_kva = jnp.concatenate(
      [w_kva[:, :rank], published_order(w_kva[:, rank:])], axis=-1)
  q = jnp.einsum('blh,hnd->blnd', rd(u), rd(w_q))
  kv_a = jnp.matmul(rd(u), rd(w_kva))
  latent = norm(kv_a[..., :rank], w['kv_a_norm']['scale'], eps)
  kv = jnp.einsum('blr,rnd->blnd', rd(latent), rd(w['kv_b']['kernel']))
  k_nope, v = kv[..., :nope], kv[..., nope:]
  q_rope = rotary_pairs(q[..., nope:], theta)
  k_rope = rotary_pairs(kv_a[..., None, rank:], theta)  # one head
  if not rotary:
    q_rope, k_rope = jnp.zeros_like(q_rope), jnp.zeros_like(k_rope)
  query = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
  key = jnp.concatenate(
      [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (rope,))], axis=-1)
  scores = jnp.einsum('bihd,bjhd->bhij', rd(query), rd(key)) * (
      (nope + rope) ** -0.5)
  out = jnp.einsum('bhij,bjhd->bihd', rd(jax.nn.softmax(scores, axis=-1)),
                   rd(v))
  return jnp.einsum('blnd,ndh->blh', rd(out),
                    rd(w['output_transform']['kernel']))


def attention_fn(w, norm_w, x, *, sizes: dict, precision: str, **faults):
  """x + attn(norm(x)) for one block of windows; the layer's leaves are
  upcast here, one layer at a time."""
  u = norm(x, _f32(norm_w)['rms_norm']['scale'], sizes['eps'])
  return x + latent_attention(_f32(w), u, rd=_rounder(precision), **sizes,
                              **faults)


def swiglu(w, n, rd):
  w = _f32(w)
  return jnp.matmul(
      rd(jax.nn.silu(jnp.matmul(rd(n), rd(w['gate_layer']['kernel'])))
         * jnp.matmul(rd(n), rd(w['up_layer']['kernel']))),
      rd(w['output_layer']['kernel']))


def dense_fn(w, norm_w, x, *, eps: float, precision: str):
  """x + ffn(norm(x)) of a dense layer for one block of windows."""
  n = norm(x, _f32(norm_w)['rms_norm']['scale'], eps)
  return x + swiglu(w, n, _rounder(precision))


def scores_fn(w, norm_w, x, *, eps: float, precision: str):
  """The normed tokens [T, H], their sigmoid scores over all E and the
  ungated shared expert's part of moe(n), for all T tokens."""
  rd = _rounder(precision)
  n = norm(x, _f32(norm_w)['rms_norm']['scale'], eps)
  scores = jax.nn.sigmoid(
      jnp.matmul(rd(n), rd(w['router']['kernel'].astype(jnp.float32))))
  return n, scores, swiglu(w['shared_expert'], n, rd)


def topk_indices(scores, bias, *, top_k: int, n_group: int, topk_group: int):
  """The published `get_topk_indices`: scores [T, E] -> experts [T, k]."""
  tokens, n_experts = scores.shape
  choice = scores + bias[None, :]
  grouped = choice.reshape(tokens, n_group, n_experts // n_group)
  group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
  kept = jax.lax.top_k(group_scores, topk_group)[1]
  group_mask = jnp.zeros_like(group_scores).at[
      jnp.arange(tokens)[:, None], kept].set(1.0)
  mask = jnp.broadcast_to(group_mask[..., None], grouped.shape).reshape(
      tokens, n_experts)
  return jax.lax.top_k(jnp.where(mask > 0, choice, 0.0), top_k)[1]


def weights_fn(scores, bias, *, top_k: int, n_group: int, topk_group: int,
               renormalise: bool, factor: float, bias_in_weights: bool = False):
  """(weights, experts) [T, k] of the published router: the k largest of
  s + b, weighed by s alone over their sum (+ 1e-20), times the factor.
  `bias_in_weights` takes the weights from s + b, which the fault tests
  turn."""
  bias = bias.astype(jnp.float32)
  top_e = topk_indices(scores, bias, top_k=top_k, n_group=n_group,
                       topk_group=topk_group)
  top_p = jnp.take_along_axis(
      scores + bias[None, :] if bias_in_weights else scores, top_e, axis=-1)
  if renormalise:
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
  return top_p * factor, top_e


def expert_fn(gate, up, down, e, n, token, weight, out, *, precision: str):
  """out with weight x expert_e(n[token]) added at rows `token`: expert e
  of the stacked leaves on the rows routed to it; its three matrices are
  upcast here, one expert at a time."""
  rd = _rounder(precision)
  pick = lambda w: rd(w[e].astype(jnp.float32))
  rows = rd(n[token])
  hidden = jax.nn.silu(jnp.matmul(rows, pick(gate))) * jnp.matmul(
      rows, pick(up))
  return out.at[token].add(
      weight[:, None] * jnp.matmul(rd(hidden), pick(down)))


def routed_experts(w, n, top_p, top_e, first: int, expert, row_step: int = 128):
  """sum over the held experts of p_e expert_e(n): a plain loop over the
  experts, each on the rows routed to it, gathered and scatter-added where
  the tokens lie (the rows padded to a multiple of `row_step` with row 0 at
  weight zero, so that few shapes compile). -> (float32 [T, H],
  assignments per held expert)."""
  top_p, top_e = np.asarray(top_p), np.asarray(top_e)
  held = w['experts_gate'].shape[0]
  out = jnp.zeros(n.shape, jnp.float32)
  counts = np.zeros(held, np.int64)
  for e in range(held):
    token, slot = np.nonzero(top_e == first + e)
    counts[e] = len(token)
    if not len(token):
      continue
    size = -(-len(token) // row_step) * row_step
    index, weight = np.zeros(size, np.int32), np.zeros(size, np.float32)
    index[:len(token)], weight[:len(token)] = token, top_p[token, slot]
    # A token names an expert at most once: plain indexed addition.
    out = expert(w['experts_gate'], w['experts_up'], w['experts_down'], e, n,
                 index, weight, out)
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


def balancing_bias(scores: np.ndarray, top_k: int) -> np.ndarray:
  """The selection bias b [E] by the published rule (auxiliary-loss-free
  balancing), from zero: BIAS_ROUNDS times, route the calibration tokens
  by the k largest of s + b and move every b_e by BIAS_STEP towards the
  mean load, b_e += u sign(mean load - load_e)."""
  n_experts = scores.shape[1]
  bias = np.zeros(n_experts, np.float32)
  for _ in range(BIAS_ROUNDS):
    top = np.argpartition(-(scores + bias), top_k - 1, axis=1)[:, :top_k]
    load = np.bincount(top.ravel(), minlength=n_experts)
    bias += np.float32(BIAS_STEP) * np.sign(load.mean() - load).astype(
        np.float32)
  return bias


def balance_routers(params, windows: np.ndarray, shape: dict):
  """The tree with its routers balanced, layer after layer, on what the
  plain reference makes of `windows` up to each layer (a router moves
  every later layer's tokens, so each is balanced on the tokens the
  balanced ones before it leave)."""
  return reference_forward(params, windows, shape, balance=True)[2]


def reference_forward(params, windows: np.ndarray, shape: dict,
                      precision: str = 'float32', block: int = 32,
                      factor=None, balance: bool = False, **faults):
  """(logits [S, L, 5], assignments [expert layers, held], the tree) of the
  plain reference; `reference_logits` says how. `balance` replaces each
  expert layer's router by `balanced_router` on its own tokens and its
  selection bias by `balancing_bias` on their scores before it routes
  them, and the tree returned is the balanced one."""
  rows = np.asarray(windows, np.float32)[..., 0].copy()
  p = shape['max_passes']
  rows[:, p:2 * p] = np.clip(rows[:, p:2 * p], 0, shape['PW_MAX'])
  rows[:, 2 * p:3 * p] = np.clip(rows[:, 2 * p:3 * p], 0, shape['IP_MAX'])
  rows[:, 4 * p + 1:] = np.clip(rows[:, 4 * p + 1:], 0, shape['SN_MAX'])
  _h, _heads, nope, rope, _dv, rank, *_ = _sizes(shape)
  eps = float(shape['rms_norm_eps'])
  sizes = dict(nope=nope, rope=rope, rank=rank, eps=eps,
               theta=float(shape['rope_theta']))
  top_k = shape['num_experts_per_tok']
  embed = jax.jit(functools.partial(embed_fn, max_passes=p,
                                    precision=precision))
  attention = jax.jit(functools.partial(
      attention_fn, sizes=sizes, precision=precision,
      **{k: v for k, v in faults.items() if k == 'rotary'}))
  dense = jax.jit(functools.partial(dense_fn, eps=eps, precision=precision))
  scores_of = jax.jit(functools.partial(scores_fn, eps=eps,
                                        precision=precision))
  weights_of = jax.jit(functools.partial(
      weights_fn, top_k=top_k, n_group=shape['n_group'],
      topk_group=shape['topk_group'],
      renormalise=bool(shape['norm_topk_prob']),
      factor=float(shape['routed_scaling_factor'] if factor is None
                   else factor),
      **{k: v for k, v in faults.items() if k == 'bias_in_weights'}))
  expert = jax.jit(functools.partial(expert_fn, precision=precision))
  head = jax.jit(functools.partial(head_fn, eps=eps))
  balanced = jax.jit(functools.partial(balanced_router, eps=eps))
  enc = dict(params['encoder'])
  n_windows, short = len(rows), -len(rows) % block
  if short:  # keep one compiled shape
    rows = np.concatenate(
        [rows, np.zeros((short,) + rows.shape[1:], np.float32)])
  blocks = range(0, len(rows), block)
  in_blocks = lambda fn, x, *w: np.concatenate(
      [np.asarray(fn(*w, jnp.asarray(x[lo:lo + block]))) for lo in blocks])
  counts = []
  with jax.default_matmul_precision('highest'):
    x = in_blocks(embed, rows, params)
    for i, letter in enumerate(shape['ffn_pattern']):
      x = in_blocks(attention, x, enc[f'latent_attention_{i}'],
                    enc[f'attention_wrapper_{i}'])
      if letter != FFN_EXPERTS:
        x = in_blocks(dense, x, enc[f'ffn_{i}'], enc[f'ffn_wrapper_{i}'])
        continue
      # The experts see every token of the sample at once, the padding
      # windows left out: an expert then has rows enough to count.
      moe = enc[f'moe_{i}']
      tokens = jnp.asarray(x[:n_windows].reshape(-1, x.shape[-1]))
      if balance:
        moe = dict(moe, router={'kernel': balanced(
            moe, enc[f'ffn_wrapper_{i}'], tokens)})
      n, scores, shared = scores_of(moe, enc[f'ffn_wrapper_{i}'], tokens)
      if balance:
        bias = moe['router_selection_bias']
        moe = enc[f'moe_{i}'] = dict(
            moe, router_selection_bias=jnp.asarray(
                balancing_bias(np.asarray(scores), top_k), bias.dtype))
        without = np.sort(np.asarray(weights_of(scores, bias)[1]))
      top_p, top_e = weights_of(scores, moe['router_selection_bias'])
      if balance:
        load = lambda e: np.bincount(np.asarray(e).ravel(),
                                     minlength=scores.shape[1])
        before, after = load(without), load(top_e)
        moved = (without != np.sort(np.asarray(top_e))).any(axis=1).mean()
        print(f'family: layer {i} balanced on {len(without)} tokens: load '
              f'max/mean {before.max() / before.mean():.3f} -> '
              f'{after.max() / after.mean():.3f}, |b| max '
              f'{float(jnp.abs(moe["router_selection_bias"]).max()):.4f}, '
              f'the bias moves the choice of {100 * moved:.1f}% of tokens',
              file=sys.stderr, flush=True)
      routed, took = routed_experts(moe, n, top_p, top_e,
                                    shape['experts_held'][0], expert)
      x[:n_windows] += np.asarray(routed + shared).reshape(
          (n_windows,) + x.shape[1:])
      counts.append(took)
    logits = in_blocks(head, x, params)
  return logits[:n_windows], np.stack(counts), dict(params, encoder=enc)


def reference_logits(params, windows: np.ndarray, shape: dict,
                     precision: str = 'float32', block: int = 32, **faults):
  """windows [S, R, L, 1] as generated -> reference logits [S, L, 5]:
  plain float32 under `jax.default_matmul_precision('highest')`, input
  clipping included; embedding, attention, dense feed-forward and head in
  blocks of windows, the experts of a layer over all the sample's tokens,
  one expert at a time. `precision` 'bfloat16' or 'fp8' rounds every
  matmul operand (activations and weights; for the attention q, the
  concatenated keys, the softmax weights and v) to that type before a
  float32-accumulated product; the router's sigmoid, bias and weights stay
  float32. `faults` (bias_in_weights=True: the weights taken from s + b;
  factor=1.0: the scaling factor dropped; rotary=False: the rotary part of
  the score left out) are for the tests that show the comparison sees
  them."""
  return reference_forward(params, windows, shape, precision, block,
                           **faults)[0]
