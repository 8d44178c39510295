"""Flax implementation of the DeepConsensus model zoo.

The flagship model is the gap-aware encoder-only transformer with
learned per-feature embeddings (reference:
deepconsensus/models/networks.py:368-520, encoder_stack.py:43-198,
attention_layer.py:34-237, ffn_layer.py:34-87), re-designed TPU-first:

* All per-row embedding lookups are a single vectorized gather per
  feature family (the reference loops over 85 rows in Python, emitting
  85 small gathers), so XLA sees a handful of large fused gathers.
* Attention uses one batched einsum per projection, a static banded
  mask, and optionally a Pallas fused kernel (ops/banded_attention).
* Compute runs in bfloat16 on the MXU with float32 parameters and a
  float32 softmax; ReZero residual scalars keep training stable.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import ml_collections
import numpy as np

from deepconsensus_tpu import constants
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.ops import gated_delta
from deepconsensus_tpu.ops import grouped_attention
from deepconsensus_tpu.ops import latent_attention
from deepconsensus_tpu.ops import moe
from deepconsensus_tpu.ops import pallas_util
from deepconsensus_tpu.ops import power_retention
from deepconsensus_tpu.parallel import ring_attention as ring_lib
from deepconsensus_tpu.preprocess.pileup import row_indices


def sinusoidal_position_encoding(
    length: int, hidden_size: int, min_timescale: float = 1.0,
    max_timescale: float = 1.0e4) -> np.ndarray:
  """Transformer timing signal: [sin | cos] halves, matching tf-models
  RelativePositionEmbedding used at networks.py:203,319-323."""
  position = np.arange(length, dtype=np.float32)
  num_timescales = hidden_size // 2
  log_increment = np.log(max_timescale / min_timescale) / max(
      num_timescales - 1, 1
  )
  inv_timescales = min_timescale * np.exp(
      np.arange(num_timescales, dtype=np.float32) * -log_increment
  )
  scaled = position[:, None] * inv_timescales[None, :]
  return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1)


class MaskedEmbed(nn.Module):
  """Embedding with zero vectors for id 0 and sqrt(dim) output scaling
  (reference ModifiedOnDeviceEmbedding: networks.py:42-63).

  The lookup is a contraction of a one-hot of the ids with the table,
  not a gather: on the TPU a gather is bound by the index (1.7 ns each,
  whatever the width) while XLA fuses the iota-compare into the
  product's operand, so the one-hot is never written. Scaling and the
  id-0 mask are applied to the [vocab, features] table, where they
  commute with selecting a row: each value is bit-identical to
  `take(table, ids, mode='clip') * sqrt(features) * (ids != 0)` for
  ids >= 0 (ids above the vocabulary clamp to its last row).
  """

  vocab_size: int
  features: int
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, ids: jnp.ndarray) -> jnp.ndarray:
    table = self.param(
        'embedding',
        nn.initializers.normal(stddev=self.features**-0.5),
        (self.vocab_size, self.features),
        jnp.float32,
    )
    # The table is rounded to the compute dtype by an operation XLA may not
    # skip: inside one fusion the TPU compiler drops a float32 -> bfloat16
    # -> float32 pair (excess precision), and the values would then depend
    # on whether the leaves were cast at load (inference_dtype) or here.
    finfo = jnp.finfo(self.dtype)
    table = jax.lax.reduce_precision(table, finfo.nexp, finfo.nmant)
    table = table.astype(self.dtype) * jnp.asarray(
        self.features**0.5, self.dtype)
    table = table.at[0].set(0)  # id 0 selects a zero row: the mask
    ids = jnp.clip(ids, 0, self.vocab_size - 1)
    onehot = ids[..., None] == jnp.arange(self.vocab_size, dtype=ids.dtype)
    # Each output is 1.0 x one table value plus zeros: exact in one
    # bfloat16 pass with a float32 accumulator; HIGHEST keeps float32
    # operands exact too (and is ignored for bfloat16 ones).
    emb = jnp.einsum(
        '...v,ve->...e', onehot.astype(self.dtype), table,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return emb.astype(self.dtype)


class BandedSelfAttention(nn.Module):
  """Multi-head self-attention with a static banded (local) mask
  (reference Attention/SelfAttention: attention_layer.py:34-237)."""

  hidden_size: int
  num_heads: int
  dropout_rate: float
  attn_win_size: Optional[int]
  dtype: Any = jnp.float32
  use_pallas: bool = False
  # Softmax accumulation dtype (XLA path). float32 matches the
  # reference; bfloat16 is a candidate MFU lever (drops the f32
  # up/downcast round-trip around the [B, N, L, L] weights) to A/B on
  # hardware — banded logits are bounded, so bf16 is numerically safe
  # at inference; keep f32 for training unless measured otherwise.
  softmax_dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool,
               ragged_widths: Optional[jnp.ndarray] = None,
               ragged_buckets: Optional[tuple] = None) -> jnp.ndarray:
    if self.hidden_size % self.num_heads:
      raise ValueError('hidden_size must be divisible by num_heads')
    head_dim = self.hidden_size // self.num_heads
    dense = lambda name: nn.DenseGeneral(
        features=(self.num_heads, head_dim),
        axis=-1,
        use_bias=False,
        dtype=self.dtype,
        kernel_init=nn.initializers.glorot_uniform(),
        name=name,
    )
    query_raw = dense('query')(x)
    query = query_raw * (head_dim**-0.5)
    key = dense('key')(x)
    value = dense('value')(x)

    if ragged_widths is not None:
      # Ragged slots (inference, use_ragged_kernel): x holds windows of
      # bucket widths packed back-to-back into slots of length S, every
      # window starting at a multiple of its own width (the divisibility
      # -chain packing invariant). The projections above are position-
      # wise, so reshaping [B, S] to [B*S/w, w] recovers each width-w
      # window as one contiguous attention batch whose compute is THE
      # SAME SHAPE as the bucketed path's — XLA produces bitwise-equal
      # outputs (a masked wide softmax would not: reassociating the
      # reduction over a different contraction length drifts 1 ulp).
      # Each position then selects the candidate from its own width.
      out = jnp.zeros(query.shape, query.dtype)
      bsz, length = x.shape[0], x.shape[1]
      for w in ragged_buckets:
        n = bsz * length // w
        shaped = lambda a: a.reshape(n, w, self.num_heads, head_dim)
        logits = jnp.einsum('BTNH,BFNH->BNFT', shaped(key), shaped(query))
        if self.attn_win_size:
          i = np.arange(w)
          band = np.abs(i[:, None] - i[None, :]) <= self.attn_win_size
          logits = jnp.where(band[None, None, :, :], logits, -1e9)
        weights = jax.nn.softmax(
            logits.astype(self.softmax_dtype), axis=-1
        ).astype(self.dtype)
        cand = jnp.einsum(
            'BNFT,BTNH->BFNH', weights, shaped(value)
        ).reshape(bsz, length, self.num_heads, head_dim)
        out = out + jnp.where(
            (ragged_widths == w)[:, :, None, None], cand,
            jnp.zeros((), cand.dtype))
      return nn.DenseGeneral(
          features=self.hidden_size,
          axis=(-2, -1),
          use_bias=False,
          dtype=self.dtype,
          kernel_init=nn.initializers.glorot_uniform(),
          name='output_transform',
      )(out)

    use_dropout = not deterministic and self.dropout_rate > 0.0
    if (x.shape[1] >= config_lib.RING_ATTENTION_MIN_LEN
        and not use_dropout):
      # Long-insert windows: past the crossover the [B, N, L, L]
      # logits/weights tensors dominate memory (at L=500 the fused
      # kernel's whole-L VMEM tiling no longer fits either), so
      # attention runs as the blockwise ring scan — exact, banded, and
      # differentiable, with K/V streamed through the online softmax.
      # The scan never materializes attention weights, so weight
      # dropout is unavailable here; long-insert configs set
      # attention_dropout=0 (training with dropout falls through to
      # the paths below).
      out = ring_lib.ring_attention_blockwise(
          query_raw, key, value, self.attn_win_size or None
      )
      return nn.DenseGeneral(
          features=self.hidden_size,
          axis=(-2, -1),
          use_bias=False,
          dtype=self.dtype,
          kernel_init=nn.initializers.glorot_uniform(),
          name='output_transform',
      )(out)
    use_pallas = self.use_pallas
    long_window = False
    if use_pallas:
      # Fused VMEM kernel with custom VJP, so it serves training too.
      # Dropout uses a caller-generated bernoulli keep-mask shared by
      # forward and backward (ops/banded_attention.py).
      from deepconsensus_tpu.ops import banded_attention as ba
      from deepconsensus_tpu.ops import flash_band_attention as fba

      long_window = x.shape[1] > fba.WHOLE_L_LIMIT
      if use_dropout and long_window:
        # The whole-L dropout kernel stops compiling past its VMEM
        # limit and would materialize a [B, N, L, L] bernoulli mask;
        # long-window training with attention dropout routes to the
        # XLA path below instead (the flash kernel has no dropout).
        use_pallas = False
    if use_pallas:
      if long_window:
        # Long windows: the whole-L kernel's [G, L, L] VMEM block no
        # longer fits; the block-banded flash kernel scales as L*band
        # instead (its speed against the XLA path is not measured) and
        # trains through its own custom VJP.
        out = fba.flash_band_attention_vjp(
            query, key, value, self.attn_win_size or None
        )
      elif not use_dropout:
        out = ba.banded_attention_vjp(
            query, key, value, self.attn_win_size or None
        )
      else:
        b, l, n, _ = query.shape
        keep_prob = 1.0 - self.dropout_rate
        mask = jax.random.bernoulli(
            self.make_rng('dropout'), keep_prob, (b, n, l, l)
        ).astype(jnp.uint8)
        out = ba.banded_attention_dropout_vjp(
            query, key, value, mask, self.attn_win_size or None,
            keep_prob,
        )
    else:
      # [B, N, Lq, Lk]
      logits = jnp.einsum('BTNH,BFNH->BNFT', key, query)
      length = x.shape[1]
      if self.attn_win_size:
        i = np.arange(length)
        band = np.abs(i[:, None] - i[None, :]) <= self.attn_win_size
        logits = jnp.where(band[None, None, :, :], logits, -1e9)
      weights = jax.nn.softmax(
          logits.astype(self.softmax_dtype), axis=-1
      ).astype(self.dtype)
      # Expose attention maps like the reference's intermediate outputs
      # (attention_scores_{n}: encoder_stack.py:184-187); retrieve with
      # apply(..., capture_intermediates=True).
      self.sow('intermediates', 'attention_scores', weights)
      weights = nn.Dropout(rate=self.dropout_rate)(
          weights, deterministic=deterministic
      )
      out = jnp.einsum('BNFT,BTNH->BFNH', weights, value)
    return nn.DenseGeneral(
        features=self.hidden_size,
        axis=(-2, -1),
        use_bias=False,
        dtype=self.dtype,
        kernel_init=nn.initializers.glorot_uniform(),
        name='output_transform',
    )(out)


class FeedForward(nn.Module):
  """filter_size relu -> hidden_size (reference ffn_layer.py:34-87)."""

  hidden_size: int
  filter_size: int
  dropout_rate: float
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
    h = nn.Dense(self.filter_size, dtype=self.dtype, name='filter_layer')(x)
    h = nn.relu(h)
    h = nn.Dropout(rate=self.dropout_rate)(h, deterministic=deterministic)
    return nn.Dense(self.hidden_size, dtype=self.dtype, name='output_layer')(h)


class RMSNorm(nn.Module):
  """x / rms(x) * scale over the last axis, reckoned in float32 and
  returned in `dtype`; `zero_centred` weights start at 0 and multiply as
  1 + scale."""

  epsilon: float
  dtype: Any = jnp.float32
  zero_centred: bool = False

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
    scale = self.param(
        'scale',
        nn.initializers.zeros if self.zero_centred else nn.initializers.ones,
        (x.shape[-1],), jnp.float32).astype(jnp.float32)
    if self.zero_centred:
      scale = 1.0 + scale
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(
        jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
    return (y * scale).astype(self.dtype)


class BiasFreeLayerNorm(nn.Module):
  """(x - mean(x)) * rsqrt(var(x) + epsilon) * scale over the last axis, no
  bias: reckoned in float32 (the variance of the centred values, two
  passes) and returned in `dtype`."""

  epsilon: float
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
    scale = self.param('scale', nn.initializers.ones, (x.shape[-1],),
                       jnp.float32).astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(
        jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + self.epsilon)
    return (y * scale).astype(self.dtype)


@dataclasses.dataclass(frozen=True)
class Rope:
  """A layer type's rotary position embedding, as a published
  `rope_parameters` entry states it: `default`, frequencies
  theta**(-2i/D) at magnitude 1, or `yarn`, those frequencies interpolated
  by `factor` where a dimension turns fewer than `beta_slow` times over
  `original_max_position` positions, kept where it turns more than
  `beta_fast` times, a linear ramp between, and cos and sin multiplied by
  `attention_factor` (so q . k carries its square)."""

  theta: float
  kind: str = 'default'
  factor: float = 1.0
  original_max_position: int = 0
  beta_fast: float = 32.0
  beta_slow: float = 1.0
  attention_factor: float = 1.0

  @classmethod
  def of(cls, parameters) -> 'Rope':
    """From a published `rope_parameters` entry; a rope_type this module
    does not compute is refused by name."""
    parameters = dict(parameters)
    kind = parameters.get('rope_type', 'default')
    theta = float(parameters['rope_theta'])
    if kind == 'default':
      return cls(theta)
    if kind != 'yarn':
      raise ValueError(f'rope_type {kind!r} is not served; Rope computes '
                       "'default' and 'yarn'")
    return cls(theta, 'yarn', float(parameters['factor']),
               int(parameters['original_max_position_embeddings']),
               float(parameters.get('beta_fast', 32)),
               float(parameters.get('beta_slow', 1)),
               float(parameters['attention_factor']))

  def describe(self) -> str:
    """What `forward_launch` says of it: `default`, or `yarn×<factor>`."""
    return 'default' if self.kind == 'default' else f'yarn×{self.factor:g}'


def rope_frequencies(rope, head_dim: int):
  """(inverse frequencies [head_dim / 2] float64, magnitude) of a Rope, or
  of the default law at a bare base theta."""
  if not isinstance(rope, Rope):
    rope = Rope(float(rope))
  inv = rope.theta ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                       / head_dim)
  if rope.kind == 'default':
    return inv, 1.0
  # The dimension that turns `rotations` times over the original positions.
  dim_of = lambda rotations: head_dim * math.log(
      rope.original_max_position / (rotations * 2 * math.pi)) / (
          2 * math.log(rope.theta))
  low = max(math.floor(dim_of(rope.beta_fast)), 0)
  high = min(math.ceil(dim_of(rope.beta_slow)), head_dim - 1)
  ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 1e-3),
                 0.0, 1.0)
  return inv * ((1.0 - ramp) + ramp / rope.factor), rope.attention_factor


def rotary_tables(length: int, head_dim: int, rope):
  """(cos, sin) [L, head_dim] float32 of the rotate-half rotary position
  embedding: `rope_frequencies` over the first half of the head, repeated
  over the second, times the rope's magnitude. `rope` is a Rope or the
  default law's base theta."""
  inv, magnitude = rope_frequencies(rope, head_dim)
  angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
  angles = np.concatenate([angles, angles], axis=1)
  return ((np.cos(angles) * magnitude).astype(np.float32),
          (np.sin(angles) * magnitude).astype(np.float32))


def apply_rotary(x: jnp.ndarray, rope,
                 rotary_dim: Optional[int] = None) -> jnp.ndarray:
  """x [B, L, N, D] float32, positions 0..L-1 -> x rotated by `rope` (a
  Rope, or the default law's base theta); with `rotary_dim`, the first
  `rotary_dim` of the D alone (at frequencies over `rotary_dim`), the rest
  as they are."""
  if rotary_dim is not None and rotary_dim != x.shape[3]:
    return jnp.concatenate(
        [apply_rotary(x[..., :rotary_dim], rope), x[..., rotary_dim:]],
        axis=-1)
  cos, sin = rotary_tables(x.shape[1], x.shape[3], rope)
  half = x.shape[3] // 2
  rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
  return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


@functools.lru_cache(maxsize=None)
def _flat_rotary_tables(length: int, rotary_dim: int, rope,
                        windows: int, heads: int):
  """(cos, sin) [windows * L, heads * rotary_dim / 2] float32: a half
  head's tables side by side a head and `windows` times down the rows.
  One array a stack, so that its layers share one constant."""
  cos, sin = rotary_tables(length, rotary_dim, rope)
  tiled = lambda table: np.tile(table[:, :rotary_dim // 2], (windows, heads))
  return tiled(cos), tiled(sin)


def apply_rotary_flat(first: jnp.ndarray, second: jnp.ndarray, length: int,
                      rope, rotary_dim: int):
  """The first halves of N heads and their second halves, each
  [B*L, N * rotary_dim / 2] with windows of `length` rows one after another
  and the heads along the lanes -> both rotated in float32 as `apply_rotary`
  rotates a head [first | second], to the bit (the same two products a
  value and their sum). Halves apart, a value and the one it turns with lie
  in the same lane of two arrays, so the rotation shifts nothing; the
  tables are laid out a row of the flat stream, a few windows tiled down
  the rows, and never as [B, L, ...]."""
  rows, width = first.shape
  # As many windows as make whole sublane tiles of every type: tiling that
  # many down the rows is a broadcast in front of a view that copies
  # nothing.
  windows = math.lcm(length, 16) // length
  if rows // length % windows:
    windows = 1
  cos, sin = (
      jnp.tile(table, (rows // (windows * length), 1))
      for table in _flat_rotary_tables(
          length, rotary_dim, rope, windows, 2 * width // rotary_dim))
  first, second = first.astype(jnp.float32), second.astype(jnp.float32)
  return first * cos + -second * sin, second * cos + first * sin


class _GateProjection(nn.Module):
  """x [..., H] -> x W (+ b) [..., features] in float32: logits that
  are summed along the window (the retention gate), exponentiated (the
  delta rule's decay) or ranked (the router) leave the matmul
  unrounded."""

  features: int
  use_bias: bool = True

  @nn.compact
  def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
    kernel = self.param('kernel', nn.initializers.lecun_normal(),
                        (x.shape[-1], self.features), jnp.float32)
    out = jnp.einsum('...h,hk->...k', x, kernel.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    if not self.use_bias:
      return out
    bias = self.param('bias', nn.initializers.zeros, (self.features,),
                      jnp.float32)
    return out + bias.astype(jnp.float32)


class PowerRetentionAttention(nn.Module):
  """Gated power retention over grouped heads, two directions
  (ops/power_retention.py): bias-free q/k/v projections, q and k each
  RMSNorm'd over the head and given rotary positions, one gate per
  key-value head, bias-free output projection."""

  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  rope_theta: float
  rms_norm_eps: float
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
    del deterministic  # the published layer has no dropout
    dense = lambda name, heads: nn.DenseGeneral(
        features=(heads, self.head_dim), axis=-1, use_bias=False,
        dtype=self.dtype, kernel_init=nn.initializers.lecun_normal(),
        name=name)
    head_norm = lambda name: RMSNorm(self.rms_norm_eps, name=name)
    query = head_norm('query_norm')(dense('query', self.num_heads)(x))
    key = head_norm('key_norm')(dense('key', self.num_kv_heads)(x))
    value = dense('value', self.num_kv_heads)(x)
    with jax.named_scope('rotary'):
      # dclint: allow=dtype-downcast (q and k meet in the compute dtype,
      # after norm and rotation in float32)
      query = apply_rotary(query, self.rope_theta).astype(self.dtype)
      # dclint: allow=dtype-downcast (as above)
      key = apply_rotary(key, self.rope_theta).astype(self.dtype)
    log_gate = jax.nn.log_sigmoid(
        _GateProjection(self.num_kv_heads, name='gate')(x))
    with jax.named_scope('retention'):
      out = power_retention.power_retention_bidirectional(
          query, key, value, log_gate)
    return nn.DenseGeneral(
        features=self.hidden_size, axis=(-2, -1), use_bias=False,
        dtype=self.dtype, kernel_init=nn.initializers.lecun_normal(),
        name='output_transform')(out)


class GatedDeltaNetMixer(nn.Module):
  """Gated DeltaNet mixer, two directions (ops/gated_delta.py): one
  bias-free projection to [q | k | v | z] and one, float32, to [b | a];
  a causal depthwise convolution of `conv_kernel` positions and silu over
  concat(q, k, v), run from the window's start and from its end with the
  same weights; q and k L2-normalised over the head, q scaled by Dk^-1/2;
  beta = sigmoid(b), log decay g = -exp(A_log) softplus(a + dt_bias); the
  two directions' outputs added, then RMSNorm over each value head (plain
  weight) gated by silu(z), and a bias-free output projection."""

  hidden_size: int
  num_key_heads: int
  num_value_heads: int
  key_head_dim: int
  value_head_dim: int
  conv_kernel: int
  rms_norm_eps: float
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
    del deterministic  # the published layer has no dropout
    hk, hv = self.num_key_heads, self.num_value_heads
    key_dim, value_dim = hk * self.key_head_dim, hv * self.value_head_dim
    length = x.shape[1]
    dense = lambda width, name: nn.Dense(
        width, use_bias=False, dtype=self.dtype,
        kernel_init=nn.initializers.lecun_normal(), name=name)
    qkvz = dense(2 * key_dim + 2 * value_dim, 'in_proj_qkvz')(x)
    mixed, z = qkvz[..., :2 * key_dim + value_dim], qkvz[..., -value_dim:]
    b, a = jnp.split(
        _GateProjection(2 * hv, use_bias=False, name='in_proj_ba')(x), 2,
        axis=-1)
    conv = self.param('conv_kernel', nn.initializers.lecun_normal(),
                      (self.conv_kernel, mixed.shape[-1]), jnp.float32)
    a_log = self.param('A_log', nn.initializers.zeros, (hv,), jnp.float32)
    dt_bias = self.param('dt_bias', nn.initializers.ones, (hv,), jnp.float32)
    norm_scale = self.param('norm_scale', nn.initializers.ones,
                            (self.value_head_dim,), jnp.float32)
    beta = jax.nn.sigmoid(b)
    log_decay = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a + dt_bias.astype(jnp.float32))

    # The run from the window's start, whose convolution reads positions
    # t - (kernel - 1) ... t, and the run from its end, which reads
    # t + (kernel - 1) ... t with the same weights. One pass over `mixed`,
    # float32 inside, the compute dtype out.
    reach = self.conv_kernel - 1
    padded = jnp.pad(mixed, ((0, 0), (reach, reach), (0, 0)))
    # dclint: allow=dtype-downcast (the convolution's output is the
    # compute dtype, as a bfloat16 model's is)
    taps = lambda starts: jax.nn.silu(sum(
        padded[:, start:start + length].astype(jnp.float32)
        * conv[i].astype(jnp.float32)
        for i, start in enumerate(starts))).astype(self.dtype)
    streams = (taps(range(self.conv_kernel)),
               taps(range(2 * reach, reach - 1, -1)))
    # Between the convolution and the output projection the stream stays
    # flat, heads along the lanes: the L2 norm of q and k, the rule in
    # two directions and the gated norm are one operator
    # (gated_delta.gated_delta_window), one Pallas call a window where
    # its rule says so.
    with jax.named_scope('gdn'):
      out = gated_delta.gated_delta_window(
          streams, z, log_decay, beta, norm_scale, num_key_heads=hk,
          num_value_heads=hv, epsilon=self.rms_norm_eps)
    return dense(self.hidden_size, 'out_proj')(out)


class GroupedSoftmaxAttention(nn.Module):
  """Softmax attention with grouped heads: query head h reads key-value
  head h // (heads // kv heads). What a layer adds to that is its sizes:

  `output_gate`  the query projection yields, per head, the query and a
                 gate of the same size, and the attention's output is
                 multiplied by sigmoid(gate) before the output projection;
  `qk_norm`      q and k are RMSNorm'd over the head (zero-centred
                 weights, `rms_norm_eps`);
  `rotary_dim`   q and k are rotated by `rope` (a Rope, or the default
                 law's base theta) on the first `rotary_dim` of the head
                 (0: the layer has no positions at all), inside scope
                 `rotary`;
  `window`       position i attends to j only where |i - j| < window, both
                 ways (None: the whole window). A window that covers the
                 forward's length masks nothing and builds no mask.

  No biases; the softmax is float32.

  Called with `window_length`, x is the flat stream [B*L, H] (or [B, L, H],
  flattened here and given back so), windows of that many rows one after
  another, and the operator with the rotation of q and k is the Pallas
  call a tile of windows (`grouped_attention.window_tile_attention`): the
  caller asked the rule (`kernel_paths`' `grouped_attention_path`). The
  leaves are the ones the modules below declare, contracted flat, heads
  along the lanes."""

  hidden_size: int
  num_heads: int
  num_kv_heads: int
  head_dim: int
  rotary_dim: int
  rope: Any
  rms_norm_eps: Optional[float] = None
  output_gate: bool = True
  qk_norm: bool = True
  window: Optional[int] = None
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool,
               window_length: Optional[int] = None) -> jnp.ndarray:
    del deterministic
    n_q, n_kv, d = self.num_heads, self.num_kv_heads, self.head_dim
    if n_q % n_kv:
      raise ValueError(f'{n_q} query heads do not group over {n_kv} '
                       'key-value heads')
    if window_length is not None:
      return self._on_the_flat_stream(
          x.reshape(-1, x.shape[-1]), window_length).reshape(x.shape)
    batch, length, _ = x.shape
    dense = lambda name, heads, width: nn.DenseGeneral(
        features=(heads, width), axis=-1, use_bias=False, dtype=self.dtype,
        kernel_init=nn.initializers.lecun_normal(), name=name)
    if self.output_gate:
      query, gate = jnp.split(dense('query', n_q, 2 * d)(x), 2, axis=-1)
    else:
      query = dense('query', n_q, d)(x)
    key = dense('key', n_kv, d)(x)
    if self.qk_norm:
      head_norm = lambda name: RMSNorm(self.rms_norm_eps, zero_centred=True,
                                       name=name)
      query = head_norm('query_norm')(query)
      key = head_norm('key_norm')(key)
    value = dense('value', n_kv, d)(x)
    if self.rotary_dim:
      # dclint: allow=dtype-downcast (q and k meet in the compute dtype,
      # after norm and rotation in float32)
      rotate = lambda t: apply_rotary(
          t.astype(jnp.float32), self.rope, self.rotary_dim).astype(
              self.dtype)
      with jax.named_scope('rotary'):
        query, key = rotate(query), rotate(key)
    with jax.named_scope('softmax'):
      grouped = query.reshape(batch, length, n_kv, n_q // n_kv, d)
      scores = jnp.einsum('blkgd,bmkd->bkglm', grouped, key,
                          preferred_element_type=jnp.float32)
      scores = scores * jnp.float32(d ** -0.5)
      if self.window is not None and length > self.window:
        i = np.arange(length)
        near = np.abs(i[:, None] - i[None, :]) < self.window
        scores = jnp.where(near, scores, jnp.float32(-1e9))
      weights = jax.nn.softmax(scores, axis=-1)
      out = jnp.einsum('bkglm,bmkd->blkgd', weights.astype(self.dtype), value,
                       preferred_element_type=jnp.float32)
    out = out.reshape(batch, length, n_q, d)
    if self.output_gate:
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
    # dclint: allow=dtype-downcast (the values' sum and the gate are
    # float32; the stream is the compute dtype)
    out = out.astype(self.dtype)
    return nn.DenseGeneral(
        features=self.hidden_size, axis=(-2, -1), use_bias=False,
        dtype=self.dtype, kernel_init=nn.initializers.lecun_normal(),
        name='output_transform')(out)

  def _on_the_flat_stream(self, x: jnp.ndarray, length: int) -> jnp.ndarray:
    """The same sublayer on x [B*L, H]. q, k and v are each one product of
    x against its leaf laid flat, [H, heads x D], so a head is a lane tile
    of what the kernel reads; the rotation is the kernel's prologue. A gate
    and q/k norms, where the layer has them, run round the call in XLA:
    the normed q and k are rounded to the compute dtype before the
    rotation, and the gate multiplies o as the call rounded it. No
    [B, L, N, D] array of q, k or v is laid out."""
    n_q, n_kv, d = self.num_heads, self.num_kv_heads, self.head_dim
    params = self.variables['params']
    leaf = lambda name: params[name]['kernel'].astype(self.dtype)
    flat = lambda w: w.reshape(w.shape[0], -1)
    query_leaf = leaf('query')
    query = jnp.dot(x, flat(query_leaf[..., :d]))
    key, value = jnp.dot(x, flat(leaf('key'))), jnp.dot(x, flat(leaf('value')))
    if self.qk_norm:
      # dclint: allow=dtype-downcast (q and k meet in the compute dtype)
      head_norm = lambda name, t, heads: RMSNorm(
          self.rms_norm_eps, zero_centred=True, name=name)(
              t.reshape(-1, heads, d)).reshape(t.shape).astype(self.dtype)
      query = head_norm('query_norm', query, n_q)
      key = head_norm('key_norm', key, n_kv)
    tables = (None, None)
    if self.rotary_dim:
      tables = grouped_attention.signed_tables(
          *rotary_tables(length, d, self.rope))
    with jax.named_scope('softmax'):
      out = grouped_attention.window_tile_attention(
          query, key, value, *tables, length=length, num_heads=n_q,
          num_kv_heads=n_kv, scale=d ** -0.5)
    if self.output_gate:
      gate = jnp.dot(x, flat(query_leaf[..., d:]))
      # dclint: allow=dtype-downcast (as in the plain form)
      out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
          self.dtype)
    return jnp.dot(out, leaf('output_transform').reshape(n_q * d, -1))


class LatentAttention(nn.Module):
  """Multi-head latent attention in its whole-window form
  (ops/latent_attention.py), no query latent: per head [q_nope | q_rope] =
  x W_q; [c | k_rope] = x W_kva, c RMSNorm'd over the latent; per head
  [k_nope | v] = c W_kvb; q_rope and the ONE k_rope that all heads share
  rotated by position; scores scaled by (nope + rope)^-1/2; bias-free
  output projection over the heads' values. The rotation is
  `apply_rotary`'s, over halves: a checkpoint published for interleaved
  pairs loads with its rotary columns in
  `latent_attention.halves_from_pairs` order.

  Called with `window_length`, x is the flat stream [B*L, H], windows of
  that many rows one after another, and the operator is the Pallas call a
  tile of windows (`latent_attention.window_tile_attention`): the caller
  asked the rule (`kernel_paths`' `latent_attention_path`). The leaves are
  the ones the modules below declare, contracted flat, heads along the
  lanes."""

  hidden_size: int
  num_heads: int
  qk_nope_head_dim: int
  qk_rope_head_dim: int
  v_head_dim: int
  kv_lora_rank: int
  rope_theta: float
  rms_norm_eps: float
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool,
               window_length: Optional[int] = None) -> jnp.ndarray:
    del deterministic  # the published layer has no dropout
    if window_length is not None:
      return self._on_the_flat_stream(x, window_length)
    n, nope, rope = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
    dense = lambda name, features, axis=-1: nn.DenseGeneral(
        features=features, axis=axis, use_bias=False, dtype=self.dtype,
        kernel_init=nn.initializers.lecun_normal(), name=name)
    q_nope, q_rope = jnp.split(dense('query', (n, nope + rope))(x), [nope],
                               axis=-1)
    latent, k_rope = jnp.split(dense('kv_a', self.kv_lora_rank + rope)(x),
                               [self.kv_lora_rank], axis=-1)
    latent = RMSNorm(self.rms_norm_eps, dtype=self.dtype, name='kv_a_norm')(
        latent)
    k_nope, value = jnp.split(dense('kv_b', (n, nope + self.v_head_dim))(
        latent), [nope], axis=-1)
    # dclint: allow=dtype-downcast (rotated in float32; q and k meet in
    # the compute dtype)
    rotate = lambda t: apply_rotary(t.astype(jnp.float32),
                                    self.rope_theta).astype(self.dtype)
    with jax.named_scope('rotary'):
      q_rope = rotate(q_rope)
      k_rope = rotate(k_rope[:, :, None, :])[:, :, 0, :]
    with jax.named_scope('latent'):
      out = latent_attention.latent_attention(
          q_nope, q_rope, k_nope, k_rope, value,
          scale=(nope + rope) ** -0.5)
    return dense('output_transform', self.hidden_size, axis=(-2, -1))(out)

  def _on_the_flat_stream(self, x: jnp.ndarray, length: int) -> jnp.ndarray:
    """The same sublayer on x [B*L, H]. Every product is the modules' own
    dot product an output element, written where the kernel reads it: the
    query's position-free columns of all heads as one product, so that a
    head's position-free part is a whole lane tile, and its rotary columns
    as another, the heads' first halves in front of their second halves,
    so that the rotation shifts nothing along the lanes
    (`apply_rotary_flat`); `kv_b`'s output as it lies, a head's k_nope and
    then its v. No [B, L, N, D] array of q, k or v is laid out."""
    n, nope, rope = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
    rank, h = self.kv_lora_rank, self.hidden_size
    params = self.variables['params']
    flat = lambda name, rows: params[name]['kernel'].astype(
        self.dtype).reshape(rows, -1)
    w_nope, w_halves = latent_attention.flat_query_kernels(
        params['query']['kernel'].astype(self.dtype), nope)
    half = rope // 2
    q_nope, q_halves = jnp.dot(x, w_nope), jnp.dot(x, w_halves)
    latent_and_key = jnp.dot(x, flat('kv_a', h))
    latent = RMSNorm(self.rms_norm_eps, dtype=self.dtype, name='kv_a_norm')(
        latent_and_key[:, :rank])
    kv = jnp.dot(latent, flat('kv_b', rank))
    # dclint: allow=dtype-downcast (rotated in float32; q and k meet in
    # the compute dtype)
    rotate = lambda first, second: tuple(
        t.astype(self.dtype) for t in apply_rotary_flat(
            first, second, length, self.rope_theta, rope))
    with jax.named_scope('rotary'):
      q_first, q_second = rotate(q_halves[:, :n * half],
                                 q_halves[:, n * half:])
      key_halves = rotate(latent_and_key[:, rank:rank + half],
                          latent_and_key[:, rank + half:])
    keys = latent_attention.placed_rotary_keys(*key_halves)
    with jax.named_scope('latent'):
      out = latent_attention.window_tile_attention(
          q_nope, q_first, q_second, kv, keys, length=length, num_heads=n,
          scale=(nope + rope) ** -0.5)
    return jnp.dot(out, flat('output_transform', n * self.v_head_dim))


class GatedFeedForward(nn.Module):
  """SwiGLU: (silu(x W_gate) * (x W_up)) W_down, no biases."""

  hidden_size: int
  filter_size: int
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
    del deterministic
    dense = lambda width, name: nn.Dense(
        width, use_bias=False, dtype=self.dtype,
        kernel_init=nn.initializers.lecun_normal(), name=name)
    h = nn.silu(dense(self.filter_size, 'gate_layer')(x))
    h = h * dense(self.filter_size, 'up_layer')(x)
    return dense(self.hidden_size, 'output_layer')(h)


class SparseExpertsFeedForward(nn.Module):
  """Routed experts plus a shared expert (ops/moe.py): a float32 router
  over all `num_experts`, scored by `scoring` (a softmax, or a sigmoid
  each), the `experts_per_token` largest kept (of scores +
  `router_selection_bias` [num_experts] where `selection_bias`: the bias
  chooses and weighs nothing), renormalised where `norm_topk` and scaled
  by `routed_scale`; the products of the experts `held_first` ...
  `held_first + held_count - 1` alone, each a SwiGLU of `expert_width`;
  plus a SwiGLU of `shared_width`, times sigmoid(x w_s) where
  `shared_gate` and times `shared_scale` (1 / m makes one SwiGLU of m x the
  width the mean of m shared experts: the down product is linear); a
  `shared_width` of 0 is no shared expert: no leaf, nothing added. The
  assignments each held expert took are sown as
  `assignments` in the `moe_counts` collection, for whoever asks for it."""

  hidden_size: int
  num_experts: int
  experts_per_token: int
  expert_width: int
  shared_width: int
  norm_topk: bool
  held_first: int
  held_count: int
  scoring: str = moe.SCORING_SOFTMAX
  selection_bias: bool = False
  routed_scale: float = 1.0
  shared_gate: bool = True
  shared_scale: float = 1.0
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool) -> jnp.ndarray:
    if not 0 <= self.held_first <= self.held_first + self.held_count <= (
        self.num_experts) or not self.held_count:
      raise ValueError(
          f'experts held [{self.held_first}, '
          f'{self.held_first + self.held_count}) are not a share of '
          f'{self.num_experts}')
    h = x.shape[-1]
    tokens = x.reshape(-1, h)  # [B, L, H], or the flat stream as it is
    expert_init = nn.initializers.variance_scaling(
        1.0, 'fan_in', 'truncated_normal', batch_axis=(0,))
    w_gate, w_up = (
        self.param(name, expert_init,
                   (self.held_count, h, self.expert_width), jnp.float32)
        for name in ('experts_gate', 'experts_up'))
    w_down = self.param('experts_down', expert_init,
                        (self.held_count, self.expert_width, h), jnp.float32)
    with jax.named_scope('moe'):
      with jax.named_scope('router'):
        logits = _GateProjection(self.num_experts, use_bias=False,
                                 name='router')(x)
        bias = self.param(
            'router_selection_bias', nn.initializers.zeros,
            (self.num_experts,), jnp.float32) if self.selection_bias else None
        weights, experts = moe.route_top_k(
            logits.reshape(-1, self.num_experts),
            self.experts_per_token, self.norm_topk, scoring=self.scoring,
            bias=bias, scale=self.routed_scale)
      routed, counts = moe.held_experts(
          tokens, weights, experts, w_gate, w_up, w_down, self.held_first)
    self.sow('moe_counts', 'assignments', counts)
    if not self.shared_width:
      return routed.reshape(x.shape)
    with jax.named_scope('shared_expert'):
      shared = GatedFeedForward(
          hidden_size=h, filter_size=self.shared_width, dtype=self.dtype,
          name='shared_expert')(x, deterministic=deterministic)
      if self.shared_gate:
        share = jax.nn.sigmoid(_GateProjection(
            1, use_bias=False, name='shared_expert_gate')(x))
        # dclint: allow=dtype-downcast (the gate is float32; the stream is
        # the compute dtype)
        shared = (share * shared.astype(jnp.float32)).astype(self.dtype)
      if self.shared_scale != 1.0:
        shared = shared * jnp.asarray(self.shared_scale, shared.dtype)
    return routed.reshape(x.shape) + shared


class ResidualWrapper(nn.Module):
  """ReZero (x + alpha*f(x), alpha init 0), pre-LN residual
  (reference PrePostProcessingWrapper: encoder_stack.py:43-93) or, with
  `rms_norm_eps`, pre-RMSNorm residual in the stream's own type
  (`rms_norm_zero_centred`: weights that multiply as 1 + w)."""

  sublayer: nn.Module
  rezero: bool
  dropout_rate: float
  rms_norm_eps: Optional[float] = None
  rms_norm_zero_centred: bool = False

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool,
               **sublayer_kwargs) -> jnp.ndarray:
    if self.rezero:
      y = x
    elif self.rms_norm_eps is not None:
      y = RMSNorm(self.rms_norm_eps, dtype=x.dtype,
                  zero_centred=self.rms_norm_zero_centred, name='rms_norm')(x)
    else:
      y = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name='layer_norm')(x)
    y = self.sublayer(y, deterministic=deterministic, **sublayer_kwargs)
    y = nn.Dropout(rate=self.dropout_rate)(y, deterministic=deterministic)
    if self.rezero:
      alpha = self.param('alpha', nn.initializers.zeros, (), jnp.float32)
      return x + alpha.astype(y.dtype) * y
    return x + y


def refuse_inference_only_kind(p, command: str) -> None:
  """`dctpu train`, `distill` and `export` of a stack that holds sparse
  experts, refused by name before anything is built."""
  if config_lib.holds_experts(p):
    raise ValueError(
        f'block kind {config_lib.block_kind_of(p)!r} is not served by '
        f'`dctpu {command}`: routed '
        'experts have no training step here (no balancing loss, no '
        'gradient through the grouped products) and no exported form; '
        'the kind runs through `dctpu run` and `dctpu serve`')


# How a layer's attention sublayer runs (`forward_launch`'s
# `attention_path`, docs/observability.md).
ATTENTION_FUSED_SUBLAYER = 'fused_sublayer'
ATTENTION_XLA = 'xla'

# The letters whose attention is GroupedSoftmaxAttention.
GROUPED_LETTERS = (config_lib.LAYER_GATED_SOFTMAX,
                   config_lib.LAYER_WINDOW_SOFTMAX,
                   config_lib.LAYER_FULL_SOFTMAX)


def _all_banded(p) -> bool:
  """Whether every layer's attention is banded softmax (`B`): the stack the
  fused kernels compute."""
  return set(config_lib.layer_pattern(p)) == {config_lib.LAYER_BANDED_SOFTMAX}


def _attention_path(p, *, length: int, deterministic: bool = True,
                    initializing: bool = False, ragged: bool = False,
                    sow_intermediates: bool = False) -> str:
  """The one rule by which a forward takes the fused attention sublayer
  kernel (ops/fused_encoder_block.py::fused_attention_sublayer) in place
  of ResidualWrapper(BandedSelfAttention); no option asks for it. Every
  term is read where the forward is traced:

  * every layer banded softmax with its ReZero residual, and no attention
    kernel asked for by option (`use_pallas_attention`);
  * inference: deterministic, not initialising (init runs the modules,
    so the parameter tree is theirs either way), no `intermediates`
    collection to `sow` attention maps into (the kernel writes none);
  * a window of at most FUSED_MAX_WINDOW_LEN positions, no ragged slots;
  * bfloat16 compute: at float32 XLA's single-pass product is the faster
    and the stated arithmetic, a Mosaic float32 product takes 3-6 passes;
  * a TPU, in a trace its caller declared inference for one device
    (pallas_util.single_device_inference: ModelRunner without a mesh).
    Under a mesh, in `dctpu export`, in training and evaluation steps
    nobody declares it, and the modules run as they always have.
  """
  fused = (
      _all_banded(p)
      and p.rezero
      and not p.get('use_pallas_attention', False)
      and deterministic
      and not initializing
      and not sow_intermediates
      and not ragged
      and length <= config_lib.FUSED_MAX_WINDOW_LEN
      and jnp.dtype(p.get('dtype', 'float32')) == jnp.bfloat16
      and pallas_util.may_choose_kernels()
  )
  return ATTENTION_FUSED_SUBLAYER if fused else ATTENTION_XLA


def _latent_attention_path(p, length: int) -> str:
  """ops/latent_attention.py::latent_attention_path asked of the stack's
  latent attention layers (`L`)."""
  return latent_attention.latent_attention_path(
      num_heads=p.num_heads, qk_nope_head_dim=p.qk_nope_head_dim,
      qk_rope_head_dim=p.qk_rope_head_dim, v_head_dim=p.v_head_dim,
      length=length, dtype=p.get('dtype', 'float32'))


def _grouped_attention_path(p, length: int) -> Optional[str]:
  """ops/grouped_attention.py::grouped_attention_path asked of each
  grouped-head softmax letter of the stack: `window_tile_kernel` where the
  rule takes it for every such layer, `plain` otherwise; None for a stack
  without such a layer."""
  letters = set(config_lib.layer_pattern(p)) & set(GROUPED_LETTERS)
  if not letters:
    return None
  paths = set()
  for letter in letters:
    sizes = _grouped_attention_sizes(p, letter)
    paths.add(grouped_attention.grouped_attention_path(
        num_heads=sizes['num_heads'], num_kv_heads=sizes['num_kv_heads'],
        head_dim=sizes['head_dim'], rotary_dim=sizes['rotary_dim'],
        window=sizes['window'], length=length,
        dtype=p.get('dtype', 'float32')))
  return paths.pop() if len(paths) == 1 else grouped_attention.GROUPED_PLAIN


def kernel_paths(p, *, batch: int, length: int,
                 ragged: bool = False) -> Dict[str, Any]:
  """What the compiled forward of a pack of `batch` windows of `length`
  takes of the kernels the model chooses by itself (`forward_launch`'s
  fields, docs/observability.md): `attention_path` always; where the stack
  has Gated DeltaNet mixers (`G`) `delta_rule_path`, where it has latent
  attention (`L`) `latent_attention_path`, where it has grouped-head
  softmax layers (`S`, `W`, `F`) `grouped_attention_path`; where some
  layer's feed-forward is sparse experts (`E`) `grouped_product_path`,
  `combine_path` and the turns a layer takes them in, `moe_turns`. Each is
  its `ops/` rule asked as the forward's trace asks it, so the caller asks
  under the declaration the forward was traced under
  (pallas_util.single_device_inference); no option asks for a kernel."""
  if 'transformer' not in str(p.model_name):
    return {'attention_path': ATTENTION_XLA}
  layers = config_lib.layer_pattern(p)
  dtype = p.get('dtype', 'float32')
  paths = {'attention_path': _attention_path(p, length=length, ragged=ragged)}
  if config_lib.LAYER_GATED_DELTA in layers:
    paths['delta_rule_path'] = gated_delta.delta_rule_path(
        key_head_dim=p.linear_key_head_dim,
        value_head_dim=p.linear_value_head_dim,
        num_key_heads=p.linear_num_key_heads,
        num_value_heads=p.linear_num_value_heads, length=length)
  if config_lib.LAYER_LATENT in layers:
    paths['latent_attention_path'] = _latent_attention_path(p, length)
  grouped = _grouped_attention_path(p, length)
  if grouped is not None:
    paths['grouped_attention_path'] = grouped
  if config_lib.holds_experts(p):
    # The rows and tokens of one turn, as `held_experts` asks.
    tokens, k = batch * length, p.num_experts_per_tok
    turns = moe.turns_of(tokens, k, p.hidden_size, dtype)
    paths['grouped_product_path'] = moe.grouped_product_path(
        tokens // turns * k, p.experts_held_count, p.hidden_size,
        p.moe_intermediate_size, dtype)
    paths['combine_path'] = moe.combine_path(
        tokens // turns, k, p.experts_held_count, p.hidden_size, dtype)
    paths['moe_turns'] = turns
  return paths


def _rotation(p, letter: str):
  """How layers of letter `letter` rotate q and k, as the kind's row says:
  a Rope from their layer type's `rope_parameters` entry, the default
  law's base `rope_theta`, or None (no positions)."""
  how = config_lib.block(p).rotation.get(letter)
  if how == config_lib.ROPE_LISTED:
    return Rope.of(config_lib.rope_parameters(p, letter))
  return None if how is None else p.rope_theta


def describe_stack(p) -> Dict[str, Any]:
  """What `forward_launch` says of the stack, the same for every pack: how
  a layer composes its sublayers (`block_form`), one letter a layer for its
  attention and one for its feed-forward (`layer_pattern`, `ffn_pattern`),
  the window of the layers that attend within one, each layer type's
  rotation where the configuration gives one a type (`rope`,
  {letter: 'default' | 'yarn×<factor>'}) and, for sparse experts, the share
  held, how the router scores and how many shared experts are averaged
  (absent where there are none). Empty for a model without an encoder
  stack."""
  if 'transformer' not in str(p.model_name):
    return {}
  layers = config_lib.layer_pattern(p)
  fields = dict(block_form=config_lib.block_form(p), layer_pattern=layers,
                ffn_pattern=config_lib.ffn_pattern(p))
  if config_lib.LAYER_WINDOW_SOFTMAX in layers:
    fields.update(attention_window=int(p.sliding_window))
  ropes = {letter: rope.describe() for letter, rope in (
      (letter, _rotation(p, letter)) for letter in dict.fromkeys(layers))
           if isinstance(rope, Rope)}
  if ropes:
    fields.update(rope=ropes)
  if config_lib.holds_experts(p):
    first = int(p.experts_held_first)
    fields.update(
        experts_held=[first, first + int(p.experts_held_count)],
        experts_published=int(p.num_experts),
        router_scoring=str(p.router_scoring) + (
            '_bias' if p.router_selection_bias else ''))
    if p.get('num_shared_experts', None):
      fields.update(shared_experts=int(p.num_shared_experts))
  return fields


def _attn_softmax_dtype(p):
  return jnp.dtype(p.get('attn_softmax_dtype', None) or 'float32')


def _sparse_experts(p, n: int, dtype):
  """Layer n's sparse experts (`E`): every size, the router's scoring, bias
  and factor and the shared expert's gate among them, as the configuration
  states it. Shared experts that are averaged run as one of their summed
  width, times one over their number; a shared width of 0 is none."""
  averaged = p.get('shared_expert_combination', None) == 'average'
  return SparseExpertsFeedForward(
      hidden_size=p.hidden_size,
      num_experts=p.num_experts,
      experts_per_token=p.num_experts_per_tok,
      expert_width=p.moe_intermediate_size,
      shared_width=p.shared_expert_intermediate_size,
      norm_topk=p.norm_topk_prob,
      held_first=p.experts_held_first,
      held_count=p.experts_held_count,
      scoring=p.router_scoring,
      selection_bias=p.router_selection_bias,
      routed_scale=p.routed_scaling_factor,
      shared_gate=p.shared_expert_gated,
      shared_scale=1.0 / p.num_shared_experts if averaged else 1.0,
      dtype=dtype,
      name=f'moe_{n}',
  )


def _dense_ffn(p, n: int, dense: str, dtype):
  """Layer n's dense feed-forward (`D`) of the kind's form `dense`."""
  if dense == config_lib.DENSE_RELU:
    return FeedForward(
        hidden_size=p.hidden_size,
        filter_size=p.filter_size,
        dropout_rate=p.relu_dropout,
        dtype=dtype,
        name=f'ffn_{n}',
    )
  return GatedFeedForward(hidden_size=p.hidden_size,
                          filter_size=p.filter_size, dtype=dtype,
                          name=f'ffn_{n}')


def _grouped_attention_sizes(p, letter: str) -> Dict[str, Any]:
  """The GroupedSoftmaxAttention sizes of a layer of letter `letter` (`S`,
  `W` or `F`): what `_grouped_attention` builds it with and what the rule
  is asked of. `S` gates its output and norms q and k; `W` attends within
  `sliding_window` positions; a letter the row rotates turns the first
  `partial_rotary_factor` of the head (all of it where the configuration
  says none) and one it does not has no positions."""
  gated = letter == config_lib.LAYER_GATED_SOFTMAX
  rope = _rotation(p, letter)
  rotary_dim = 0 if rope is None else int(
      p.head_dim * p.get('partial_rotary_factor', 1.0))
  windowed = letter == config_lib.LAYER_WINDOW_SOFTMAX
  return dict(hidden_size=p.hidden_size, num_heads=p.num_heads,
              num_kv_heads=p.num_kv_heads, head_dim=p.head_dim,
              rotary_dim=rotary_dim, rope=rope,
              rms_norm_eps=p.rms_norm_eps if gated else None,
              output_gate=gated, qk_norm=gated,
              window=p.sliding_window if windowed else None)


def _banded_attention(p, n: int, letter: str, dtype):
  del letter
  return BandedSelfAttention(
      hidden_size=p.hidden_size,
      num_heads=p.num_heads,
      dropout_rate=p.attention_dropout,
      attn_win_size=p.attn_win_size,
      dtype=dtype,
      use_pallas=p.get('use_pallas_attention', False),
      softmax_dtype=_attn_softmax_dtype(p),
      name=f'self_attention_{n}',
  )


def _retention_attention(p, n: int, letter: str, dtype):
  if p.retention_degree != power_retention.DEGREE:
    raise ValueError(
        f'retention_degree {p.retention_degree} is not served; '
        f'ops/power_retention.py computes degree {power_retention.DEGREE}')
  return PowerRetentionAttention(
      hidden_size=p.hidden_size,
      num_heads=p.num_heads,
      num_kv_heads=p.num_kv_heads,
      head_dim=p.head_dim,
      rope_theta=_rotation(p, letter),
      rms_norm_eps=p.rms_norm_eps,
      dtype=dtype,
      name=f'self_attention_{n}',
  )


def _delta_mixer(p, n: int, letter: str, dtype):
  del letter
  return GatedDeltaNetMixer(
      hidden_size=p.hidden_size,
      num_key_heads=p.linear_num_key_heads,
      num_value_heads=p.linear_num_value_heads,
      key_head_dim=p.linear_key_head_dim,
      value_head_dim=p.linear_value_head_dim,
      conv_kernel=p.linear_conv_kernel_dim,
      rms_norm_eps=p.rms_norm_eps,
      dtype=dtype,
      name=f'gdn_{n}',
  )


def _latent_attention(p, n: int, letter: str, dtype):
  if p.q_lora_rank is not None or (p.n_group, p.topk_group) != (1, 1):
    raise ValueError(
        f'q_lora_rank {p.q_lora_rank}, n_group {p.n_group} and topk_group '
        f'{p.topk_group} are not served: LatentAttention has no query '
        'latent and ops/moe.py::route_top_k chooses over one group')
  return LatentAttention(
      hidden_size=p.hidden_size,
      num_heads=p.num_heads,
      qk_nope_head_dim=p.qk_nope_head_dim,
      qk_rope_head_dim=p.qk_rope_head_dim,
      v_head_dim=p.v_head_dim,
      kv_lora_rank=p.kv_lora_rank,
      rope_theta=_rotation(p, letter),
      rms_norm_eps=p.rms_norm_eps,
      dtype=dtype,
      name=f'latent_attention_{n}',
  )


def _grouped_attention(p, n: int, letter: str, dtype):
  name = ('gated_attention' if letter == config_lib.LAYER_GATED_SOFTMAX
          else 'self_attention')
  return GroupedSoftmaxAttention(**_grouped_attention_sizes(p, letter),
                                 dtype=dtype, name=f'{name}_{n}')


# Layer n's attention sublayer, by its letter in config.layer_pattern.
_ATTENTION = {
    config_lib.LAYER_BANDED_SOFTMAX: _banded_attention,
    config_lib.LAYER_POWER_RETENTION: _retention_attention,
    config_lib.LAYER_GATED_DELTA: _delta_mixer,
    config_lib.LAYER_LATENT: _latent_attention,
    config_lib.LAYER_GATED_SOFTMAX: _grouped_attention,
    config_lib.LAYER_WINDOW_SOFTMAX: _grouped_attention,
    config_lib.LAYER_FULL_SOFTMAX: _grouped_attention,
}


def _norm(p, norm: str, name: str, dtype=jnp.float32):
  """A block's norm (config.BLOCKS) as one module: the stack's final
  normalization (float32 out) or the parallel block's one norm."""
  if norm == config_lib.NORM_BIAS_FREE_LAYER:
    return BiasFreeLayerNorm(p.layer_norm_eps, dtype=dtype, name=name)
  if norm == config_lib.NORM_REZERO:
    return nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32, name=name)
  return RMSNorm(p.rms_norm_eps, dtype=dtype,
                 zero_centred=norm == config_lib.NORM_RMS_ZERO_CENTRED,
                 name=name)


def _layer_modules(p, n: int, attention: str, ffn: str, dtype):
  """(attention, feed-forward, wrap, norm) of encoder layer `n`, built from
  its two letters (`attention` of config.layer_pattern, `ffn` of
  config.ffn_pattern) and the kind's row (config.BLOCKS) for the form and
  the norm. The last two say the form and one of them is None: a sequential
  block has `wrap(sublayer, name)`, the row's residual around each
  sublayer; a parallel block has `norm`, the ONE norm both sublayers read.
  Called inside EncoderStack's compact method, so the modules are its
  children."""
  row = config_lib.block(p)
  parallel = row.form == config_lib.FORM_PARALLEL
  if parallel and p.first_k_dense_replace:
    raise ValueError(
        f'first_k_dense_replace {p.first_k_dense_replace} is not served: '
        'the parallel block has no dense feed-forward')
  attn = _ATTENTION[attention](p, n, attention, dtype)
  ffn = (_sparse_experts(p, n, dtype) if ffn == config_lib.FFN_EXPERTS
         else _dense_ffn(p, n, row.dense, dtype))
  if parallel:
    return attn, ffn, None, _norm(p, row.norm, f'block_norm_{n}', dtype)
  if row.norm == config_lib.NORM_REZERO:
    residual = dict(rezero=p.rezero)
  else:
    residual = dict(
        rezero=False, rms_norm_eps=p.rms_norm_eps,
        rms_norm_zero_centred=row.norm == config_lib.NORM_RMS_ZERO_CENTRED)
  wrap = lambda sublayer, name: ResidualWrapper(
      sublayer, dropout_rate=p.layer_postprocess_dropout, name=name,
      **residual)
  return attn, ffn, wrap, None


def expert_assignments(sown) -> jnp.ndarray:
  """What the stack's SparseExpertsFeedForward layers sowed in one apply
  (the `moe_counts` collection) as [expert layers, experts held] int32, in
  layer order; the layers are found by name (`moe_<n>`), so a stack whose
  leading layers are dense returns a row for each of the others."""
  layers = sown['encoder']
  numbers = sorted(int(name[len('moe_'):]) for name in layers
                   if name.startswith('moe_'))
  return jnp.stack([layers[f'moe_{n}']['assignments'][0] for n in numbers])


class EncoderStack(nn.Module):
  """N x (attention + feed-forward), layer n built from its letters in
  config.layer_pattern and config.ffn_pattern, then the kind's final
  normalization (config.BLOCKS; reference encoder_stack.py:96-198 for the
  published block).

  [B, L, H] in; [B, L, H] out, or the same rows flat, [B*L, H], where
  the stack took the attention sublayer kernel, the latent attention's or
  the grouped-head attention's (`kernel_paths`' `attention_path`,
  `latent_attention_path`, `grouped_attention_path`)."""

  params: ml_collections.FrozenConfigDict
  dtype: Any = jnp.float32

  @nn.compact
  def __call__(self, x: jnp.ndarray, deterministic: bool,
               skip_first_attention: bool = False,
               skip_blocks: bool = False,
               ragged_widths: Optional[jnp.ndarray] = None,
               ragged_buckets: Optional[tuple] = None) -> jnp.ndarray:
    p = self.params
    output_norm = lambda: _norm(p, config_lib.block(p).norm,
                                'output_normalization')

    if skip_blocks:
      # The fused hot path (ops/fused_encoder_block.py) already ran
      # every attention/FFN block including the ReZero residuals; only
      # the final normalization remains. Init never takes this branch,
      # so the param tree is created identically.
      return output_norm()(x)

    # Optional rematerialization: drop each residual block's
    # activations and recompute them in the backward pass, trading
    # FLOPs for HBM so long-window/large-batch runs fit
    # (params.remat; jax.checkpoint under the hood).
    def run_block(wrapper, x, **kw):
      return wrapper(x, deterministic=deterministic, **kw)

    # Ragged routing is inference-only; remat is a training lever and
    # would treat the static bucket tuple as traced args, so the two
    # never compose.
    if p.get('remat', False) and ragged_widths is None:
      run_block = nn.remat(run_block)

    attn_kwargs = {}
    if ragged_widths is not None:
      attn_kwargs = dict(ragged_widths=ragged_widths,
                         ragged_buckets=ragged_buckets)

    layers, ffns = config_lib.layer_pattern(p), config_lib.ffn_pattern(p)
    fused = _attention_path(
        p, length=x.shape[1], deterministic=deterministic,
        initializing=self.is_initializing(),
        ragged=ragged_widths is not None,
        sow_intermediates=self.is_mutable_collection('intermediates'),
    ) == ATTENTION_FUSED_SUBLAYER
    batch, length, hidden = x.shape
    grouped_kwargs = {}
    if not self.is_initializing():
      # Those kernels' blocks are row ranges of the flat stream too, and
      # norms, experts and feed-forwards are position-wise: flattened once,
      # here (init runs the modules on [B, L, H], which declare the
      # leaves). The Gated DeltaNet mixer's convolution runs along the
      # window, so beside it a grouped-head layer flattens its own input.
      if config_lib.LAYER_LATENT in layers and _latent_attention_path(
          p, length) == latent_attention.LATENT_WINDOW_TILE_KERNEL:
        x = x.reshape(batch * length, hidden)
        attn_kwargs = dict(window_length=length)
      if _grouped_attention_path(p, length) == (
          grouped_attention.GROUPED_WINDOW_TILE_KERNEL):
        grouped_kwargs = dict(window_length=length)
        if config_lib.LAYER_GATED_DELTA not in layers:
          x = x.reshape(batch * length, hidden)

    for n, (attention, feed_forward) in enumerate(zip(layers, ffns)):
      attn, ffn, wrap, norm = _layer_modules(p, n, attention, feed_forward,
                                             self.dtype)
      kwargs = grouped_kwargs if attention in GROUPED_LETTERS else attn_kwargs
      if norm is not None:
        # The parallel form: one norm, both sublayers on it, one addition;
        # neither sublayer waits for the other.
        u = norm(x)
        with jax.named_scope('attention'):
          attended = attn(u, deterministic=deterministic, **kwargs)
        with jax.named_scope('ffn'):
          x = x + attended + ffn(u, deterministic=deterministic)
        continue
      if skip_first_attention and n == 0:
        # The fused hot path (ops/fused_window_attention.py) already
        # applied attention_wrapper_0's block including the residual;
        # module names below stay aligned so the param tree is
        # unchanged (init never takes this branch).
        pass
      else:
        with jax.named_scope('attention'):
          if fused:
            # The kernel's blocks are row ranges of the flat [B*L, H]
            # stream, and everything after it is position-wise: the
            # stream is flattened once, here, and stays flat to the
            # caller, so no layer pays a [B, L, H] <-> [B*L, H] copy (at
            # L=100 that is no bitcast on the chip's 8-row tiles).
            x = self._fused_attention_sublayer(
                n, x.reshape(batch * length, hidden), length)
          else:
            x = run_block(wrap(attn, f'attention_wrapper_{n}'), x, **kwargs)
      with jax.named_scope('ffn'):
        x = run_block(wrap(ffn, f'ffn_wrapper_{n}'), x)
    return output_norm()(x)

  def _fused_attention_sublayer(self, n: int, x2: jnp.ndarray,
                                length: int) -> jnp.ndarray:
    """Layer n's `attention_wrapper_n(self_attention_n)` on the flat
    stream through the sublayer kernel, reading the leaves the modules
    read (the effective values where models/quantize.py replaced them).
    Sublayers are constructed outside ResidualWrapper, so Flax names
    them as siblings of their wrapper inside this scope."""
    from deepconsensus_tpu.ops import fused_encoder_block as feb

    p = self.params
    params = self.variables['params']
    attn = params[f'self_attention_{n}']
    h = p.hidden_size
    return feb.fused_attention_sublayer(
        x2,
        attn['query']['kernel'].reshape(h, h),
        attn['key']['kernel'].reshape(h, h),
        attn['value']['kernel'].reshape(h, h),
        attn['output_transform']['kernel'].reshape(h, h),
        params[f'attention_wrapper_{n}']['alpha'],
        length=length,
        num_heads=p.num_heads,
        attn_win_size=p.attn_win_size or None,
        softmax_dtype=_attn_softmax_dtype(p),
    )


class DeepConsensusModel(nn.Module):
  """Encoder-only transformer with learned per-feature embeddings.

  Input: rows [batch, total_rows, max_length, 1] float32 as produced by
  the feature pipeline; output: per-position softmax over
  {gap, A, T, C, G} (reference networks.py:368-520).
  """

  params: ml_collections.FrozenConfigDict

  def setup(self):
    p = self.params
    self.compute_dtype = jnp.dtype(p.get('dtype', 'float32'))
    self.learn_values = 'learn_values' in p.model_name
    dt = self.compute_dtype
    if not self.learn_values:
      # Plain transformer: raw rows are the per-position feature vector
      # (reference EncoderOnlyTransformer: networks.py:173-365).
      self.encoder = EncoderStack(p, dtype=dt, name='encoder')
      self.logits_layer = nn.Dense(
          constants.SEQ_VOCAB_SIZE, use_bias=True, dtype=jnp.float32,
          kernel_init=nn.initializers.glorot_uniform(), name='logits')
      return
    if p.use_bases or p.use_ccs:
      self.bases_embedding = MaskedEmbed(
          constants.SEQ_VOCAB_SIZE, p.per_base_hidden_size, dt,
          name='bases_embedding')
    if p.use_pw:
      self.pw_embedding = MaskedEmbed(
          p.PW_MAX + 1, p.pw_hidden_size, dt, name='pw_embedding')
    if p.use_ip:
      self.ip_embedding = MaskedEmbed(
          p.IP_MAX + 1, p.ip_hidden_size, dt, name='ip_embedding')
    if p.use_strand:
      self.strand_embedding = MaskedEmbed(
          p.STRAND_MAX + 1, p.strand_hidden_size, dt, name='strand_embedding')
    if p.use_ccs_bq:
      self.ccs_bq_embedding = MaskedEmbed(
          p.CCS_BQ_MAX, p.ccs_bq_hidden_size, dt, name='ccs_bq_embedding')
    if p.use_sn:
      self.sn_embedding = MaskedEmbed(
          p.SN_MAX + 1, p.sn_hidden_size, dt, name='sn_embedding')
    if p.condense_transformer_input:
      self.condenser = nn.Dense(
          p.transformer_input_size, use_bias=False, dtype=dt,
          kernel_init=nn.initializers.glorot_uniform(), name='condenser')
    self.encoder = EncoderStack(p, dtype=dt, name='encoder')
    self.logits_layer = nn.Dense(
        constants.SEQ_VOCAB_SIZE, use_bias=True, dtype=jnp.float32,
        kernel_init=nn.initializers.glorot_uniform(), name='logits')

  def _embed_rows(self, rows: jnp.ndarray) -> jnp.ndarray:
    """Vectorized per-feature embedding of the stacked pileup tensor.

    rows: [B, R, L]; returns [B, L, sum(feature_rows * widths)], the
    concat order matching the reference's per-row append order
    (networks.py:436-506).
    """
    p = self.params
    (base_r, pw_r, ip_r, strand_r, ccs_r, ccs_bq_r, sn_r) = row_indices(
        p.max_passes, p.use_ccs_bq
    )
    blocks = []

    def lookup(embedding, row_range, shift: int = 0):
      ids = rows[:, row_range[0]:row_range[1], :].astype(jnp.int32) + shift
      # Ids go in position-major, so the lookup emits [B, L, r, E] and
      # no [B, r, L, E] array is transposed.
      emb = embedding(jnp.swapaxes(ids, 1, 2))
      b, l, r, e = emb.shape
      return emb.reshape(b, l, r * e)

    if p.use_bases:
      blocks.append(lookup(self.bases_embedding, base_r))
    if p.use_pw:
      blocks.append(lookup(self.pw_embedding, pw_r))
    if p.use_ip:
      blocks.append(lookup(self.ip_embedding, ip_r))
    if p.use_strand:
      blocks.append(lookup(self.strand_embedding, strand_r))
    if p.use_ccs:
      blocks.append(lookup(self.bases_embedding, ccs_r))
    if p.use_ccs_bq:
      # Shift -1 (gap) to 0 (networks.py:491-497).
      blocks.append(lookup(self.ccs_bq_embedding, ccs_bq_r, shift=1))
    if p.use_sn:
      blocks.append(lookup(self.sn_embedding, sn_r))
    return jnp.concatenate(blocks, axis=-1)

  def _fused_hotpath_eligible(self, rows: jnp.ndarray, train: bool) -> bool:
    """True when this apply can route through the batch-major fused
    embed->condense->attention kernel. Init always runs the XLA path so
    the param tree is created identically; training needs gradients and
    dropout the kernel doesn't serve; the kernel computes the banded
    softmax block only (any other block kind declines), and assumes the
    condensed learn-values input, a ReZero residual for layer 0, and a window
    short enough for whole-L score blocks. rows.shape is static under
    trace, so with window buckets the routing is per bucket: each
    bucket's compiled forward independently picks fused
    (L <= MAX_WINDOW_LEN) or the XLA fallback."""
    from deepconsensus_tpu.ops import fused_window_attention as fwa

    p = self.params
    return bool(
        p.get('use_fused_hotpath', False)
        and _all_banded(p)
        and not train
        and not self.is_initializing()
        and self.learn_values
        and p.condense_transformer_input
        and p.rezero
        and p.num_hidden_layers >= 1
        and rows.shape[-1] <= fwa.MAX_WINDOW_LEN
    )

  def _fused_forward(self, rows: jnp.ndarray) -> jnp.ndarray:
    """Embed+condense+pos+layer-0 attention block via the fused Pallas
    kernel; returns activations ready for the remaining encoder blocks
    (call the encoder with skip_first_attention=True)."""
    from deepconsensus_tpu.ops import fused_window_attention as fwa

    p = self.params
    specs, table_keys, _ = fwa.build_family_specs(p)
    params = self.variables['params']
    tables = {k: params[f'{k}_embedding']['embedding'] for k in table_keys}
    h = p.hidden_size
    # Sublayers are constructed outside ResidualWrapper, so Flax names
    # them as siblings of their wrapper inside the encoder scope.
    attn0 = params['encoder']['self_attention_0']
    wrap0 = params['encoder']['attention_wrapper_0']
    pos = None
    if p.add_pos_encoding:
      # dclint: allow=dtype-downcast (position encodings enter the
      # fused kernel at the configured compute dtype)
      pos = jnp.asarray(
          sinusoidal_position_encoding(rows.shape[-1], h),
          self.compute_dtype)
    x_base, attn_out = fwa.fused_embed_condense_attention(
        rows,
        tables,
        params['condenser']['kernel'],
        attn0['query']['kernel'].reshape(h, h),
        attn0['key']['kernel'].reshape(h, h),
        attn0['value']['kernel'].reshape(h, h),
        attn0['output_transform']['kernel'].reshape(h, h),
        pos,
        specs=specs,
        table_keys=table_keys,
        num_heads=p.num_heads,
        attn_win_size=p.attn_win_size or None,
        softmax_dtype=_attn_softmax_dtype(p),
        compute_dtype=self.compute_dtype,
    )
    alpha = wrap0['alpha']
    return x_base + alpha.astype(x_base.dtype) * attn_out

  def _fused_encoder_blocks(
      self, x: jnp.ndarray,
      lengths: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Run every remaining encoder block (layer-0 FFN onward) through
    the fused Pallas block kernel (ops/fused_encoder_block.py); the
    caller finishes with the encoder's output LayerNorm
    (skip_blocks=True). int8-quantized matmul weights ride in from the
    'quant' collection when params.quantize_matmuls is set. lengths:
    per-slot window widths for ragged slots (every attention block
    masks with the lengths-derived ragged mask)."""
    from deepconsensus_tpu.ops import fused_encoder_block as feb

    p = self.params
    quant = None
    if p.get('quantize_matmuls', None) == 'int8':
      quant = self.variables.get('quant', {}).get('encoder')
    blocks = feb.blocks_from_params(
        self.variables['params']['encoder'],
        quant,
        p.num_hidden_layers,
        skip_first_attention=True,
    )
    return feb.fused_encoder_stack(
        x,
        blocks,
        num_heads=p.num_heads,
        attn_win_size=p.attn_win_size or None,
        softmax_dtype=_attn_softmax_dtype(p),
        compute_dtype=self.compute_dtype,
        lengths=lengths,
    )

  def _ragged_hotpath_eligible(self, rows: jnp.ndarray) -> bool:
    """Fused-kernel eligibility for ragged slots: same levers as
    _fused_hotpath_eligible except the window-length bound — slots
    span the LARGEST bucket, so the ragged kernel carries its own
    (higher) slot-length ceiling."""
    from deepconsensus_tpu.ops import ragged_window_attention as rwa

    p = self.params
    return bool(
        p.get('use_fused_hotpath', False)
        and _all_banded(p)
        and not self.is_initializing()
        and self.learn_values
        and p.condense_transformer_input
        and p.rezero
        and p.num_hidden_layers >= 1
        and rows.shape[-1] <= rwa.RAGGED_MAX_SLOT_LEN
    )

  def _ragged_fused_forward(self, rows: jnp.ndarray,
                            lengths: jnp.ndarray) -> jnp.ndarray:
    """Embed+condense+pos+layer-0 attention over ragged slots via the
    ragged Pallas kernel (ops/ragged_window_attention.py); mirrors
    _fused_forward's weight plumbing and residual split."""
    from deepconsensus_tpu.ops import fused_window_attention as fwa
    from deepconsensus_tpu.ops import ragged_window_attention as rwa

    p = self.params
    specs, table_keys, _ = fwa.build_family_specs(p)
    params = self.variables['params']
    tables = {k: params[f'{k}_embedding']['embedding'] for k in table_keys}
    h = p.hidden_size
    attn0 = params['encoder']['self_attention_0']
    wrap0 = params['encoder']['attention_wrapper_0']
    pos = None
    if p.add_pos_encoding:
      # dclint: allow=dtype-downcast (position encodings enter the
      # fused kernel at the configured compute dtype)
      pos = jnp.asarray(
          sinusoidal_position_encoding(rows.shape[-1], h),
          self.compute_dtype)
    x_base, attn_out = rwa.ragged_embed_condense_attention(
        rows,
        lengths,
        tables,
        params['condenser']['kernel'],
        attn0['query']['kernel'].reshape(h, h),
        attn0['key']['kernel'].reshape(h, h),
        attn0['value']['kernel'].reshape(h, h),
        attn0['output_transform']['kernel'].reshape(h, h),
        pos,
        specs=specs,
        table_keys=table_keys,
        num_heads=p.num_heads,
        attn_win_size=p.attn_win_size or None,
        softmax_dtype=_attn_softmax_dtype(p),
        compute_dtype=self.compute_dtype,
    )
    alpha = wrap0['alpha']
    return x_base + alpha.astype(x_base.dtype) * attn_out

  def _ragged_forward_with_intermediates(
      self, rows: jnp.ndarray,
      window_lengths: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Single-shape ragged forward: rows [B, R, S] with mixed-width
    windows packed back-to-back per slot, window_lengths [B, wps] the
    per-slot widths. The XLA route is bitwise-identical per position
    to the bucketed forward at each window's own width (reshape-select
    attention + exact per-position pos gather); the Pallas route (when
    use_fused_hotpath is on) is the ragged kernel pair, allclose-
    validated against the reference in interpret mode."""
    from deepconsensus_tpu.models import config as config_lib
    from deepconsensus_tpu.ops import ragged_window_attention as rwa

    p = self.params
    if not self.learn_values:
      raise ValueError('ragged forward requires the learn_values model')
    slot_len = rows.shape[-1]
    # Only widths that tile the slot can be recovered by reshape; the
    # packer feeds exactly these (slot_len is the largest bucket of a
    # divisibility chain, so normally every bucket qualifies).
    buckets = rwa.validate_ragged_buckets(
        tuple(b for b in config_lib.resolve_window_buckets(p)
              if slot_len % b == 0))
    lengths = jnp.asarray(window_lengths, jnp.int32)
    if self._ragged_hotpath_eligible(rows):
      x = self._ragged_fused_forward(rows, lengths)
      x = self._fused_encoder_blocks(x, lengths=lengths)
      encoded = self.encoder(x, deterministic=True, skip_blocks=True)
      logits = self.logits_layer(encoded.astype(jnp.float32))
      preds = jax.nn.softmax(logits, axis=-1)
      return {'final_output': encoded, 'logits': logits, 'preds': preds}
    _seg, start, width, valid = rwa.slot_geometry(lengths, slot_len)
    x = self._embed_rows(rows)
    if p.condense_transformer_input:
      x = self.condenser(x)
    if p.add_pos_encoding:
      pos = jnp.asarray(
          sinusoidal_position_encoding(slot_len, x.shape[2]), x.dtype)
      off = jnp.clip(
          jnp.arange(slot_len, dtype=jnp.int32)[None, :] - start,
          0, slot_len - 1)
      # Per-position gather pos[p - window_start(p)]: the same value
      # (and the same single add) the bucketed path applies at this
      # position's window offset, so the sum is bitwise-equal.
      x = x + jnp.where(valid[:, :, None], jnp.take(pos, off, axis=0),
                        jnp.zeros((), x.dtype))
    encoded = self.encoder(x, deterministic=True, ragged_widths=width,
                           ragged_buckets=buckets)
    logits = self.logits_layer(encoded.astype(jnp.float32))
    preds = jax.nn.softmax(logits, axis=-1)
    return {'final_output': encoded, 'logits': logits, 'preds': preds}

  def __call__(
      self, rows: jnp.ndarray, train: bool = False,
      window_lengths: Optional[jnp.ndarray] = None
  ) -> jnp.ndarray:
    return self.apply_with_intermediates(
        rows, train, window_lengths=window_lengths)['preds']

  @nn.compact_name_scope
  def apply_with_intermediates(
      self, rows: jnp.ndarray, train: bool = False,
      window_lengths: Optional[jnp.ndarray] = None
  ) -> Dict[str, jnp.ndarray]:
    p = self.params
    deterministic = not train
    if rows.ndim == 4:
      rows = jnp.squeeze(rows, -1)
    if window_lengths is not None and not train:
      return self._ragged_forward_with_intermediates(rows, window_lengths)
    if self._fused_hotpath_eligible(rows, train):
      x = self._fused_forward(rows)
      x = self._fused_encoder_blocks(x)
      encoded = self.encoder(x, deterministic=True, skip_blocks=True)
      logits = self.logits_layer(encoded.astype(jnp.float32))
      preds = jax.nn.softmax(logits, axis=-1)
      return {'final_output': encoded, 'logits': logits, 'preds': preds}
    # Scope names (`embed`, `attention` with `retention`, `gdn` or
    # `softmax` inside, `ffn` with `moe` and `shared_expert` inside,
    # `head`) are kept as a promise to whoever reads the device trace by
    # scope (docs/observability.md); they are metadata and change no HLO.
    with jax.named_scope('embed'):
      if self.learn_values:
        x = self._embed_rows(rows)
        if p.condense_transformer_input:
          x = self.condenser(x)
      else:
        # Raw per-position feature vectors [B, L, total_rows], zero-padded
        # to an even width for the positional encoding
        # (reference: networks.py:266-306).
        # dclint: allow=dtype-downcast (model entry point: inputs adopt
        # the configured compute dtype once, here)
        x = jnp.transpose(rows, (0, 2, 1)).astype(self.compute_dtype)
        if p.add_pos_encoding and x.shape[-1] % 2 != 0:
          x = jnp.pad(x, ((0, 0), (0, 0), (0, 1)))
      if p.add_pos_encoding:
        pos = sinusoidal_position_encoding(x.shape[1], x.shape[2])
        x = x + jnp.asarray(pos, x.dtype)
    if train and p.layer_postprocess_dropout > 0:
      x = nn.Dropout(rate=p.layer_postprocess_dropout, name='input_dropout')(
          x, deterministic=deterministic
      )
    encoded = self.encoder(x, deterministic=deterministic)
    with jax.named_scope('head'):
      logits = self.logits_layer(encoded.astype(jnp.float32))
      preds = jax.nn.softmax(logits, axis=-1)
    if encoded.ndim == 2:
      # The stack ran on the flat stream and the head is position-wise:
      # windows come back here, where a position is 5 values wide.
      windows = lambda a: a.reshape(x.shape[0], x.shape[1], a.shape[-1])
      encoded, logits, preds = windows(encoded), windows(logits), windows(preds)
    return {'final_output': encoded, 'logits': logits, 'preds': preds}


class FullyConnectedModel(nn.Module):
  """Simple FC baseline (reference networks.py:67-92)."""

  params: ml_collections.FrozenConfigDict

  @nn.compact
  def __call__(self, rows: jnp.ndarray, train: bool = False) -> jnp.ndarray:
    p = self.params
    x = rows.reshape(rows.shape[0], -1)
    for width in p.fc_size:
      x = nn.Dense(width)(x)
      x = nn.relu(x)
      x = nn.Dropout(rate=p.fc_dropout)(x, deterministic=not train)
    x = nn.Dense(p.max_length * constants.SEQ_VOCAB_SIZE)(x)
    x = x.reshape(rows.shape[0], p.max_length, constants.SEQ_VOCAB_SIZE)
    return jax.nn.softmax(x, axis=-1)


def summarize_params(variables) -> str:
  """Human-readable parameter summary with per-module counts
  (counterpart of reference print_model_summary: model_utils.py)."""
  lines = []
  total = 0
  flat = jax.tree_util.tree_flatten_with_path(variables)[0]
  for path, leaf in flat:
    name = '/'.join(getattr(k, 'key', str(k)) for k in path)
    count = int(np.prod(leaf.shape)) if leaf.shape else 1
    total += count
    lines.append(f'{name:70s} {str(leaf.shape):20s} {count:>12,}')
  lines.append(f'{"TOTAL":70s} {"":20s} {total:>12,}')
  return '\n'.join(lines)


def get_model(params: ml_collections.ConfigDict) -> nn.Module:
  """Model factory (reference model_utils.py:142-152)."""
  frozen = ml_collections.FrozenConfigDict(params)
  if 'transformer' in params.model_name:
    return DeepConsensusModel(frozen)
  if params.model_name == 'fc':
    return FullyConnectedModel(frozen)
  if params.model_name == 'conv_net':
    from deepconsensus_tpu.models.convnet import ConvNetModel

    return ConvNetModel(frozen)
  raise ValueError(f'Unknown model name: {params.model_name}')
