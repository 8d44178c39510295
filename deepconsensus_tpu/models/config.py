"""Model/hparam configuration system.

Mirrors the reference's {model}+{dataset} ConfigDict presets and the
hardware-dependent parameter derivation of modify_params (reference:
deepconsensus/models/model_configs.py:40-379,
models/model_utils.py:237-354, models/transformer_basic_params.py:33-97),
with TPU-native additions: compute dtype, mesh axes, and kernel toggles.

params.json written next to checkpoints is the source of truth at
inference time, exactly like the reference (model_utils.py:434-476).
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Callable, Mapping, Optional

import ml_collections

from deepconsensus_tpu.preprocess.pileup import total_rows as _total_rows

# Canonical window geometry. All shape literals live here (dclint's
# shape-literals checker fences them out of the rest of the tree):
# DEFAULT_MAX_LENGTH is the reference window length (reference:
# model_configs.py max_length=100); FUSED_MAX_WINDOW_LEN is the VMEM
# row budget of the Pallas fused hot path — buckets at or under it run
# fused, longer buckets fall back to XLA.
DEFAULT_MAX_LENGTH = 100
FUSED_MAX_WINDOW_LEN = 128
# Default bucket set when params.window_buckets is requested but unset
# by a config: the reference L=100 plus one 2x bucket (the distill
# configs' target geometry, arxiv 2211.09862).
DEFAULT_WINDOW_BUCKETS = (100, 200)
# Long-insert geometry. Training windows at or past
# RING_ATTENTION_MIN_LEN route BandedSelfAttention through the
# blockwise ring-attention scan (parallel/ring_attention.py) instead
# of materializing the [B, N, L, L] logits: at L=500 the full logits
# tensor no longer fits the fused kernel's VMEM tiling, and the
# banded structure makes the blockwise online-softmax pass both exact
# and memory-bounded. Buckets below the crossover (100, 200) keep the
# XLA einsum path, whose fused/Pallas eligibility is decided
# downstream by _fused_hotpath_eligible.
RING_ATTENTION_MIN_LEN = 256
LONG_INSERT_WINDOW_LEN = 500

# Quantization acceptance gates — the ONE shared home. The runtime
# gates (models/flywheel.py) and the acceptance tests
# (tests/test_quantized_inference.py) both import these so the
# documented thresholds can never drift between test and release gate:
# int8 — held-out alignment identity within this delta of the f32
# baseline; bf16 — per-base Phred QVs within this many units of f32 on
# argmax-agreeing positions.
INT8_IDENTITY_GATE = 0.002
BF16_QV_GATE = 3


def normalize_window_buckets(buckets, max_length: int):
  """Validate and canonicalize a window-bucket spec.

  None/empty means bucketing is off: the single-shape pipeline runs
  exactly as before with one bucket equal to max_length. A non-empty
  spec must be strictly ascending positive ints whose smallest entry
  equals params.max_length — max_length stays the featurize stride and
  base window geometry; buckets only widen the variable-width (smart
  window) path.
  """
  if not buckets:
    return (int(max_length),)
  if isinstance(buckets, str):
    # '--set window_buckets=100,200' reaches here as the raw string;
    # accept the same comma form as the dedicated CLI flag.
    buckets = [b for b in buckets.replace(',', ' ').split()]
  out = tuple(int(b) for b in buckets)
  if any(b <= 0 for b in out):
    raise ValueError(f'window_buckets must be positive ints, got {out}')
  if list(out) != sorted(set(out)):
    raise ValueError(
        f'window_buckets must be strictly ascending, got {out}')
  if out[0] != int(max_length):
    raise ValueError(
        f'smallest window bucket {out[0]} must equal max_length '
        f'{max_length} (max_length is the featurize stride)')
  return out


def resolve_window_buckets(params):
  """Bucket set for a params object: normalized params.window_buckets,
  or the single-bucket (max_length,) when unset."""
  buckets = getattr(params, 'window_buckets', None)
  return normalize_window_buckets(buckets, int(params.max_length))


def bucket_for(width: int, buckets):
  """Smallest bucket that fits `width`, or None when it overflows all
  buckets (the caller's overflow-skip path)."""
  for b in buckets:
    if width <= b:
      return int(b)
  return None


# Encoder block kinds, named by mechanism. A kind is one row of BLOCKS
# below, the one place the kind is read: models/model.py builds layer n
# from its two letters and the row.
#   banded_softmax_relu     banded softmax self-attention over full heads
#                           + ReLU feed-forward, ReZero or pre-LayerNorm
#                           residuals, final LayerNorm (the published
#                           DeepConsensus block).
#   power_retention_swiglu  gated power retention (a linear-attention
#                           layer, run in its two-direction quadratic
#                           form: ops/power_retention.py) over grouped
#                           heads with per-head q/k RMSNorm and rotary
#                           positions + SwiGLU feed-forward, pre-RMSNorm
#                           residuals, final RMSNorm.
#   gated_delta_hybrid_moe  a stack whose layers are not alike: layer n
#                           is gated softmax attention over grouped heads
#                           where (n + 1) % full_attention_interval == 0
#                           and a Gated DeltaNet mixer otherwise (the
#                           gated delta rule in chunked form, two
#                           directions: ops/gated_delta.py); every layer's
#                           feed-forward is sparse experts (router at its
#                           full width, top-k, the products of the experts
#                           this process holds: ops/moe.py) plus a gated
#                           shared expert; zero-centred pre-RMSNorm
#                           residuals, final RMSNorm.
#   latent_attention_moe    every layer's attention is multi-head latent
#                           attention in its whole-window form: keys and
#                           values come out of one shared low-rank latent
#                           (RMSNorm'd), a rotary key of its own is shared
#                           by all heads, query/key heads are wider than
#                           value heads (ops/latent_attention.py); the
#                           feed-forward is SwiGLU in the first
#                           `first_k_dense_replace` layers, behind them
#                           sparse experts scored by a sigmoid and chosen
#                           with a balancing bias (ops/moe.py) plus an
#                           ungated shared expert; pre-RMSNorm residuals,
#                           final RMSNorm.
#   parallel_window_moe     a block that is no chain of sublayers: ONE
#                           bias-free LayerNorm, attention and feed-forward
#                           both on its output, one addition (x + attn(u) +
#                           ffn(u), u = LN(x)). Every layer's attention is
#                           softmax attention over grouped heads without
#                           q/k norm or gate; layer n has no positions at
#                           all where (n + 1) % layer_switch == 0 and
#                           otherwise rotates the whole head and attends
#                           within `sliding_window` positions, both ways;
#                           every layer's feed-forward is sparse experts
#                           scored by a sigmoid without a bias (ops/moe.py)
#                           plus `num_shared_experts` shared experts that
#                           are averaged; final bias-free LayerNorm.
#   window_moe              a sequential pre-RMSNorm stack (plain weights)
#                           whose layers' attention is LISTED, not derived:
#                           `layer_types` names each layer's
#                           (sliding_attention: grouped softmax attention
#                           within `sliding_window` positions, both ways;
#                           full_attention: over the whole window), and
#                           `rope_parameters` gives each layer type its
#                           rotation (the default law, or YaRN with its
#                           magnitude); no q/k norm, no gate. Every layer's
#                           feed-forward is sparse experts alone
#                           (`mlp_layer_types` all sparse): a softmax router
#                           without a bias, the top-k renormalised, no
#                           shared expert; final RMSNorm.
#   mamba_hybrid            a sequential pre-RMSNorm stack (plain weights)
#                           whose layers are not alike: layer n is softmax
#                           attention over grouped heads without positions,
#                           q/k norm or gate where n % attn_layer_period ==
#                           attn_layer_offset, and a Mamba-1 mixer
#                           otherwise (a two-direction causal depthwise
#                           convolution with a bias, then a selective scan
#                           over a [d_inner, d_state] state with its own
#                           RMSNorms on dt, B and C, two directions:
#                           ops/selective_scan.py). Every layer's
#                           feed-forward is SwiGLU (`num_experts` 1: the
#                           published expert period chooses nothing); final
#                           RMSNorm.
BLOCK_BANDED_SOFTMAX = 'banded_softmax_relu'
BLOCK_POWER_RETENTION = 'power_retention_swiglu'
BLOCK_GATED_DELTA_MOE = 'gated_delta_hybrid_moe'
BLOCK_LATENT_MOE = 'latent_attention_moe'
BLOCK_PARALLEL_WINDOW_MOE = 'parallel_window_moe'
BLOCK_WINDOW_MOE = 'window_moe'
BLOCK_MAMBA_HYBRID = 'mamba_hybrid'
BLOCK_KINDS = (BLOCK_BANDED_SOFTMAX, BLOCK_POWER_RETENTION,
               BLOCK_GATED_DELTA_MOE, BLOCK_LATENT_MOE,
               BLOCK_PARALLEL_WINDOW_MOE, BLOCK_WINDOW_MOE,
               BLOCK_MAMBA_HYBRID)

# How a layer composes its two sublayers (`forward_launch`'s `block_form`):
# one after the other, each behind a norm of its own (x + f(norm_1(x)), then
# h + g(norm_2(h))), or both on ONE norm's output with one addition.
FORM_SEQUENTIAL = 'sequential'
FORM_PARALLEL = 'parallel'

# A block's norm, around each sublayer (or the parallel block's one) and at
# the end of the stack: ReZero residuals (pre-LayerNorm 1e-6 where `rezero`
# is off) and a final LayerNorm 1e-6; RMSNorm (`rms_norm_eps`), its weights
# plain or zero-centred (multiplying as 1 + w); a bias-free LayerNorm
# (`layer_norm_eps`).
NORM_REZERO = 'rezero'
NORM_RMS = 'rms'
NORM_RMS_ZERO_CENTRED = 'rms_zero_centred'
NORM_BIAS_FREE_LAYER = 'bias_free_layer'

# A dense feed-forward: relu with biases and dropout (FeedForward), or
# SwiGLU without biases (GatedFeedForward).
DENSE_RELU = 'relu'
DENSE_SWIGLU = 'swiglu'

# How an attention letter rotates q and k: by the default law at base
# `rope_theta`, or by its layer type's entry of `rope_parameters`. A letter
# a row does not name has no rotary positions.
ROPE_THETA = 'rope_theta'
ROPE_LISTED = 'rope_parameters'

# A layer's attention, one letter a layer in `layer_pattern` (the
# `forward_launch` span, docs/observability.md).
LAYER_BANDED_SOFTMAX = 'B'
LAYER_POWER_RETENTION = 'R'
LAYER_GATED_DELTA = 'G'
LAYER_GATED_SOFTMAX = 'S'
LAYER_LATENT = 'L'
LAYER_WINDOW_SOFTMAX = 'W'
LAYER_FULL_SOFTMAX = 'F'
LAYER_MAMBA = 'M'

# A layer's feed-forward, one letter a layer in `ffn_pattern`: one dense
# feed-forward of the kind's form, or sparse experts.
FFN_DENSE = 'D'
FFN_EXPERTS = 'E'

# The published names of a listed pattern's entries (`layer_types`,
# `mlp_layer_types`), and the letter each is.
LAYER_TYPES = {'sliding_attention': LAYER_WINDOW_SOFTMAX,
               'full_attention': LAYER_FULL_SOFTMAX}
MLP_LAYER_TYPES = {'sparse': FFN_EXPERTS}


def _repeat(letter: str) -> Callable[[Any], str]:
  """Every layer `letter`."""
  return lambda params: letter * params.num_hidden_layers


def _every(key: str, letter: str, others: str) -> Callable[[Any], str]:
  """`letter` where (n + 1) % params[key] == 0, `others` elsewhere."""
  def pattern(params) -> str:
    every = params[key]
    return ''.join(letter if (n + 1) % every == 0 else others
                   for n in range(params.num_hidden_layers))
  return pattern


def _offset_every(period_key: str, offset_key: str, letter: str,
                  others: str) -> Callable[[Any], str]:
  """`letter` where n % params[period_key] == params[offset_key], `others`
  elsewhere; a period below 1 or an offset outside [0, period) is refused
  by name."""
  def pattern(params) -> str:
    period, offset = params[period_key], params[offset_key]
    if period < 1 or not 0 <= offset < period:
      raise ValueError(
          f'{offset_key} {offset} of {period_key} {period} places no layer: '
          f'the offset lies in [0, {period_key})')
    return ''.join(letter if n % period == offset else others
                   for n in range(params.num_hidden_layers))
  return pattern


def _dense_unless_experts(key: str) -> Callable[[Any], str]:
  """A dense feed-forward in every layer where params[key] is 1: a single
  expert is a dense layer, and the expert period chooses nothing. More
  experts are refused by name."""
  def pattern(params) -> str:
    if params[key] != 1:
      raise ValueError(
          f'{key} {params[key]} is not served: this kind runs a dense '
          'feed-forward in every layer and has no expert layers')
    return FFN_DENSE * params.num_hidden_layers
  return pattern


def _leading(key: str, letter: str, others: str) -> Callable[[Any], str]:
  """`letter` in the first params[key] layers, `others` behind them."""
  def pattern(params) -> str:
    leading = params[key]
    return ''.join(letter if n < leading else others
                   for n in range(params.num_hidden_layers))
  return pattern


def _listed(key: str, letters: dict) -> Callable[[Any], str]:
  """The configuration's list `key`, one published name a layer, as
  letters: refused by name where it does not name every layer once, or
  names a type this kind does not run."""
  def pattern(params) -> str:
    names = list(params[key])
    if len(names) != params.num_hidden_layers:
      raise ValueError(
          f'{key} lists {len(names)} layers and num_hidden_layers is '
          f'{params.num_hidden_layers}: a stage lists its own layers')
    unknown = sorted(set(names) - set(letters))
    if unknown:
      raise ValueError(f'{key} {unknown} are not served; the kind runs '
                       f'{sorted(letters)}')
    return ''.join(letters[name] for name in names)
  return pattern


@dataclasses.dataclass(frozen=True)
class Block:
  """A block kind's parts. `layers` and `ffns` derive `layer_pattern` and
  `ffn_pattern` from what the configuration states; `rotation` says how
  each attention letter rotates (ROPE_THETA or ROPE_LISTED; a letter not
  named has no positions); `dense` is the module of a `D` layer (None:
  the kind has none)."""

  form: str
  norm: str
  dense: Optional[str]
  layers: Callable[[Any], str]
  ffns: Callable[[Any], str]
  rotation: Mapping[str, str]


# The seven kinds, value for value.
BLOCKS = {
    BLOCK_BANDED_SOFTMAX: Block(
        FORM_SEQUENTIAL, NORM_REZERO, DENSE_RELU,
        _repeat(LAYER_BANDED_SOFTMAX), _repeat(FFN_DENSE), {}),
    BLOCK_POWER_RETENTION: Block(
        FORM_SEQUENTIAL, NORM_RMS, DENSE_SWIGLU,
        _repeat(LAYER_POWER_RETENTION), _repeat(FFN_DENSE),
        {LAYER_POWER_RETENTION: ROPE_THETA}),
    BLOCK_GATED_DELTA_MOE: Block(
        FORM_SEQUENTIAL, NORM_RMS_ZERO_CENTRED, None,
        _every('full_attention_interval', LAYER_GATED_SOFTMAX,
               LAYER_GATED_DELTA),
        _repeat(FFN_EXPERTS), {LAYER_GATED_SOFTMAX: ROPE_THETA}),
    BLOCK_LATENT_MOE: Block(
        FORM_SEQUENTIAL, NORM_RMS, DENSE_SWIGLU, _repeat(LAYER_LATENT),
        _leading('first_k_dense_replace', FFN_DENSE, FFN_EXPERTS),
        {LAYER_LATENT: ROPE_THETA}),
    BLOCK_PARALLEL_WINDOW_MOE: Block(
        FORM_PARALLEL, NORM_BIAS_FREE_LAYER, None,
        _every('layer_switch', LAYER_FULL_SOFTMAX, LAYER_WINDOW_SOFTMAX),
        _repeat(FFN_EXPERTS), {LAYER_WINDOW_SOFTMAX: ROPE_THETA}),
    BLOCK_WINDOW_MOE: Block(
        FORM_SEQUENTIAL, NORM_RMS, None,
        _listed('layer_types', LAYER_TYPES),
        _listed('mlp_layer_types', MLP_LAYER_TYPES),
        {LAYER_WINDOW_SOFTMAX: ROPE_LISTED, LAYER_FULL_SOFTMAX: ROPE_LISTED}),
    BLOCK_MAMBA_HYBRID: Block(
        FORM_SEQUENTIAL, NORM_RMS, DENSE_SWIGLU,
        _offset_every('attn_layer_period', 'attn_layer_offset',
                      LAYER_FULL_SOFTMAX, LAYER_MAMBA),
        _dense_unless_experts('num_experts'), {}),
}


def block_kind_of(params) -> str:
  """The encoder block kind a configuration names (params.json files
  from before the key existed mean the one block there was)."""
  kind = params.get('block_kind', None) or BLOCK_BANDED_SOFTMAX
  if kind not in BLOCKS:
    raise ValueError(f'unknown block_kind {kind!r}; have {BLOCK_KINDS}')
  return kind


def block(params) -> Block:
  """The row of the kind the configuration names."""
  return BLOCKS[block_kind_of(params)]


def block_form(params) -> str:
  return block(params).form


def layer_pattern(params) -> str:
  """The attention of every layer of the stack, in order, as the kind's
  row derives it from what the configuration states."""
  return block(params).layers(params)


def ffn_pattern(params) -> str:
  """The feed-forward of every layer of the stack, in order, as the kind's
  row derives it from what the configuration states."""
  return block(params).ffns(params)


def holds_experts(params) -> bool:
  """Whether some layer of the model's encoder stack is sparse experts:
  what `forward_launch` says of experts, the counts the forward returns
  and what is refused by name (--tp, int8, train, distill, export) follow
  from it."""
  return ('transformer' in str(params.model_name)
          and FFN_EXPERTS in ffn_pattern(params))


def inference_only(params) -> bool:
  """Whether the model's encoder stack has a layer with no training step,
  no exported form and no sharded or int8 form here: sparse experts, or a
  Mamba mixer (the selective scan has no backward pass in this program)."""
  return holds_experts(params) or (
      'transformer' in str(params.model_name)
      and LAYER_MAMBA in layer_pattern(params))


def rope_parameters(params, letter: str) -> dict:
  """The published `rope_parameters` entry of the layer type whose letter
  `letter` is (window_moe: one entry a layer type)."""
  name = {v: k for k, v in LAYER_TYPES.items()}[letter]
  return dict(params.rope_parameters[name])


# Transformer size presets (reference: transformer_basic_params.py).
TRANSFORMER_SIZE_PARAMS = {
    'tiny': dict(
        num_hidden_layers=6,
        num_heads=4,
        filter_size=256,
    ),
    'base': dict(
        num_hidden_layers=6,
        num_heads=8,
        filter_size=2048,
    ),
    'big': dict(
        num_hidden_layers=6,
        num_heads=16,
        filter_size=4096,
    ),
}


def _set_base_transformer_hparams(params):
  params.model_name = 'transformer'
  params.block_kind = BLOCK_BANDED_SOFTMAX
  params.add_pos_encoding = True
  params.num_heads = 2
  params.layer_norm = False
  params.rezero = True
  params.condense_transformer_input = False
  params.transformer_model_size = 'base'
  # Band half-width; full band is 2*attn_win_size+1 columns.
  params.attn_win_size = 12

  params.num_channels = 1
  params.per_base_hidden_size = 1
  params.pw_hidden_size = 1
  params.ip_hidden_size = 1
  params.sn_hidden_size = 1
  params.ccs_bq_hidden_size = 1
  params.strand_hidden_size = 1

  params.layer_postprocess_dropout = 0.1
  params.attention_dropout = 0.1
  params.relu_dropout = 0.1

  params.batch_size = 256
  params.num_epochs = 9
  params.num_epochs_for_decay = 9
  params.buffer_size = 1_000_000

  params.initial_learning_rate = 3.6246e-3
  params.end_learning_rate = 2.86594e-5
  params.warmup_steps = 35536
  params.weight_decay_rate = 6.9868e-3
  params.beta_1 = 0.9
  params.beta_2 = 0.999
  params.epsilon = 1e-6


def _set_transformer_learned_embeddings_hparams(params):
  _set_base_transformer_hparams(params)
  params.model_name = 'transformer_learn_values'
  params.per_base_hidden_size = 8
  params.pw_hidden_size = 8
  params.ip_hidden_size = 8
  params.strand_hidden_size = 2
  params.sn_hidden_size = 8
  params.ccs_bq_hidden_size = 8
  params.condense_transformer_input = True
  params.transformer_input_size = 280


def _set_transformer_learned_embeddings_distill_hparams(params):
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_distill'
  params.num_hidden_layers = 5
  params.filter_size = 2048
  params.layer_postprocess_dropout = 0.0
  params.attention_dropout = 0.1
  params.relu_dropout = 0.0
  params.init_encoder_stack = True
  params.init_nonencoder_layers = True
  params.teacher_encoder_layers = [1, 2, 3, 4, 5]
  params.student_encoder_layers = [0, 1, 2, 3, 4]
  params.warmup_steps = 0
  params.distill_alpha = 1.0e5
  params.student_alpha = 1.0
  params.temperature = 1.0
  params.logit_loss_identifier = 'mean_squared_error'


def _set_published_block_hparams(params):
  """What every published-block preset below shares: the block's own
  positions (rotary, a convolution, or none) take the sinusoidal
  encoding's place and its own residual the ReZero one's, the published
  models have no dropout, and they are served in bfloat16."""
  params.add_pos_encoding = False
  params.rezero = False
  params.attn_win_size = 0
  params.layer_postprocess_dropout = 0.0
  params.attention_dropout = 0.0
  params.relu_dropout = 0.0
  params.dtype = 'bfloat16'
  params.inference_dtype = 'bfloat16'
  params.use_fused_hotpath = False


def _set_transformer_learned_embeddings_retention_hparams(params):
  """A second encoder block kind at the widths of a public 14B
  linear-attention language model (every attention layer a gated power
  retention layer; 40 layers, hidden 5120, 40 query / 8 key-value heads
  of 128, SwiGLU 17408, RMSNorm 1e-6, rotary base 1e6), behind this
  system's pile-up embedding and 5-way head. Served in bfloat16; at these
  widths one chip holds 8 of the 40 layers (--set num_hidden_layers=8)
  and a pack of 256 windows fills it (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_retention'
  params.block_kind = BLOCK_POWER_RETENTION
  params.transformer_input_size = 5120
  params.num_hidden_layers = 40
  params.num_heads = 40
  params.num_kv_heads = 8
  params.head_dim = 128
  params.filter_size = 17408
  params.rope_theta = 1.0e6
  params.rms_norm_eps = 1.0e-6
  params.retention_degree = 2
  _set_published_block_hparams(params)


def _set_transformer_learned_embeddings_gdn_moe_hparams(params):
  """A third encoder block kind at the widths of a public 80B
  sparse-expert language model with 3B active parameters: hidden 2048, 48
  layers of which every fourth is gated softmax attention (16 query / 2
  key-value heads of 256, rotary on a quarter of the head at base 1e7)
  and the rest Gated DeltaNet mixers (16 key / 32 value heads of 128,
  short convolution of 4), each followed by 512 routed experts of width
  512, 10 a token, and a gated shared expert of width 512. Behind this
  system's pile-up embedding and 5-way head, served in bfloat16.

  Which experts this process holds is a size of the configuration:
  `experts_held_first` and `experts_held_count` of the `num_experts`
  published. The router keeps its full width and its top-k; what the
  experts held elsewhere would add is added elsewhere (the exchange
  between sharing chips is not in this program). One v5e chip holds one
  period of the pattern with half of each layer's experts
  (--set num_hidden_layers=4 --set experts_held_count=256) and a pack of
  512 windows (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_gdn_moe'
  params.block_kind = BLOCK_GATED_DELTA_MOE
  params.transformer_input_size = 2048
  params.num_hidden_layers = 48
  params.full_attention_interval = 4
  # Gated DeltaNet mixer.
  params.linear_num_key_heads = 16
  params.linear_num_value_heads = 32
  params.linear_key_head_dim = 128
  params.linear_value_head_dim = 128
  params.linear_conv_kernel_dim = 4
  # Gated softmax attention.
  params.num_heads = 16
  params.num_kv_heads = 2
  params.head_dim = 256
  params.partial_rotary_factor = 0.25
  params.rope_theta = 1.0e7
  params.rms_norm_eps = 1.0e-6
  # Sparse experts; filter_size is one expert's width.
  params.num_experts = 512
  params.num_experts_per_tok = 10
  params.moe_intermediate_size = 512
  params.filter_size = 512
  params.shared_expert_intermediate_size = 512
  params.norm_topk_prob = True
  # A softmax router without a selection bias or a scaling factor, and a
  # sigmoid gate on the shared expert.
  params.router_scoring = 'softmax'
  params.router_selection_bias = False
  params.routed_scaling_factor = 1.0
  params.shared_expert_gated = True
  params.experts_held_first = 0
  params.experts_held_count = 512
  _set_published_block_hparams(params)


def _set_transformer_learned_embeddings_mla_moe_hparams(params):
  """A fourth encoder block kind at the widths of a public 30B
  sparse-expert language model with 3B active parameters: hidden 2048, 48
  layers, each with multi-head latent attention (32 heads; keys and
  values out of one latent of 512 behind an RMSNorm, no query latent;
  query/key heads of 128 + a rotary part of 64 whose key all heads share,
  value heads of 128; rotary base 1e6) and a feed-forward chosen per
  layer: SwiGLU 6144 in the one leading layer, behind it 128 routed
  experts of width 768, 6 a token, scored by a sigmoid and chosen with a
  balancing bias (one group, so no group limit), weights renormalised and
  scaled by 2.448, plus an ungated shared expert of 2 x 768. Behind this
  system's pile-up embedding and 5-way head, served in bfloat16.

  One v5e chip holds the leading dense layer and the seven expert layers
  behind it with every expert (--set num_hidden_layers=8) and a pack of
  512 windows (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_mla_moe'
  params.block_kind = BLOCK_LATENT_MOE
  params.transformer_input_size = 2048
  params.num_hidden_layers = 48
  # Multi-head latent attention.
  params.num_heads = 32
  params.qk_nope_head_dim = 128
  params.qk_rope_head_dim = 64
  params.v_head_dim = 128
  params.kv_lora_rank = 512
  params.q_lora_rank = None
  params.rope_theta = 1.0e6
  params.rms_norm_eps = 1.0e-6
  # The leading dense layers' SwiGLU; filter_size is its width.
  params.first_k_dense_replace = 1
  params.filter_size = 6144
  # Sparse experts behind them.
  params.num_experts = 128
  params.num_experts_per_tok = 6
  params.moe_intermediate_size = 768
  params.shared_expert_intermediate_size = 1536
  params.norm_topk_prob = True
  params.router_scoring = 'sigmoid'
  params.router_selection_bias = True
  params.routed_scaling_factor = 2.448
  params.shared_expert_gated = False
  params.n_group = 1
  params.topk_group = 1
  params.experts_held_first = 0
  params.experts_held_count = 128
  _set_published_block_hparams(params)


def _set_transformer_learned_embeddings_parallel_moe_hparams(params):
  """A fifth encoder block kind at the widths of a public 218B
  sparse-expert language model with 25B active parameters: hidden 4096, 32
  layers, each ONE bias-free LayerNorm (eps 1e-5) whose output both the
  attention and the feed-forward read, added to the stream together. The
  attention has 128 query / 8 key-value heads of 128 without q/k norm or
  gate; three layers in four rotate the whole head (base 50,000) and attend
  within 4,096 positions, the fourth has no positions and no mask. The
  feed-forward is 128 routed experts of width 4096, 8 a token, scored by a
  sigmoid without a bias or a factor and renormalised, plus the mean of 4
  shared experts of width 4096 (run as one SwiGLU of 16384 times 1/4).
  Behind this system's pile-up embedding and 5-way head, served in
  bfloat16.

  Which experts this process holds is a size of the configuration, as for
  the other sparse-expert kinds. One v5e chip holds one period of the
  pattern with an eighth of each layer's experts (--set num_hidden_layers=4
  --set experts_held_count=16) and a pack of 256 windows
  (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_parallel_moe'
  params.block_kind = BLOCK_PARALLEL_WINDOW_MOE
  params.transformer_input_size = 4096
  params.num_hidden_layers = 32
  params.layer_norm_eps = 1.0e-5
  # Window and full attention, one full layer a `layer_switch` layers.
  params.layer_switch = 4
  params.sliding_window = 4096
  params.num_heads = 128
  params.num_kv_heads = 8
  params.head_dim = 128
  params.rope_theta = 5.0e4
  # Sparse experts in every layer; filter_size is one expert's width.
  params.first_k_dense_replace = 0
  params.num_experts = 128
  params.num_experts_per_tok = 8
  params.moe_intermediate_size = 4096
  params.filter_size = 4096
  params.num_shared_experts = 4
  params.shared_expert_combination = 'average'
  params.shared_expert_intermediate_size = 4 * 4096
  params.norm_topk_prob = True
  params.router_scoring = 'sigmoid'
  params.router_selection_bias = False
  params.routed_scaling_factor = 1.0
  params.shared_expert_gated = False
  params.experts_held_first = 0
  params.experts_held_count = 128
  _set_published_block_hparams(params)


# A public 12B sparse-expert model's layer types, period 4 over 28 layers.
_WINDOW_MOE_LAYER_TYPES = (['sliding_attention'] * 3 + ['full_attention']) * 7


def _set_transformer_learned_embeddings_window_moe_hparams(params):
  """A sixth encoder block kind at the widths of a public 12B sparse-expert
  model with 2.5B active parameters: hidden 2304, 28 layers, each a
  sequential pre-RMSNorm block (eps 1e-6, plain weights). The attention has
  32 query / 4 key-value heads of 128 without q/k norm, gate or bias, and
  rotates the whole head; `layer_types` lists three sliding_attention
  layers (window 1,024, default rotation at base 500,000) to one
  full_attention layer (YaRN rotation: factor 16 over an original 8,192
  positions, beta 32 / 1, magnitude 1.2772588722239782). The feed-forward
  of every layer is 64 routed experts of width 896, 8 a token, scored by a
  softmax and renormalised, with no shared expert. Behind this system's
  pile-up embedding and 5-way head, served in bfloat16.

  Which experts this process holds is a size of the configuration, as for
  the other sparse-expert kinds. One v5e chip holds two periods of the
  pattern with every expert (num_hidden_layers 8 and the first 8 entries
  of both lists) and a pack of 512 windows (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_window_moe'
  params.block_kind = BLOCK_WINDOW_MOE
  params.transformer_input_size = 2304
  params.num_hidden_layers = 28
  params.rms_norm_eps = 1.0e-6
  # Each layer's attention as listed, and each layer type's rotation.
  params.layer_types = list(_WINDOW_MOE_LAYER_TYPES)
  params.mlp_layer_types = ['sparse'] * 28
  params.sliding_window = 1024
  params.num_heads = 32
  params.num_kv_heads = 4
  params.head_dim = 128
  params.rope_parameters = {
      'sliding_attention': {'rope_type': 'default', 'rope_theta': 500000},
      'full_attention': {
          'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
          'original_max_position_embeddings': 8192, 'beta_fast': 32,
          'beta_slow': 1, 'attention_factor': 1.2772588722239782}}
  # Routed experts alone in every layer; filter_size is one expert's width.
  params.num_experts = 64
  params.num_experts_per_tok = 8
  params.moe_intermediate_size = 896
  params.filter_size = 896
  params.num_shared_experts = 0
  params.shared_expert_intermediate_size = 0
  params.norm_topk_prob = True
  params.router_scoring = 'softmax'
  params.router_selection_bias = False
  params.routed_scaling_factor = 1.0
  params.shared_expert_gated = False
  params.experts_held_first = 0
  params.experts_held_count = 64
  _set_published_block_hparams(params)


def _set_transformer_learned_embeddings_mamba_hybrid_hparams(params):
  """A seventh encoder block kind at the widths of a public 3B dense hybrid
  model: hidden 2560, 28 layers, each a sequential pre-RMSNorm block (eps
  1e-6, plain weights). Layer n is attention where n % 14 == 7 (20 query
  heads over one key-value head of 128, no positions, no q/k norm, gate or
  bias) and a Mamba-1 mixer otherwise (inner width 2 x 2560, a causal
  convolution of 4 taps with a bias, dt of rank 160, a state of 16, RMSNorms
  on dt, B and C, no projection biases). Every feed-forward is SwiGLU 8192
  (one expert: a dense layer). Behind this system's pile-up embedding and
  5-way head, served in bfloat16.

  One v5e chip holds the whole stack (5.3 GiB of weights) and a pack of 256
  windows (docs/inference.md)."""
  _set_transformer_learned_embeddings_hparams(params)
  params.model_name = 'transformer_learn_values_mamba_hybrid'
  params.block_kind = BLOCK_MAMBA_HYBRID
  params.transformer_input_size = 2560
  params.num_hidden_layers = 28
  params.rms_norm_eps = 1.0e-6
  # Attention where n % attn_layer_period == attn_layer_offset.
  params.attn_layer_period = 14
  params.attn_layer_offset = 7
  params.num_heads = 20
  params.num_kv_heads = 1
  params.head_dim = 128
  # The Mamba mixer.
  params.mamba_d_state = 16
  params.mamba_d_conv = 4
  params.mamba_expand = 2
  params.mamba_dt_rank = 160
  params.mamba_conv_bias = True
  params.mamba_proj_bias = False
  # One expert: every feed-forward a SwiGLU of filter_size.
  params.num_experts = 1
  params.num_experts_per_tok = 1
  params.expert_layer_period = 2
  params.expert_layer_offset = 1
  params.filter_size = 8192
  _set_published_block_hparams(params)


def _set_base_fc_hparams(params):
  params.model_name = 'fc'
  params.fc_size = [256, 512, 256, 128]
  params.fc_dropout = 0.0
  params.num_channels = 1
  params.per_base_hidden_size = 1
  params.pw_hidden_size = 1
  params.ip_hidden_size = 1
  params.strand_hidden_size = 1
  params.ccs_bq_hidden_size = 1
  params.sn_hidden_size = 1
  params.l2 = 0.0
  params.batch_size = 256
  params.num_epochs = 15
  params.num_epochs_for_decay = 15
  params.buffer_size = 1_000_000
  params.initial_learning_rate = 3.6246e-3
  params.end_learning_rate = 2.86594e-5
  params.warmup_steps = 35536
  params.weight_decay_rate = 6.9868e-3
  params.beta_1 = 0.9
  params.beta_2 = 0.999
  params.epsilon = 1e-6


def _set_base_conv_hparams(params):
  """Convolutional (ResNet-v2) model family."""
  params.model_name = 'conv_net'
  params.conv_model = 'resnet50'
  params.num_channels = 1
  params.per_base_hidden_size = 1
  params.pw_hidden_size = 1
  params.ip_hidden_size = 1
  params.strand_hidden_size = 1
  params.ccs_bq_hidden_size = 1
  params.sn_hidden_size = 1
  params.batch_size = 256
  params.num_epochs = 9
  params.num_epochs_for_decay = 9
  params.buffer_size = 1_000_000
  params.initial_learning_rate = 3.6246e-3
  params.end_learning_rate = 2.86594e-5
  params.warmup_steps = 35536
  params.weight_decay_rate = 6.9868e-3
  params.beta_1 = 0.9
  params.beta_2 = 0.999
  params.epsilon = 1e-6


_TESTDATA = '/root/reference/deepconsensus/testdata'


def _set_test_data_hparams(params):
  params.train_path = [
      os.path.join(_TESTDATA, 'human_1m/tf_examples/train/*')
  ]
  params.eval_path = params.train_path
  params.test_path = params.train_path
  params.inference_path = os.path.join(
      _TESTDATA, 'human_1m/tf_examples/inference/*'
  )
  params.n_examples_train = 253
  params.n_examples_eval = 253
  params.max_passes = 20
  params.batch_size = 1
  params.num_epochs = 1
  params.buffer_size = 10
  if params.model_name == 'fc':
    params.fc_size = [4, 4]


def _set_test_bq_data_hparams(params):
  _set_test_data_hparams(params)
  params.use_ccs_bq = True
  params.train_path = [
      os.path.join(_TESTDATA, 'human_1m/tf_examples_bq/train/*')
  ]
  params.eval_path = params.train_path
  params.test_path = params.train_path
  params.inference_path = os.path.join(
      _TESTDATA, 'human_1m/tf_examples_bq/inference/*'
  )


def _set_custom_data_hparams(params):
  params.tf_dataset = ['/path_to_training_data']
  params.max_passes = 20


def get_config(config_name: Optional[str] = None) -> ml_collections.ConfigDict:
  """Builds a ConfigDict for '{model}+{dataset}' preset names."""
  params = ml_collections.ConfigDict()

  params.trial = 1
  params.rezero = False

  params.PW_MAX = 255
  params.IP_MAX = 255
  params.SN_MAX = 500
  params.CCS_BQ_MAX = 95
  params.STRAND_MAX = 2

  params.use_bases = True
  params.use_pw = True
  params.use_ip = True
  params.use_strand = True
  params.use_sn = True
  params.use_ccs = True
  params.use_ccs_bq = False
  params.per_base_hidden_size = 1
  params.pw_hidden_size = 1
  params.ip_hidden_size = 1
  params.sn_hidden_size = 1
  params.strand_hidden_size = 1
  params.ccs_bq_hidden_size = 1

  params.total_rows = ml_collections.config_dict.placeholder(int)

  params.vocab_size = 5
  params.seed = 1
  params.remove_label_gaps = False
  # Use the shard-interleaved StreamingDataset for training input
  # instead of the eager in-memory DatasetIterator. Requires
  # n_examples_train to size the per-epoch step budget
  # (--set streaming=true --set n_examples_train=N).
  params.streaming = False
  # Streaming-loader decode processes (0 = in-process decode). What one
  # worker sustains (gzip + minimal proto parse) is not measured; size to
  # the mesh's consumption rate on multi-core hosts.
  params.loader_workers = 0
  params.loss_function = 'alignment_loss'

  # Training-time window augmentation (no reference counterpart: the
  # reference effectively never repeats a window across ~100M-example
  # epochs, train_tpu_model.md:234-239; augmentation substitutes for
  # that diversity on small corpora). Probabilities are per example,
  # applied to training batches only (models/data.py:augment_batch).
  params.augment = False
  params.augment_perm_prob = 0.5     # shuffle subread order
  params.augment_drop_prob = 0.3     # downsample subreads (keep >= half)
  params.augment_rc_prob = 0.5       # reverse-complement the window
  params.augment_jitter_prob = 0.3   # +/-1 jitter on nonzero PW/IP

  # AlignmentLoss parameters (reference: model_configs.py:320-323).
  params.del_cost = 10.0
  params.loss_reg = 0.1
  params.band_width = ml_collections.config_dict.placeholder(int)

  params.max_length = DEFAULT_MAX_LENGTH

  params.model_config_name = 'transformer_learn_values'
  params.dataset_config_name = 'ccs'

  # TPU-native execution knobs (not in the reference).
  params.dtype = 'bfloat16'          # compute dtype; params stay float32
  # The attention softmax accumulation dtype on the XLA path
  # (None/'float32' = reference-matching default).
  params.attn_softmax_dtype = ml_collections.config_dict.placeholder(str)
  params.use_pallas_attention = False
  # Batch-major fused embed->condense->layer-0-attention Pallas kernel
  # for the short-window (L<=128) inference hot path
  # (ops/fused_window_attention.py). Falls back to the XLA path for
  # training, init, non-condensed/non-ReZero configs, and long windows.
  params.use_fused_hotpath = False
  # Quantized-inference levers (inference-only; training ignores both).
  # inference_dtype: 'bfloat16' casts checkpoint weights once at load
  # and runs activations end-to-end in bf16 (attn_softmax_dtype stays
  # an independent f32 escape hatch). quantize_matmuls: 'int8' applies
  # per-output-channel symmetric weight quantization to the encoder's
  # attention-projection and FFN matmuls, with the dequant folded into
  # the fused kernel epilogue (models/quantize.py).
  params.inference_dtype = ml_collections.config_dict.placeholder(str)
  params.quantize_matmuls = ml_collections.config_dict.placeholder(str)
  # Window length buckets for variable-width inference (None = single
  # shape at max_length, the reference behavior). When set (e.g.
  # (100, 200)), featurize pads each smart window to the smallest
  # bucket that fits instead of pad-to-max, and the engine packs and
  # dispatches each bucket separately with one compiled executable per
  # bucket (resolve_window_buckets / bucket_for above). The smallest
  # bucket must equal max_length.
  params.window_buckets = ml_collections.config_dict.placeholder(object)
  # Route AlignmentLoss through the whole-DP Pallas wavefront kernels
  # (forward scorer + custom-VJP backward) instead of the lax.scan DP.
  # Only applies when band_width is None (the training default).
  # None = auto: Pallas on a real TPU backend (its speed against the scan
  # DP is not measured), lax.scan elsewhere (the interpreted kernel would
  # dominate CPU runs).
  params.use_pallas_wavefront = None
  # Rematerialize encoder blocks in the backward pass (jax.checkpoint):
  # trades FLOPs for HBM headroom at large batch/long windows.
  params.remat = False
  params.dp_axis = 'data'            # mesh axis names
  params.tp_axis = 'model'
  params.eval_every_n_steps = 3000
  params.log_every_n_steps = 100
  # Eval metric that selects best_checkpoint.txt (HIGHER is better —
  # do not point it at eval/loss). The reference pins
  # eval/per_example_accuracy; on small held-out eval sets that ties
  # at 0.0 for every checkpoint, so eval/identity_pred is the
  # useful override there.
  params.best_checkpoint_metric = 'eval/per_example_accuracy'

  params.tpu_scale_factor = 1

  # Training fault tolerance (models/train.py, models/data.py).
  # on_shard_error: StreamingDataset policy for an undecodable shard —
  # 'fail' aborts, 'skip' counts + moves on (--on_shard_error).
  params.on_shard_error = 'fail'
  # NaN/Inf sentinel: after this many CONSECUTIVE non-finite train
  # steps, roll back to the last valid checkpoint (0 disables).
  params.nan_sentinel_steps = 3
  # Rollback budget; divergence persisting past it raises a permanent
  # NonFiniteTrainingError instead of ping-ponging forever.
  params.nan_max_rollbacks = 2
  # Decode window ids ('name') into training batches so NaN dead
  # letters can attribute a diverged batch to its windows (small
  # decode cost; off by default).
  params.track_window_ids = False
  # Mid-run checkpoint cadence for distillation (models/distill.py):
  # save every N steps so a killed/preempted distill stage resumes
  # from the last save instead of restarting (0 = final-only, the
  # pre-flywheel behavior). Training proper already checkpoints on its
  # eval_every_n_steps cadence.
  params.checkpoint_every_n_steps = 0

  if config_name is None:
    return params

  model_config_name, dataset_config_name = config_name.split('+')
  params.model_config_name = model_config_name
  params.dataset_config_name = dataset_config_name
  params.tf_dataset = None
  params.limit = -1
  if model_config_name == 'fc':
    _set_base_fc_hparams(params)
  elif model_config_name == 'conv_net':
    _set_base_conv_hparams(params)
  elif model_config_name == 'transformer':
    _set_base_transformer_hparams(params)
  elif model_config_name == 'transformer_learn_values':
    _set_transformer_learned_embeddings_hparams(params)
  elif model_config_name == 'transformer_learn_values_distill':
    _set_transformer_learned_embeddings_distill_hparams(params)
  elif model_config_name == 'transformer_learn_values_retention':
    _set_transformer_learned_embeddings_retention_hparams(params)
  elif model_config_name == 'transformer_learn_values_gdn_moe':
    _set_transformer_learned_embeddings_gdn_moe_hparams(params)
  elif model_config_name == 'transformer_learn_values_mla_moe':
    _set_transformer_learned_embeddings_mla_moe_hparams(params)
  elif model_config_name == 'transformer_learn_values_parallel_moe':
    _set_transformer_learned_embeddings_parallel_moe_hparams(params)
  elif model_config_name == 'transformer_learn_values_window_moe':
    _set_transformer_learned_embeddings_window_moe_hparams(params)
  elif model_config_name == 'transformer_learn_values_mamba_hybrid':
    _set_transformer_learned_embeddings_mamba_hybrid_hparams(params)
  else:
    raise ValueError(f'Unknown model_config_name: {model_config_name}')

  if dataset_config_name == 'test':
    _set_test_data_hparams(params)
  elif dataset_config_name == 'test_bq':
    _set_test_bq_data_hparams(params)
  elif dataset_config_name == 'custom':
    _set_custom_data_hparams(params)
  else:
    raise ValueError(
        f'dataset_config_name is {dataset_config_name}. Must be one of: '
        'test, test_bq, custom'
    )
  return params


def finalize_params(
    params: ml_collections.ConfigDict,
    max_length: Optional[int] = None,
    num_devices: int = 1,
    is_training: bool = True,
) -> None:
  """Derives dependent parameters (reference modify_params).

  Batch size scales by device count (global batch = per-replica x N,
  reference: model_utils.py:279-299); hidden size derives from the
  enabled per-feature embedding widths.
  """
  with params.unlocked():
    if not is_training:
      for key in ('tf_dataset', 'train_path', 'eval_path', 'test_path',
                  'inference_path'):
        if key in params:
          del params[key]

    if num_devices > 1:
      params.batch_size = params.batch_size * params.tpu_scale_factor
      params.batch_size *= num_devices

    if max_length is not None:
      params.max_length = max_length
    if 'max_length' not in params:
      raise ValueError('No params.max_length provided.')

    params.total_rows = _total_rows(params.max_passes, params.use_ccs_bq)

    if 'transformer_learn_values' in params.model_name:
      dim = (
          params.use_bases * params.per_base_hidden_size
          + params.use_pw * params.pw_hidden_size
          + params.use_ip * params.ip_hidden_size
          + params.use_strand * params.strand_hidden_size
          + params.use_ccs_bq * params.ccs_bq_hidden_size
      )
      params.hidden_size = (
          params.max_passes * dim
          + params.use_ccs * params.per_base_hidden_size
          + params.use_ccs_bq * params.ccs_bq_hidden_size
          + params.use_sn * params.sn_hidden_size * 4
      )
    else:
      params.hidden_size = params.total_rows

    if 'transformer' in params.model_name and params.hidden_size % 2 != 0:
      params.hidden_size += 1

    if 'transformer_learn_values' in params.model_name:
      if params.condense_transformer_input:
        params.hidden_size = params.transformer_input_size
    if 'transformer' in params.model_name:
      for name, value in TRANSFORMER_SIZE_PARAMS[
          params.get('transformer_model_size', 'base')
      ].items():
        if name not in params:
          params[name] = value


def save_params_as_json(out_dir: str, params: ml_collections.ConfigDict) -> str:
  """Writes params.json beside checkpoints (model_utils.py:468-476)."""
  os.makedirs(out_dir, exist_ok=True)
  path = os.path.join(out_dir, 'params.json')
  with open(path, 'w') as f:
    json.dump(params.to_dict(), f, indent=2, sort_keys=True, default=str)
  return path


def read_params_from_json(
    checkpoint_path: str,
) -> ml_collections.ConfigDict:
  """Loads params.json from a checkpoint directory or file prefix
  (model_utils.py:434-465). Unknown keys are kept (forward compat)."""
  # Orbax checkpoints are directories under <out_dir>/checkpoints/, so
  # walk up from the given path until params.json is found.
  candidates = []
  base = checkpoint_path if os.path.isdir(checkpoint_path) else (
      os.path.dirname(checkpoint_path)
  )
  for _ in range(3):
    candidates.append(os.path.join(base, 'params.json'))
    base = os.path.dirname(base)
  for json_path in candidates:
    if os.path.exists(json_path):
      break
  else:
    raise FileNotFoundError(
        f'params.json not found near {checkpoint_path!r}; looked in '
        f'{candidates}'
    )
  with open(json_path) as f:
    loaded = json.load(f)
  params = get_config()
  with params.unlocked():
    for key, value in loaded.items():
      params[key] = value
  return params
