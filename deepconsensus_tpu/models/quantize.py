"""Load-time quantization levers for inference.

Two independent levers, both applied ONCE at checkpoint load (before
any device placement, so sharded transfers ship the shrunken bytes; a
leaf that already has the requested type, on the host or on the device,
passes through as the same array):

* `params.inference_dtype = 'bfloat16'`: cast every float param leaf
  to bf16. The model's compute dtype follows (runner sets params.dtype
  to match), activations run bf16 end-to-end, and the
  `attn_softmax_dtype` escape hatch stays an independent f32 knob.

* `params.quantize_matmuls = 'int8'`: per-output-channel symmetric
  weight quantization of the encoder's attention-projection and FFN
  matmul kernels. scale[n] = max|W[:, n]| / 127, values = round(W /
  scale) clipped to int8. Two artifacts come out:

  - the params leaf is REPLACED by the dequantized weight
    (values * scale, f32) so every consumer that reads raw params —
    the XLA fallback path, the PR-5 layer-0 attention kernel,
    models/evaluate.py — sees the exact quantized-effective weights,
    making accuracy gates and parity tests consistent across paths;
  - a parallel 'quant' collection carries the int8 values + f32
    scales, mirroring the params tree shape, for the fused encoder
    block kernel (ops/fused_encoder_block.py) to consume directly:
    int8 stays int8 in HBM/VMEM and the dequant runs in the matmul
    epilogue.

Quantization happens on the f32 checkpoint BEFORE any bf16 cast, so
scales are computed at full precision and stay f32.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

_ATTN_SUBS = ('query', 'key', 'value', 'output_transform')
_FFN_SUBS = ('filter_layer', 'output_layer')


def _as_mutable(tree):
  """Deep-copy a (possibly frozen) nested mapping into plain dicts."""
  if hasattr(tree, 'items'):
    return {k: _as_mutable(v) for k, v in tree.items()}
  return tree


def _quantize_2d(w2: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
  """[K, N] f32 -> (int8 values [K, N], f32 scale [N])."""
  w2 = jnp.asarray(w2, jnp.float32)
  scale = jnp.max(jnp.abs(w2), axis=0) / 127.0
  scale = jnp.where(scale == 0.0, 1.0, scale)
  values = jnp.clip(jnp.round(w2 / scale), -127, 127).astype(jnp.int8)
  return values, scale


def quantize_matmul_params(
    variables: Dict[str, Any], num_layers: int
) -> Tuple[Dict[str, Any], int]:
  """int8-quantize the encoder matmul kernels of a loaded checkpoint.

  Returns (variables with dequantized params + 'quant' collection,
  number of quantized matmuls). Attention kernels quantize in their 2D
  matmul form (q/k/v [H, heads, hd] -> [H, H]; output [heads, hd, H]
  -> [H, H]) so the per-output-channel axis matches how the fused
  kernel contracts them.
  """
  variables = _as_mutable(variables)
  encoder = variables.get('params', {}).get('encoder')
  if encoder is None:
    return variables, 0
  quant_encoder: Dict[str, Any] = {}
  n_quantized = 0

  def quantize_leaf(module: Dict[str, Any], mod_name: str, sub: str,
                    to2d, from2d):
    nonlocal n_quantized
    kernel = module[sub]['kernel']
    values, scale = _quantize_2d(to2d(kernel))
    module[sub] = dict(module[sub])
    module[sub]['kernel'] = from2d(
        values.astype(jnp.float32) * scale).astype(kernel.dtype)
    quant_encoder.setdefault(mod_name, {})[sub] = {
        'values': values, 'scale': scale}
    n_quantized += 1

  for n in range(num_layers):
    attn_name = f'self_attention_{n}'
    if attn_name in encoder:
      attn = encoder[attn_name] = dict(encoder[attn_name])
      for sub in _ATTN_SUBS:
        kernel = attn[sub]['kernel']
        shape = kernel.shape
        if sub == 'output_transform':
          to2d = lambda w: w.reshape(-1, w.shape[-1])
        else:
          to2d = lambda w: w.reshape(w.shape[0], -1)
        quantize_leaf(attn, attn_name, sub, to2d,
                      lambda w2, shape=shape: w2.reshape(shape))
    ffn_name = f'ffn_{n}'
    if ffn_name in encoder:
      ffn = encoder[ffn_name] = dict(encoder[ffn_name])
      for sub in _FFN_SUBS:
        quantize_leaf(ffn, ffn_name, sub, lambda w: w, lambda w2: w2)

  if n_quantized:
    variables.setdefault('quant', {})['encoder'] = quant_encoder
  return variables, n_quantized


def cast_params(variables: Dict[str, Any], dtype: Any) -> Dict[str, Any]:
  """Cast the float leaves of the 'params' collection to `dtype`,
  leaving every other collection (int8 values, f32 scales) untouched."""
  variables = dict(variables)
  dtype = jnp.dtype(dtype)

  def cast(x):
    have = jnp.result_type(x)
    if have == dtype or not jnp.issubdtype(have, jnp.floating):
      # Already there (gigabytes of bfloat16 leaves resident on the
      # device stay the one copy), or not a float.
      return x
    return x.astype(dtype)

  variables['params'] = jax.tree_util.tree_map(
      cast, _as_mutable(variables['params']))
  return variables


def prepare_inference_variables(
    variables: Dict[str, Any], params
) -> Tuple[Dict[str, Any], int]:
  """Apply the configured quantization levers to loaded variables.

  Order matters: int8 quantization runs on the f32 checkpoint first
  (full-precision scales), then the bf16 weight cast rounds the
  already-dequantized leaves. Returns (variables, n_quantized_matmuls).
  """
  n_quantized = 0
  if params.get('quantize_matmuls', None) == 'int8':
    variables, n_quantized = quantize_matmul_params(
        variables, params.num_hidden_layers)
  inference_dtype = params.get('inference_dtype', None)
  if inference_dtype and jnp.dtype(inference_dtype) != jnp.float32:
    variables = cast_params(variables, inference_dtype)
  return variables, n_quantized
