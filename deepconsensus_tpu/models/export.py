"""Deployable model export via jax.export (StableHLO).

Equivalent of the reference's checkpoint->SavedModel conversion
(reference: deepconsensus/models/convert_to_saved_model.py:67-105):
bakes restored parameters into a fixed-batch serving function, exports
it as portable StableHLO bytes, and copies params.json alongside. The
artifact reloads without any model code, like a SavedModel signature.
"""
from __future__ import annotations

import json
import logging
import os
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import export as jax_export
import ml_collections

from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.models import config as config_lib
from deepconsensus_tpu.models import model as model_lib
from deepconsensus_tpu.ops import output_plane

ARTIFACT_NAME = 'serving.stablehlo'


def export_model(
    checkpoint_path: str,
    out_dir: str,
    batch_size: int = 1024,
    variables: Optional[Dict] = None,
    params: Optional[ml_collections.ConfigDict] = None,
    polymorphic_batch: bool = True,
    strict_polymorphic: bool = False,
    inference_dtype: Optional[str] = None,
    quantize_matmuls: Optional[str] = None,
    device_epilogue: bool = True,
    max_base_quality: int = 93,
    dc_calibration: str = 'skip',
) -> str:
  """Exports a serving function; returns the artifact path.

  With device_epilogue (the default) the whole output plane is
  compiled into the artifact: the serving call returns the final uint8
  (base ids, Phred quality) planes — argmax plus the exact
  threshold-table quality (ops/output_plane.py) for the given
  dc_calibration / max_base_quality, which are baked into the program
  and recorded in the metadata (from_exported refuses a load whose
  quality knobs disagree). Without it, the serving call returns
  softmax preds and the host computes qualities, as before.

  polymorphic_batch exports the batch dimension symbolically, so the
  artifact serves ANY batch size (the reference's SavedModel does
  this; a fixed-batch artifact was the round-2 limitation).
  batch_size is kept in the metadata as the recommended serving batch.
  Falls back to a fixed-batch export if symbolic export fails — unless
  strict_polymorphic, which re-raises so automated pipelines cannot
  silently ship an artifact that rejects every batch size but the
  baked one. The fallback is always surfaced in export_meta.json's
  `polymorphic_batch` field; callers that require a polymorphic
  artifact should assert on it (see load_exported).
  """
  if strict_polymorphic and not polymorphic_batch:
    raise ValueError(
        'strict_polymorphic=True requires polymorphic_batch=True (a '
        'fixed-batch export can never satisfy the strict guarantee).')
  if params is None:
    params = config_lib.read_params_from_json(checkpoint_path)
    config_lib.finalize_params(params, is_training=False)
  model_lib.refuse_inference_only_kind(params, 'export')
  if inference_dtype or (quantize_matmuls and quantize_matmuls != 'none'):
    with params.unlocked():
      if inference_dtype:
        params.inference_dtype = inference_dtype
        params.dtype = inference_dtype
      if quantize_matmuls and quantize_matmuls != 'none':
        params.quantize_matmuls = quantize_matmuls
  model = model_lib.get_model(params)

  if variables is None:
    from deepconsensus_tpu.models.checkpoints import load_params

    variables = {'params': load_params(checkpoint_path)}
  # Bake the quantization levers into the exported program: weights
  # are cast/quantized before tracing, so the artifact carries the
  # quantized-effective weights and the metadata below records which
  # levers it was built with (from_exported refuses a mismatched load).
  from deepconsensus_tpu.models import quantize as quantize_lib

  variables, _ = quantize_lib.prepare_inference_variables(variables, params)

  thresholds = None
  if device_epilogue:
    thresholds = output_plane.quality_thresholds(
        calibration_lib.parse_calibration_string(dc_calibration),
        max_base_quality)
    if thresholds is None:
      logging.warning(
          'device epilogue requested but dc_calibration=%r / '
          'max_base_quality=%d is not device-representable; exporting '
          'a pre-epilogue (softmax-preds) artifact instead.',
          dc_calibration, max_base_quality)
      device_epilogue = False

  def serving_fn(rows):
    preds = model.apply(variables, rows)
    if thresholds is None:
      return preds
    return output_plane.phred_epilogue(preds, thresholds)

  static_shape = (batch_size, params.total_rows, params.max_length, 1)
  exported = None
  is_polymorphic = False
  if polymorphic_batch:
    try:
      (b,) = jax_export.symbolic_shape('b')
      exported = jax_export.export(jax.jit(serving_fn))(
          jax.ShapeDtypeStruct(
              (b,) + static_shape[1:], jnp.float32
          )
      )
      is_polymorphic = True
    except Exception as e:  # pragma: no cover - model not batch-polymorphic
      if strict_polymorphic:
        raise RuntimeError(
            'Batch-polymorphic export failed and strict_polymorphic is '
            'set; refusing to fall back to a fixed-batch artifact.'
        ) from e
      logging.warning(
          'Batch-polymorphic export failed (%s: %s); falling back to a '
          'fixed-batch artifact that only serves batch_size=%d.',
          type(e).__name__, e, batch_size)
      exported = None
  if exported is None:
    exported = jax_export.export(jax.jit(serving_fn))(
        jax.ShapeDtypeStruct(static_shape, jnp.float32)
    )
  os.makedirs(out_dir, exist_ok=True)
  artifact = os.path.join(out_dir, ARTIFACT_NAME)
  with open(artifact, 'wb') as f:
    f.write(exported.serialize())
  config_lib.save_params_as_json(out_dir, params)
  with open(os.path.join(out_dir, 'export_meta.json'), 'w') as f:
    json.dump({'batch_size': batch_size, 'rows_shape': static_shape,
               'polymorphic_batch': is_polymorphic,
               'inference_dtype': params.get('inference_dtype', None)
               or 'float32',
               'quantize_matmuls': params.get('quantize_matmuls', None)
               or 'none',
               'device_epilogue': bool(device_epilogue),
               'max_base_quality': int(max_base_quality),
               'dc_calibration': dc_calibration}, f)
  return artifact


def load_exported(out_dir: str) -> Tuple[Callable, Dict]:
  """Loads an exported artifact; returns (callable, meta)."""
  with open(os.path.join(out_dir, ARTIFACT_NAME), 'rb') as f:
    exported = jax_export.deserialize(f.read())
  with open(os.path.join(out_dir, 'export_meta.json')) as f:
    meta = json.load(f)
  return exported.call, meta
