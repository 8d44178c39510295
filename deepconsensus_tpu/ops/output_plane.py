"""Device-resident output plane: softmax preds -> uint8 (ids, quals).

The host epilogue (inference/runner._finalize_sync) turns the device
max-prob into a Phred integer with numpy transcendentals:

    error = np.maximum(1.0 - max_prob, 1e-12)
    q     = -10 * np.log10(error)            # then calibrate / clamp /
    q     = round_half_even(min(q, maxq))    # round / floor at 0

Re-evaluating that math on device cannot be byte-identical: XLA CPU
lowers log10 through its own polynomial approximations, TPU through
different ones again, and a 1-ulp drift flips any quality that lands
within a ulp of a .5 boundary. So the device never computes a
logarithm. Instead the host precomputes — with the real numpy pipeline
as the oracle — the smallest float32 probability at which each integer
quality step first becomes reachable. The final quality is a monotone
step function of max_prob with at most max_base_quality steps, so on
device a quality is just a count of thresholds <= max_prob: pure IEEE
comparisons, bit-exact on every backend by construction.

The device epilogue (plain XLA: compare + sum) emits two uint8 planes
— base ids and Phred qualities — shrinking D2H per pack from
8 bytes/position (int32 ids + f32 max_prob) to 2.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax.numpy as jnp
import numpy as np

from deepconsensus_tpu.calibration import lib as calibration_lib

# The host epilogue's error-probability floor (runner._finalize_sync).
MIN_ERROR_PROB = 1e-12

# uint8 output plane: the largest quality the device contract can emit.
MAX_DEVICE_QUALITY = 255

# Verification probes per threshold build (vectorized, ~milliseconds):
# a uniform f32 sweep of [0, 1] plus a log-spaced cluster hugging
# p -> 1 where the quality curve is steepest.
_VERIFY_LINEAR = 1 << 16
_VERIFY_LOG = 1 << 14


def host_quality_reference(
    max_prob: np.ndarray,
    calibration_values: calibration_lib.QualityCalibrationValues,
    max_base_quality: int,
) -> np.ndarray:
  """The host epilogue, verbatim (runner._finalize_sync's tail).

  This is the oracle the threshold table is bisected against; it must
  stay operation-for-operation identical to the host fallback path —
  including dtype promotion inside calibrate_quality_scores — or the
  byte-identity contract silently breaks.
  """
  max_prob = np.asarray(max_prob)
  error_prob = np.maximum(1.0 - max_prob, MIN_ERROR_PROB)
  quality = -10.0 * np.log10(error_prob)
  if calibration_values.enabled:
    quality = calibration_lib.calibrate_quality_scores(
        quality, calibration_values)
  quality = np.minimum(quality, max_base_quality)
  quality = np.round(quality, decimals=0).astype(np.int32)
  return np.maximum(quality, 0)


def calibration_is_monotone(
    calibration_values: calibration_lib.QualityCalibrationValues) -> bool:
  """True when the calibrated quality is non-decreasing in the raw
  quality — the precondition for representing the prob->quality map as
  a threshold table. q*w+b applies above the threshold (everywhere
  when the threshold is 0), so monotonicity needs w >= 0 and no
  downward jump where the transform kicks in."""
  cv = calibration_values
  if not cv.enabled:
    return True
  if cv.w < 0:
    return False
  if cv.threshold > 0 and cv.threshold * cv.w + cv.b < cv.threshold:
    return False
  return True


def _bits(p: np.ndarray) -> np.ndarray:
  return np.asarray(p, np.float32).view(np.uint32).astype(np.int64)


def _from_bits(bits: np.ndarray) -> np.ndarray:
  return bits.astype(np.uint32).view(np.float32)


def quality_thresholds(
    calibration_values: calibration_lib.QualityCalibrationValues,
    max_base_quality: int,
) -> Optional[np.ndarray]:
  """Exact f32 probability thresholds for the device quality plane.

  thresholds[k-1] is the smallest float32 p in [0, 1] with
  host_quality_reference(p) >= k, found by bisection over the f32 bit
  lattice (non-negative floats are monotone in their bit patterns), so
  `sum(p >= thresholds)` reproduces the host integer exactly for every
  representable probability. Returns None when the map is not
  device-representable — non-monotone calibration, a top quality past
  the uint8 plane, or (defensively) a failed verification sweep — and
  the caller falls back to the host epilogue.
  """
  if not calibration_is_monotone(calibration_values):
    return None
  oracle = functools.partial(
      host_quality_reference,
      calibration_values=calibration_values,
      max_base_quality=max_base_quality)
  q_top = int(oracle(np.float32([1.0]))[0])
  if q_top > MAX_DEVICE_QUALITY:
    return None
  if q_top == 0:
    thresholds = np.zeros((0,), np.float32)
  else:
    ks = np.arange(1, q_top + 1, dtype=np.int64)
    # Invariant: oracle(lo) < k <= oracle(hi), over bit patterns.
    lo = np.full(q_top, -1, np.int64)  # one below bits(0.0) == 0
    hi = np.full(q_top, int(_bits(np.float32([1.0]))[0]), np.int64)
    while int((hi - lo).max()) > 1:
      active = (hi - lo) > 1
      mid = np.where(active, (lo + hi) // 2, hi)
      ge = oracle(_from_bits(mid)) >= ks
      hi = np.where(active & ge, mid, hi)
      lo = np.where(active & ~ge, mid, lo)
    thresholds = _from_bits(hi)
  if not _verify_thresholds(thresholds, oracle):
    return None  # pragma: no cover - defensive; bisection is exact
  return thresholds


def _verify_thresholds(thresholds: np.ndarray, oracle) -> bool:
  """Belt-and-braces sweep: the threshold count must match the oracle
  on a dense probe set evaluated at realistic (vectorized) array sizes,
  including every threshold's bit neighbourhood."""
  probes = [
      np.linspace(0.0, 1.0, _VERIFY_LINEAR, dtype=np.float32),
      (1.0 - np.logspace(-12, 0, _VERIFY_LOG)).astype(np.float32),
  ]
  if thresholds.size:
    bits = _bits(thresholds)[:, None] + np.arange(-2, 3)[None, :]
    bits = np.clip(bits, 0, int(_bits(np.float32([1.0]))[0]))
    probes.append(_from_bits(bits.ravel()))
  p = np.unique(np.concatenate(probes))
  p = p[(p >= 0.0) & (p <= 1.0)]
  counted = (p[:, None] >= thresholds[None, :]).sum(axis=1).astype(np.int32)
  return bool(np.array_equal(counted, oracle(p)))


# ---------------------------------------------------------------------------
# Device epilogue.
# ---------------------------------------------------------------------------


def phred_epilogue(
    preds: jnp.ndarray,
    thresholds: np.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Softmax preds [B, L, V] -> (ids uint8 [B, L], quals uint8 [B, L]).

  ids is the same argmax the split outputs shipped (first-index ties);
  quals counts how many precomputed thresholds the per-position max
  prob clears — exactly host_quality_reference, with no device
  transcendentals (see module docstring).
  """
  thr = jnp.asarray(thresholds, jnp.float32)
  ids = jnp.argmax(preds, axis=-1).astype(jnp.uint8)
  max_prob = jnp.max(preds, axis=-1)
  quals = jnp.sum(
      max_prob[..., None] >= thr[None, None, :], axis=-1
  ).astype(jnp.uint8)
  return ids, quals
