"""Threshold-table exactness for the device-resident output plane.

ops/output_plane.py bisects, against the real host epilogue as oracle,
the smallest f32 probability at which each integer quality becomes
reachable; the device then computes a quality as a count of cleared
thresholds (pure IEEE comparisons, no transcendentals). These tests
pin the oracle/threshold equivalence over dense f32 probes, the
non-representable fallbacks, and the device epilogue against the
host oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from deepconsensus_tpu.calibration import lib as calibration_lib
from deepconsensus_tpu.ops import output_plane


def _probes(thresholds, n_random=100_000, seed=0):
  """Dense f32 probe set: uniform randoms, a near-1 log cluster where
  the quality curve is steepest, and every threshold's bit
  neighbourhood (the exact boundaries the bisection pinned)."""
  rng = np.random.default_rng(seed)
  parts = [
      rng.random(n_random, dtype=np.float32),
      (1.0 - np.logspace(-12, 0, 4096)).astype(np.float32),
      np.float32([0.0, 1.0]),
  ]
  if thresholds.size:
    bits = output_plane._bits(thresholds)[:, None] + np.arange(-2, 3)
    bits = np.clip(bits, 0, int(output_plane._bits(np.float32([1.0]))[0]))
    parts.append(output_plane._from_bits(bits.ravel()))
  p = np.concatenate(parts)
  return p[(p >= 0.0) & (p <= 1.0)]


@pytest.mark.parametrize('calibration,maxq', [
    ('skip', 93),
    ('0,0.9,2.5', 93),     # threshold 0: transform everywhere
    ('15,1.1,2', 93),      # thresholded, monotone at the seam
    ('10,0.5,30', 90),     # compressive but still monotone
    ('skip', 40),          # low clamp: every step near the top
])
def test_threshold_count_matches_host_oracle(calibration, maxq):
  cv = calibration_lib.parse_calibration_string(calibration)
  thresholds = output_plane.quality_thresholds(cv, maxq)
  assert thresholds is not None
  # thresholds[k-1] is the SMALLEST f32 with oracle >= k: exact at the
  # threshold, one ulp below must fall short.
  ks = np.arange(1, thresholds.size + 1)
  oracle = output_plane.host_quality_reference(thresholds, cv, maxq)
  assert np.all(oracle >= ks)
  # One-ulp-below must fall short (skip thresholds already at p=0.0 —
  # a quality reachable everywhere has no "below", and bits-1 of 0
  # is not a float).
  bits = output_plane._bits(thresholds)
  positive = bits > 0
  below = output_plane._from_bits(bits[positive] - 1)
  below_q = output_plane.host_quality_reference(below, cv, maxq)
  assert np.all(below_q < ks[positive])
  # Count-of-cleared-thresholds == host integer on a dense probe set.
  p = _probes(thresholds)
  counted = (p[:, None] >= thresholds[None, :]).sum(axis=1)
  np.testing.assert_array_equal(
      counted.astype(np.int32),
      output_plane.host_quality_reference(p, cv, maxq))


def test_non_monotone_calibration_not_representable():
  # w < 0: quality decreases in max_prob — no threshold table exists.
  cv = calibration_lib.parse_calibration_string('0,-1,50')
  assert not output_plane.calibration_is_monotone(cv)
  assert output_plane.quality_thresholds(cv, 93) is None
  # Downward jump at the seam: 15*1.1-3 = 13.5 < 15.
  cv = calibration_lib.parse_calibration_string('15,1.1,-3')
  assert not output_plane.calibration_is_monotone(cv)
  assert output_plane.quality_thresholds(cv, 93) is None


def test_top_quality_past_uint8_plane_not_representable():
  # maxq clamp above 255 with an amplifying calibration: the top
  # quality exceeds what the uint8 plane can carry.
  cv = calibration_lib.parse_calibration_string('0,3,0')
  assert output_plane.calibration_is_monotone(cv)
  assert output_plane.quality_thresholds(cv, 400) is None
  # The same calibration under the uint8 ceiling is fine.
  assert output_plane.quality_thresholds(cv, 93) is not None


def _soft_preds(b=8, length=16, vocab=5, seed=3):
  rng = np.random.default_rng(seed)
  logits = rng.normal(size=(b, length, vocab)).astype(np.float32)
  e = np.exp(logits - logits.max(-1, keepdims=True))
  return (e / e.sum(-1, keepdims=True)).astype(np.float32)


@pytest.mark.parametrize('calibration,maxq', [
    ('skip', 93), ('15,1.1,2', 93), ('skip', 40),
])
def test_phred_epilogue_matches_host_oracle(calibration, maxq):
  cv = calibration_lib.parse_calibration_string(calibration)
  thresholds = output_plane.quality_thresholds(cv, maxq)
  preds = _soft_preds()
  ids, quals = output_plane.phred_epilogue(jnp.asarray(preds), thresholds)
  assert ids.dtype == jnp.uint8 and quals.dtype == jnp.uint8
  np.testing.assert_array_equal(np.asarray(ids), preds.argmax(-1))
  np.testing.assert_array_equal(
      np.asarray(quals, np.int32),
      output_plane.host_quality_reference(preds.max(-1), cv, maxq))
