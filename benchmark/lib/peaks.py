"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

_V5E = {
    'bf16_flops_per_s': 197e12,
    'int8_ops_per_s': 393e12,
    'hbm_bytes_per_s': 819e9,
    'hbm_bytes': 16e9,
    'source': 'cloud.google.com/tpu/docs/v5e (system architecture)',
}

PEAKS = {
    'TPU v5 lite': _V5E,
    'TPU v5e': _V5E,
}


def peaks_for(device_kind: str) -> dict:
  try:
    return PEAKS[device_kind]
  except KeyError:
    raise KeyError(
        f'no published peaks for device kind {device_kind!r}; add a row '
        'with its source to benchmark/lib/peaks.py') from None
