"""A part of the forward's share of its roofline, read by device scope.

The program names the parts of its forward with `jax.named_scope`
(docs/observability.md), and the trace carries the scope of every device
operation (`xplane.scope_seconds`). The family says what the part needs a
pack (`part_work(shape, batch, part)` -> FLOPs and bytes, all layers
together); the least time for that on the chip's peaks, times the packs
the window ran, over the seconds the device spent in the scope, is the
share. Nothing to read (a program without the scope, a family without the
work, no chip) is None, never a zero.
"""
from __future__ import annotations


def scope_pattern(scope: str) -> str:
  """The scope as one whole step of an operation's name path."""
  return rf'(^|/){scope}(/|$)'


def scope_seconds(r, scope: str) -> float:
  lo, hi = r.trace_window
  return r.xplane.scope_seconds(r.planes, lo, hi, scope_pattern(scope))


def read(r, part: str, scope: str):
  """Percent of the roofline, or None."""
  part_work = getattr(r.work, 'part_work', None)
  packs = r.result['counters'].get('n_packs')
  if not r.on_chip or r.peaks is None or part_work is None or not packs:
    return None
  seconds = scope_seconds(r, scope)
  if not seconds:
    return None
  need = part_work(r.shape, r.batch, part)
  least = max(need['flops'] / r.peaks['bf16_flops_per_s'],
              need['bytes'] / r.peaks['hbm_bytes_per_s'])
  return 100.0 * least * packs / (seconds * r.chips)
