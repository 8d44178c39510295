"""The program's stage spans (obs/trace.py), read back from its JSONL file.

The program writes Chrome trace events, one per line, when its tracer is
configured with a path; the harness configures it in the traced run only
and reads the file once the window has closed. Times are wall-clock
seconds (`time.time()`), the clock of the harness's own stamps.
"""
from __future__ import annotations

import json
from typing import Dict, List, Tuple

Span = Tuple[float, float, dict]  # start_s, end_s, args


def read_spans(path: str) -> Dict[str, List[Span]]:
  spans: Dict[str, List[Span]] = {}
  try:
    with open(path) as f:
      lines = f.readlines()
  except FileNotFoundError:
    return spans
  for line in lines:
    line = line.strip().rstrip(',')
    if not line.startswith('{'):
      continue
    ev = json.loads(line)
    if ev.get('ph') != 'X':
      continue
    t0 = ev['ts'] / 1e6
    spans.setdefault(ev['name'], []).append(
        (t0, t0 + ev['dur'] / 1e6, ev.get('args', {})))
  return spans


def seconds_in(spans: List[Span], lo: float, hi: float) -> float:
  """Sum of the spans' time inside [lo, hi) (no union: spans of one
  stage on one thread do not overlap)."""
  return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b, _ in spans)
