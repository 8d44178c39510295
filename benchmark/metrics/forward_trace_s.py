"""Seconds of set-up that JAX spent tracing the forward: the union of the
`jit_trace` spans (obs/compiles.py) that arrived under a `forward_launch`
and ended before the window started. The functions the forward calls are
traced inside its own trace and carry the same stage, so the union counts
them once. The helpers below serve `forward_lower_s`, `forward_compile_s`
and `compile_ms_in_window` too."""

COMPILE_SPANS = ('jit_trace', 'jit_lower', 'xla_compile')


def union_seconds(spans, lo, hi):
  """Seconds of [lo, hi) that the spans cover, nested and overlapping ones
  counted once."""
  total, end = 0.0, lo
  for a, b, _ in sorted(spans, key=lambda s: s[0]):
    a, b = max(a, end), min(b, hi)
    if b > a:
      total += b - a
      end = b
  return total


def forward_setup_seconds(r, name):
  """The union of the forward's `name` spans of set-up; nothing where the
  program wrote none (a program without the instrument)."""
  start = r.span_window[0]
  spans = [s for s in r.spans.get(name, ())
           if s[2].get('under') == 'forward_launch' and s[1] <= start]
  if not spans:
    return None
  return union_seconds(spans, float('-inf'), start)


def read(r):
  return forward_setup_seconds(r, 'jit_trace')
