"""Milliseconds of the window in which JAX traced, lowered or compiled
anything, whatever stage it was under: the union of `jit_trace`, `jit_lower`
and `xla_compile` spans inside the window. Every shape is warmed up in
set-up, so this reads 0.0 and agrees with the harness's `compiles_in_window`;
a step that recompiles shows here in milliseconds. Nothing where the file has
no `forward_launch`, or no compile span at all (set-up always compiles, so a
file without one comes from a program without the instrument)."""
from benchmark.metrics import forward_trace_s


def read(r):
  spans = [s for name in forward_trace_s.COMPILE_SPANS
           for s in r.spans.get(name, ())]
  if not spans or not r.spans.get('forward_launch'):
    return None
  lo, hi = r.span_window
  return 1e3 * forward_trace_s.union_seconds(spans, lo, hi)
