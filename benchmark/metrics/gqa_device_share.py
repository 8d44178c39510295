"""Share of the device's busy time that the grouped-head softmax attention
operator takes: device seconds in scope `softmax` (score product, float32
softmax, values) / busy seconds, in the traced window. Says how much of the
cell the operator is. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  if not r.on_chip:
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  seconds = scope_roofline.scope_seconds(r, 'softmax')
  if not busy or not seconds:
    return None
  return 100.0 * seconds / busy
