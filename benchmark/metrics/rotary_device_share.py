"""Share of the device's busy time that the rotation of q and k takes:
device seconds in scope `rotary` (the layer type's cos and sin tables,
YaRN's magnitude among them, applied in float32 and cast back) / busy
seconds, in the traced window. A program without the scope (one from
before it) reads nothing. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  if not r.on_chip:
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  seconds = scope_roofline.scope_seconds(r, 'rotary')
  if not busy or not seconds:
    return None
  return 100.0 * seconds / busy
