"""Share of the device's busy time that the Mamba mixers take: device
seconds in scope `mamba` (in_proj, the convolution, x_proj, dt_proj, the
scan and out_proj) / busy seconds, in the traced window. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  if not r.on_chip:
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  seconds = scope_roofline.scope_seconds(r, 'mamba')
  if not busy or not seconds:
    return None
  return 100.0 * seconds / busy
