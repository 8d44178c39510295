"""Share of the device's busy time that the routed experts take: device
seconds in scope `moe` (router, dispatch, grouped products, combine; the
compiler's `ragged-dot` calls with it, as `moe_roofline` says) / busy
seconds, in the traced window. Says how much of the cell the expert layer
is. Only on a chip."""
from benchmark.metrics import moe_roofline


def read(r):
  if not r.on_chip:
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  seconds = moe_roofline.moe_seconds(r)
  if not busy or not seconds:
    return None
  return 100.0 * seconds / busy
