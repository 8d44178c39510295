"""Share of the device's busy time that the selective scan takes: device
seconds in scope `ssm_scan` / busy seconds, in the traced window. Says how
much of the cell the new mechanism is. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  if not r.on_chip:
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  seconds = scope_roofline.scope_seconds(r, 'ssm_scan')
  if not busy or not seconds:
    return None
  return 100.0 * seconds / busy
