"""1 - (union of the device's busy intervals / the traced window), from
the device plane of the profiler's trace. Only on a chip."""


def read(r):
  if not r.on_chip or not r.xplane.device_planes(r.planes):
    return None
  lo, hi = r.trace_window
  busy = r.xplane.busy_seconds(r.planes, lo, hi)
  return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
