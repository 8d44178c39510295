"""Median device duration of the forward program's executions in the
traced window (`jit_forward` on the device plane's `XLA Modules` line).
A median of pieces: a number of the forward layer only."""


def read(r):
  value = r.xplane.forward_median_seconds(r.planes, *r.trace_window)
  return None if value is None else 1e3 * value
