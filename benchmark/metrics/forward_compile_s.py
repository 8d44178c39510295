"""Seconds of set-up in the backend's compile of the forward: the union of
the `xla_compile` spans under a `forward_launch` that ended before the window
started. On a warm machine that is the read of the executable out of the
persistent cache (`args.cache_hit` true), in a fresh checkout XLA's compile
itself: the driver's `first_setup_s` run is explained by this one."""
from benchmark.metrics import forward_trace_s


def read(r):
  return forward_trace_s.forward_setup_seconds(r, 'xla_compile')
