"""Seconds of set-up that JAX spent lowering the forward's jaxpr to an MLIR
module (where Pallas kernels become Mosaic): the union of the `jit_lower`
spans under a `forward_launch` that ended before the window started."""
from benchmark.metrics import forward_trace_s


def read(r):
  return forward_trace_s.forward_setup_seconds(r, 'jit_lower')
