"""Shared helpers for the Pallas TPU kernels."""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Dict, Optional

import jax

# How many pallas_call sites resolved each way in this process (counted
# when a call is traced, i.e. once per compiled shape). The run and
# train sidecars carry these through execution_report() so a kernel
# that quietly ran in the interpreter on a TPU host is visible.
_resolved: collections.Counter = collections.Counter()


def on_tpu() -> bool:
  return jax.default_backend() == 'tpu'


def resolve_interpret(interpret: Optional[bool]) -> bool:
  """None -> interpret everywhere but real TPU, so the same flag runs
  the kernels under CPU tests and the virtual mesh."""
  if interpret is None:
    interpret = not on_tpu()
  _resolved['interpret' if interpret else 'compiled'] += 1
  return bool(interpret)


# Set while a caller traces a forward-only program that one device runs
# whole. Nothing else may take a kernel on its own initiative: XLA cannot
# partition a Mosaic custom call over a mesh, an exported artifact must
# not carry one, and the kernels chosen by the code have no gradient.
_tracing = threading.local()


@contextlib.contextmanager
def single_device_inference(holds: bool = True):
  """Declares, for the trace inside the block, that the program is
  inference for one device: no mesh, no export, no gradient. The model
  reads it through `may_choose_kernels()`; `holds=False` is the
  explicit "no" of a caller that knows its mesh."""
  before = getattr(_tracing, 'single_device_inference', False)
  _tracing.single_device_inference = bool(holds)
  try:
    yield
  finally:
    _tracing.single_device_inference = before


def may_choose_kernels() -> bool:
  """True where code may route to a Mosaic kernel no option asked for:
  on a TPU, inside a trace declared `single_device_inference`."""
  return bool(
      getattr(_tracing, 'single_device_inference', False) and on_tpu())


def execution_report() -> Dict[str, Any]:
  """Where this process really runs: the device as JAX reports it and
  how the Pallas calls traced so far were resolved."""
  devices = jax.devices()
  return {
      'platform': devices[0].platform,
      'device_kind': devices[0].device_kind,
      'device_count': len(devices),
      'pallas_interpret_default': int(not on_tpu()),
      'n_pallas_calls_compiled': _resolved['compiled'],
      'n_pallas_calls_interpret': _resolved['interpret'],
  }


# Scoped-VMEM ceiling for the batch-tiled kernels (fused front end,
# ragged front end, fused encoder block). The compiler's default scope
# is 16 MiB; at the production 280/2048 shape and tile=8 the f32
# encoder block asks for 20.66 MiB and the 200-wide ragged front end
# for more than 16. 48 MiB stays far under the 128 MiB a v5e core has.
BATCH_TILE_VMEM_LIMIT_BYTES = 48 << 20

# Scoped VMEM for the grouped products (ops/grouped_product.py): a group's
# matrices stay while its row tiles pass, two of them for gate and up, each
# buffered twice. At hidden 2048 x width 768 that is 12 MiB of matrices, 4
# of row tiles of 512 and 3 of outputs and routing weights: the call
# compiles within 20 MiB and not within the default 16.
GROUPED_PRODUCT_VMEM_LIMIT_BYTES = 32 << 20

# Scoped VMEM for the experts' combine (ops/moe_combine.py): two buffers of
# a tile's runs in bfloat16 rows of 4 KiB at hidden 2048, 128 * k rows and
# up to 14 rows of other tokens a run: 2,560 rows at k = 6 over 128 groups
# (2 x 10 MiB), 5,120 at k = 10 over 256 (2 x 20 MiB); the accumulator, a
# product, the places along the lanes and the blocks' double buffers are 4
# MiB more (moe_combine.vmem_bytes: 23.5 / 43.7 MiB).
COMBINE_VMEM_LIMIT_BYTES = 56 << 20

# Scoped VMEM for the latent attention's kernel (ops/latent_attention.py):
# a step's blocks of 400 rows are 2.7 MiB at four heads of the published
# sizes (q 0.4 + 0.2, k and v 0.8, the placed rotary keys 0.8, o 0.4),
# twice each for the pipeline where their index changes, and what of its
# 16 head-windows' float32 scores and weights the register file cannot
# hold side by side. The call compiles within 12 MiB at twice the
# windows; the limit is the batch-tiled kernels'.
LATENT_ATTENTION_VMEM_LIMIT_BYTES = BATCH_TILE_VMEM_LIMIT_BYTES

# Scoped VMEM for the grouped-head attention's kernel
# (ops/grouped_attention.py): a step's blocks of 400 rows at a group of 16
# heads of 128 are q and o 1.6 MiB each, twice each for the pipeline, k, v
# and the two tables 0.6, the rotated q and k 1.7, and what of its 64
# head-windows' float32 scores (3.4 MiB) and weights the register file
# cannot hold side by side: about 14 MiB, too near the default 16 to
# leave it; the limit is the batch-tiled kernels'.
GROUPED_ATTENTION_VMEM_LIMIT_BYTES = BATCH_TILE_VMEM_LIMIT_BYTES

# Scoped VMEM for the selective scan's kernel (ops/selective_scan.py): at
# four windows of L=100 and 1,024 channels a step, Delta, Delta x and y of
# both directions in float32 are 9.4 MiB, the five streams in and y out
# twice each for the pipeline 9.4 MiB; beyond the default 16.
SSM_SCAN_VMEM_LIMIT_BYTES = BATCH_TILE_VMEM_LIMIT_BYTES


def batch_tile_compiler_params(
    vmem_limit_bytes: int = BATCH_TILE_VMEM_LIMIT_BYTES):
  """Mosaic parameters for a kernel whose 1-D grid walks independent
  tiles of windows."""
  from jax.experimental.pallas import tpu as pltpu

  return pltpu.CompilerParams(
      dimension_semantics=('parallel',),
      vmem_limit_bytes=vmem_limit_bytes)
