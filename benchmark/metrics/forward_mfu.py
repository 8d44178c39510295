"""The whole step's share of the chip's bf16 peak: matmul FLOPs a window
needs (from shapes, benchmark/lib/work.py) x windows delivered in the
traced window / (its seconds x peak x chips). Only on a chip."""


def read(r):
  if not r.on_chip or r.peaks is None or not r.window_s:
    return None
  flops = r.work.flops_per_window(r.shape)['total']
  done = r.result['windows_delivered']
  return 100.0 * flops * done / (
      r.window_s * r.peaks['bf16_flops_per_s'] * r.chips)
