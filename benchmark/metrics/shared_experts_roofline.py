"""The shared experts' share of their roofline: what the m shared experts
need a pack (the family's `part_work(..., 'shared_expert')`: three products
of the hidden size by m x the expert width, which bound it; their weights
once and the stream in and out as bytes) x packs in the traced window /
device seconds in scope `shared_expert` (inside `ffn`, beside `moe`). Half
the cell's FLOPs: it guards the one wide product the program runs against
m narrow ones. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'shared_expert', 'shared_expert')
