"""The feed-forward branch's share of its roofline: what the gated
feed-forward needs a pack (the family's `part_work(..., 'ffn')`: three
matrix products, which bound it; weights once and the stream in and out
as bytes) x packs in the traced window / device seconds in scope `ffn`
(norm, products, gate and residual add). Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'ffn', 'ffn')
