"""The latent attention operator's share of its roofline: what both score
products and the values need a pack (the family's `part_work(..., 'latent')`:
2 L^2 (nope + rope + value) multiply-adds a head; q, the two keys, v in and o
out once, in bfloat16, as bytes) x packs in the traced window / device
seconds in scope `latent` (the operator alone, inside `attention`: the five
projections, the latent's norm and the rotary are outside it). Only on a
chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'latent', 'latent')
