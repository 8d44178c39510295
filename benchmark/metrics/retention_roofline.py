"""The retention operator's share of its roofline: what the two-direction
quadratic form needs a pack (the family's `part_work(..., 'retention')`:
scores and values FLOPs; q, k, v, log g in and y out as bytes, which
bound it) x packs in the traced window / device seconds in scope
`retention` (the operator alone, inside `attention`). Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'retention', 'retention')
