"""The routed experts' share of their roofline where a chip holds all 64
experts of each layer and no shared expert stands beside them:
`moe128_roofline` under the name of the cell whose experts are 64 a layer,
6,400 rows an expert a pack of 512 (that entry lists one cell, and no entry
may be edited). What router, dispatch, grouped products and combine need
(the family's `moe_work` on the window's COUNTED held assignments, the held
experts' bytes once a turn) / device seconds in scope `moe`. An uneven
router cannot read over 100%: the work is what was routed. Only on a chip,
and only from a program that counts its assignments."""
from benchmark.metrics import moe128_roofline


def read(r):
  return moe128_roofline.read(r)
