"""The grouped-head softmax attention operator's share of its roofline over
32 query / 4 key-value heads: what the score product and the values need a
pack (the family's `part_work(..., 'gqa')`: 2 L^2 D multiply-adds a query
head each; q, k, v in and o out once, in bfloat16, as bytes) x packs in the
traced window / device seconds in scope `softmax` (the operator alone,
inside `attention`: the projections and the rotation, scope `rotary`, are
outside it). Window and full layers alike: at this length the window
masks nothing. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'gqa', 'softmax')
