"""The selective scan's share of its roofline: what the scan of both
directions with its D terms and gate needs a pack (the family's
`part_work(..., 'ssm_scan')`: six operations a state cell, position and
direction as FLOPs; x, dt and z in, B and C in, y out as bytes, which bound
the roofline) x packs in the traced window / device seconds in scope
`ssm_scan` (the scan's call inside `mamba`). The kernel is bound by its
vector work, not by those bytes, so the share reads well below 100 where
the vector unit is busy. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'ssm_scan', 'ssm_scan')
