"""The gated delta rule's share of its roofline: what the chunked rule
needs a pack in both directions (the family's `part_work(..., 'gdn')`:
key.key, query.key, substitution and read over triangles of pairs; q, k,
v, g, beta in and o out as bytes) x packs in the traced window / device
seconds in scope `gdn` (the operator alone, inside `attention`). A form
that inverts the system by dense products does more than this counts, and
reads low for it. Only on a chip."""
from benchmark.lib import scope_roofline


def read(r):
  return scope_roofline.read(r, 'gdn', 'gdn')
